"""Latent attention (multi-head latent attention, the DeepSeek-V2 form): a
layer whose cache row is one compressed latent a token, shared by all heads.

With x the layer's input, H heads, a latent of `kv_lora_rank` r, and per
head a `qk_nope_head_dim` d_n without positions, a `qk_rope_head_dim` d_r
that is rotated, and a `v_head_dim` d_v (no bias anywhere):

    q_h      = (W_q x)_h                    [d_n + d_r], RMSNorm over it with
                                            `qk_norm`, the last d_r rotated
    [c, k_r] = W_kva x                      [r], [d_r]: c = RMSNorm_r(c), k_r
                                            rotated, ONE for all heads
    [k_n, v]_h = (W_kvb c)_h                [d_n], [d_v]
    scores   = (q_n . k_n + q_r . k_r) / sqrt(d_n + d_r), causal softmax in
               float32;  o_h = sum p v_h
    o_h     *= sigmoid((W_gate x)_h)        one gate a head (`attn_head_gate`)
    y        = W_o [o_h]_h

What a token leaves in the cache is the row `[c ; rot(k_r)]`, r + d_r wide
(576 values where full keys and values of 32 heads x 128 would be 8192). The
cached forward has two paths that compute the same thing
(`tests/test_latent_attention.py` holds both to the plain reference):

* more than one new position (a prompt chunk): the cached rows are expanded
  through W_kvb into every head's keys and values, and attention runs over
  those, as the uncached forward does;
* one new position (decode): W_kvb is absorbed into the query and the output
  instead. q_n W_kvb,k is a query against the latent itself ([H, r]), the
  probabilities sum the latents ([H, r]) and W_kvb,v takes that sum to the
  head's value: no key or value of any cached token is ever formed, and what
  crosses HBM is the rows as they are stored.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import nn
from .. import ops
from ..core.dispatch import apply
from ..nn import functional as F

F32 = jnp.float32


def _latent_attn_impl(q, new_rows, rows, w_kvb, pos, *, rank, d_nope, d_v):
    """q [B,s,H,d_n+d_r] (normed, rotated), new_rows [B,s,r+d_r] this
    chunk's rows, rows [B,T,r+d_r] the cache (None: this chunk alone, from
    position 0), w_kvb [r, H*(d_n+d_v)], pos the chunk's offset. Returns
    (o [B,s,H,d_v], the cache with the chunk's rows written)."""
    b, s, heads, _ = q.shape
    if rows is None:
        rows, pos = new_rows, 0
    else:
        rows = jax.lax.dynamic_update_slice_in_dim(
            rows, new_rows.astype(rows.dtype), pos, axis=1)
    t = rows.shape[1]
    c, k_r = rows[..., :rank], rows[..., rank:]
    w = w_kvb.reshape(rank, heads, d_nope + d_v)
    w_k, w_v = w[..., :d_nope], w[..., d_nope:]
    q_n, q_r = q[..., :d_nope], q[..., d_nope:]
    scores = jnp.einsum("bshd,btd->bhst", q_r, k_r,
                        preferred_element_type=F32)
    absorbed = s == 1
    if absorbed:
        # the query against the latent itself
        q_c = jnp.einsum("bshd,rhd->bshr", q_n, w_k,
                         preferred_element_type=F32).astype(q.dtype)
        scores = scores + jnp.einsum("bshr,btr->bhst", q_c, c,
                                     preferred_element_type=F32)
    else:
        kv = jnp.einsum("btr,rhd->bthd", c, w,
                        preferred_element_type=F32).astype(q.dtype)
        k_n, v = kv[..., :d_nope], kv[..., d_nope:]
        scores = scores + jnp.einsum("bshd,bthd->bhst", q_n, k_n,
                                     preferred_element_type=F32)
    scores = scores * (1.0 / math.sqrt(q.shape[-1]))
    mask = jnp.arange(t)[None, :] <= pos + jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf),
                           axis=-1).astype(q.dtype)
    if absorbed:
        mixed = jnp.einsum("bhst,btr->bshr", probs, c,
                           preferred_element_type=F32).astype(q.dtype)
        out = jnp.einsum("bshr,rhd->bshd", mixed, w_v,
                         preferred_element_type=F32)
    else:
        out = jnp.einsum("bhst,bthd->bshd", probs, v,
                         preferred_element_type=F32)
    return out.astype(q.dtype), rows


def _uncached_impl(q, new_rows, w_kvb, **sizes):
    return _latent_attn_impl(q, new_rows, None, w_kvb, 0, **sizes)[0]


class LatentAttention(nn.Layer):
    """The mixer of a latent-attention layer. `forward(x, position_ids)`
    attends over the sequence's own rows; `forward(x, position_ids,
    cache=(rows, pos))` writes the chunk's rows at `pos` and returns
    `(y, (rows,))`."""

    def __init__(self, cfg):
        super().__init__()
        h, nh = cfg.hidden_size, cfg.num_heads
        self.heads = nh
        self.rank = r = cfg.kv_lora_rank
        self.d_nope, self.d_rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        self.d_v = cfg.v_head_dim
        self.theta = cfg.rope_theta
        std = cfg.initializer_range
        eps = cfg.layer_norm_epsilon

        def linear(n_in, n_out, s=std):
            return nn.Linear(n_in, n_out, bias_attr=False,
                             weight_attr=nn.ParamAttr(
                                 initializer=nn.initializer.Normal(0.0, s)))

        self.q_proj = linear(h, nh * (self.d_nope + self.d_rope))
        self.kv_a_proj = linear(h, r + self.d_rope)
        self.kv_norm = nn.RMSNorm(r, epsilon=eps)
        self.kv_b_proj = linear(r, nh * (self.d_nope + self.d_v))
        self.q_norm = nn.RMSNorm(self.d_nope + self.d_rope, epsilon=eps) \
            if cfg.qk_norm else None
        self.gate_proj = linear(h, nh) if cfg.attn_head_gate else None
        self.out_proj = linear(nh * self.d_v, h,
                               std / math.sqrt(2 * cfg.num_layers))

    def forward(self, x, position_ids=None, cache=None):
        b, s = x.shape[0], x.shape[1]
        nh, dn, dr = self.heads, self.d_nope, self.d_rope
        if position_ids is None:
            position_ids = ops.expand(
                ops.unsqueeze(ops.arange(s, dtype="int32"), 0), [b, s])
        q = ops.reshape(self.q_proj(x), [b, s, nh, dn + dr])
        if self.q_norm is not None:
            q = self.q_norm(q)
        q_n, q_r = ops.split(q, [dn, dr], axis=-1)
        c, k_r = ops.split(self.kv_a_proj(x), [self.rank, dr], axis=-1)
        q_r, k_r = F.apply_rotary_pos_emb(
            q_r, ops.reshape(k_r, [b, s, 1, dr]), position_ids,
            theta=self.theta)
        q = ops.concat([q_n, q_r], axis=-1)
        new_rows = ops.concat([self.kv_norm(c),
                               ops.reshape(k_r, [b, s, dr])], axis=-1)
        sizes = {"rank": self.rank, "d_nope": dn, "d_v": self.d_v}
        new_cache = None
        if cache is None:
            out = apply("latent_attn", _uncached_impl,
                        [q, new_rows, self.kv_b_proj.weight], sizes)
        else:
            rows, pos = cache
            out, rows = apply("latent_attn_cached", _latent_attn_impl,
                              [q, new_rows, rows, self.kv_b_proj.weight,
                               pos], sizes)
            new_cache = (rows,)
        if self.gate_proj is not None:
            out = out * ops.unsqueeze(F.sigmoid(self.gate_proj(x)), -1)
        y = self.out_proj(ops.reshape(out, [b, s, nh * self.d_v]))
        return y if cache is None else (y, new_cache)
