"""Gated delta-rule linear attention: a layer whose cache is a fixed-size
recurrent state, not a row a position.

Per head h (d_k keys, d_v values), with x the layer's input and no bias:

    q~, k~, v~ = W_q x, W_k x, W_v x      each channel through a causal
                                           depthwise convolution over the
                                           last K positions, then SiLU
    q_t = q~ / |q~|_2 * d_k^-1/2           k_t = k~ / |k~|_2
    beta_t  = sigmoid(W_b x)               (x 2 with `allow_neg_eigval`)
    alpha_t = exp(-exp(A_log) * softplus(W_a x + dt_bias))
    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
    o_t = S_t q_t                          S in R^{d_v x d_k}, S_0 = 0
    y   = W_o [ RMSNorm_{d_v}(o_t) * SiLU(W_g x) ]

The recurrence comes in three forms that compute the same thing
(`tests/test_linear_attention.py` holds them to each other and to the plain
reference):

* `delta_rule_recurrent`: position by position over a whole sequence (the
  uncached forward);
* `delta_rule_chunked`: a prompt chunk from a carried state. Inside a block
  of `CHUNK` positions the rule is a unit lower-triangular system: with
  g = cumsum(log alpha), the pseudo-values U solve
  (I + A) U = beta (V - exp(g) K S_0^T), A_ij = beta_i exp(g_i - g_j)
  (k_i . k_j) for j < i, and then O = exp(g) Q S_0^T + (decay * Q K^T) U,
  S_C = exp(g_C) S_0 + (exp(g_C - g) U)^T K. (I + A)^-1 is built for every
  block of the chunk at once (forward substitution on 16 x 16 diagonal
  blocks, merged pairwise), so only the state's hand-over from block to
  block is sequential;
* `delta_rule_step`: one position (decode).

What a sequence carries between dispatches is `(window, state)`: the last
K - 1 inputs of the convolution (`[K-1, channels]`, the activations' dtype)
and S (`[heads, d_v, d_k]`, float32). Positions at or past `valid_len` (a
prompt chunk's bucket padding) leave both untouched: alpha 1, beta 0, and
the window is cut at `valid_len`.

All of the rule runs in float32: the state is an accumulator over the whole
sequence, and the triangular system loses its meaning in bfloat16.
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
_HI = jax.lax.Precision.HIGHEST
#: positions a block of the chunked form solves together
CHUNK = 64
_BASE = 16
#: eps of the keys' and queries' L2 norm (x / sqrt(sum x^2 + eps))
L2_EPS = 1e-6


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HI, preferred_element_type=F32)


def causal_conv_silu(x, window, w):
    """x [s, C] new inputs, window [K-1, C] the inputs before them, w [K, C]:
    y_t = silu(sum_j w[j] * x_{t-K+1+j}) in float32, and the inputs laid end
    to end `[K-1+s, C]` (the next window is a slice of it)."""
    k, s = w.shape[0], x.shape[0]
    full = jnp.concatenate([window.astype(x.dtype), x], axis=0)
    y = sum(full[j:j + s].astype(F32) * w[j].astype(F32) for j in range(k))
    return jax.nn.silu(y), full


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def delta_rule_step(q, k, v, g, beta, state):
    """One position: q, k [H, dk], v [H, dv], g = log alpha and beta [H],
    state [H, dv, dk]. Returns (o [H, dv], the new state). Products and
    sums on the vector unit: exact float32, no matrix unit's rounding."""
    state = state * jnp.exp(g)[:, None, None]
    sk = jnp.sum(state * k[:, None, :], axis=-1)
    state = state + (beta[:, None] * (v - sk))[:, :, None] * k[:, None, :]
    return jnp.sum(state * q[:, None, :], axis=-1), state


def delta_rule_recurrent(q, k, v, g, beta, state):
    """The rule position by position over q, k [s, H, dk], v [s, H, dv],
    g, beta [s, H] from `state`. Returns (o [s, H, dv], the final state)."""
    def body(st, x):
        o, st = delta_rule_step(*x, st)
        return st, o

    state, o = jax.lax.scan(body, state, (q, k, v, g, beta))
    return o, state


def _unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower-triangular a [..., n, n], n a multiple
    of 16 times a power of two: forward substitution row by row on the
    16 x 16 diagonal blocks (all of them at once), then pairs of blocks
    merged by inv([[P, 0], [R, Q]]) = [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]."""
    n = a.shape[-1]
    nb = n // _BASE
    diag = jnp.stack([a[..., i * _BASE:(i + 1) * _BASE,
                        i * _BASE:(i + 1) * _BASE] for i in range(nb)],
                     axis=-3)                        # [..., nb, 16, 16]
    eye = jnp.eye(_BASE, dtype=F32)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (_BASE,))]
    for i in range(1, _BASE):
        prev = jnp.stack(rows, axis=-2)              # [..., nb, i, 16]
        rows.append(eye[i] - jnp.sum(diag[..., i, :i, None] * prev,
                                     axis=-2))
    inv = jnp.stack(rows, axis=-2)
    blocks = [inv[..., i, :, :] for i in range(nb)]
    m = _BASE
    while len(blocks) > 1:
        merged = []
        for p in range(0, len(blocks), 2):
            top, bot = blocks[p], blocks[p + 1]
            r0, c0 = (p + 1) * m, p * m
            low = -_mm("...ij,...jk->...ik",
                       _mm("...ij,...jk->...ik", bot,
                           a[..., r0:r0 + m, c0:c0 + m]), top)
            merged.append(jnp.concatenate([
                jnp.concatenate([top, jnp.zeros_like(top)], axis=-1),
                jnp.concatenate([low, bot], axis=-1)], axis=-2))
        blocks, m = merged, 2 * m
    return blocks[0]


def delta_rule_chunked(q, k, v, g, beta, state, chunk=CHUNK):
    """The rule over q, k [s, H, dk], v [s, H, dv], g, beta [s, H] from
    `state`, in blocks of `chunk` positions (s is padded up to whole blocks
    with alpha 1, beta 0, which leave the state as it is). Returns
    (o [s, H, dv], the final state)."""
    s = q.shape[0]
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1))
                            for t in (q, k, v, g, beta))
    n = (s + pad) // chunk

    def blocks(t):                       # [s, H, ...] -> [n, H, chunk, ...]
        return jnp.swapaxes(t.reshape((n, chunk) + t.shape[1:]), 1, 2)

    q, k, v, g, beta = (blocks(t) for t in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)                              # [n, H, C]
    idx = jnp.arange(chunk)
    seen = idx[:, None] >= idx[None, :]                      # j <= i
    decay = jnp.exp(jnp.where(seen, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))                     # [n, H, C, C]
    kk = _mm("nhid,nhjd->nhij", k, k)
    a = beta[..., :, None] * kk * decay * (idx[:, None] > idx[None, :])
    solve = _unit_lower_inverse(a)                           # (I + A)^-1
    qk = _mm("nhid,nhjd->nhij", q, k) * decay
    eg = jnp.exp(gc)                                         # [n, H, C]
    tail = jnp.exp(gc[..., -1:] - gc)                        # g_C - g_i

    def body(st, x):
        qc, kc, vc, bc, egc, tc, solve_c, qk_c = x
        ks = _mm("hid,hvd->hiv", kc, st)                     # K S_0^T
        u = _mm("hij,hjv->hiv", solve_c,
                bc[..., None] * (vc - egc[..., None] * ks))
        o = egc[..., None] * _mm("hid,hvd->hiv", qc, st) \
            + _mm("hij,hjv->hiv", qk_c, u)
        st = egc[:, -1, None, None] * st \
            + _mm("hiv,hid->hvd", u * tc[..., None], kc)
        return st, o

    state, o = jax.lax.scan(body, state,
                            (q, k, v, beta, eg, tail, solve, qk))
    o = jnp.swapaxes(o, 1, 2)                        # [n, chunk, H, dv]
    o = o.reshape((n * chunk,) + o.shape[2:])
    return o[:s], state


def _split(qkv, ab, a_log, dt_bias, *, heads, dk, dv, neg_eigval):
    """The rule's operands of one sequence from the convolved projections
    qkv [s, H*(2dk+dv)] (float32) and the gates' projections ab [s, 2H]."""
    s = qkv.shape[0]
    q, k, v = jnp.split(qkv, [heads * dk, 2 * heads * dk], axis=-1)
    q = _l2norm(q.reshape(s, heads, dk)) * (1.0 / math.sqrt(dk))
    k = _l2norm(k.reshape(s, heads, dk))
    v = v.reshape(s, heads, dv)
    a, b = jnp.split(ab.astype(F32), 2, axis=-1)
    beta = jax.nn.sigmoid(b) * (2.0 if neg_eigval else 1.0)
    g = -jnp.exp(a_log.astype(F32)) * jax.nn.softplus(
        a + dt_bias.astype(F32))
    return q, k, v, g, beta


def _uncached_impl(qkv, ab, conv_w, a_log, dt_bias, *, heads, dk, dv,
                   neg_eigval):
    """Whole sequences [B, s, ...] from a zero state: o [B, s, H*dv]."""
    def one(qkv, ab):
        window = jnp.zeros((conv_w.shape[0] - 1, qkv.shape[-1]), qkv.dtype)
        y, _ = causal_conv_silu(qkv, window, conv_w)
        o, _ = delta_rule_recurrent(
            *_split(y, ab, a_log, dt_bias, heads=heads, dk=dk, dv=dv,
                    neg_eigval=neg_eigval),
            jnp.zeros((heads, dv, dk), F32))
        return o.reshape(o.shape[0], heads * dv).astype(qkv.dtype)

    return jax.vmap(one)(qkv, ab)


def _cached_impl(qkv, ab, conv_w, a_log, dt_bias, window, state, valid_len,
                 *, heads, dk, dv, neg_eigval):
    """A chunk [B, s, ...] from the carried (window [B, K-1, C], state
    [B, H, dv, dk]); positions >= valid_len change neither. Returns (o
    [B, s, H*dv], the new window, the new state). One position takes the
    step form, more the chunked form."""
    s = qkv.shape[1]
    live = jnp.arange(s) < valid_len

    def one(qkv, ab, window, state):
        y, full = causal_conv_silu(qkv, window, conv_w)
        q, k, v, g, beta = _split(y, ab, a_log, dt_bias, heads=heads, dk=dk,
                                  dv=dv, neg_eigval=neg_eigval)
        g = jnp.where(live[:, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
        st = state.astype(F32)
        if s == 1:
            o, st = delta_rule_step(q[0], k[0], v[0], g[0], beta[0], st)
            o = o[None]
        else:
            o, st = delta_rule_chunked(q, k, v, g, beta, st)
        new_window = jax.lax.dynamic_slice_in_dim(
            full, valid_len, window.shape[0], axis=0)
        return (o.reshape(s, heads * dv).astype(qkv.dtype),
                new_window.astype(window.dtype), st.astype(state.dtype))

    return jax.vmap(one)(qkv, ab, window, state)


class GatedDeltaNet(nn.Layer):
    """The mixer of a linear-attention layer. `forward(x)` runs whole
    sequences from a zero state; `forward(x, cache=(window, state),
    valid_len=n)` runs a chunk from a carried state and returns
    `(y, (window, state))`."""

    def __init__(self, cfg):
        super().__init__()
        h = cfg.hidden_size
        self.heads = nh = cfg.linear_num_heads
        self.dk, self.dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        self.neg_eigval = bool(cfg.linear_allow_neg_eigval)
        kc = cfg.linear_conv_kernel_dim
        std = cfg.initializer_range
        channels = nh * (2 * self.dk + self.dv)

        def normal(s):
            return nn.ParamAttr(initializer=nn.initializer.Normal(0.0, s))

        # q, k and v fused along the output axis, as the attention's
        self.qkv_proj = nn.Linear(h, channels, weight_attr=normal(std),
                                  bias_attr=False)
        self.conv_weight = self.create_parameter(
            [kc, channels],
            default_initializer=nn.initializer.Normal(0.0, 1.0 / kc))
        # the gates: alpha's input a, then beta's input b
        self.ab_proj = nn.Linear(h, 2 * nh, weight_attr=normal(std),
                                 bias_attr=False)
        # alpha = exp(-A softplus(a + dt_bias)): A log-uniform on [1, 16)
        # and dt_bias the inverse softplus of a step log-uniform on
        # [1e-3, 1e-1), the state-space family's initialisation, here at
        # the quantiles of those ranges (no generator at construction)
        quant = (jnp.arange(nh, dtype=F32) + 0.5) / nh
        dt = jnp.exp(math.log(1e-3) + quant * (math.log(1e-1)
                                               - math.log(1e-3)))
        self.A_log = self.create_parameter(
            [nh], default_initializer=nn.initializer.Assign(
                jnp.log(1.0 + 15.0 * quant)))
        self.dt_bias = self.create_parameter(
            [nh], default_initializer=nn.initializer.Assign(
                dt + jnp.log(-jnp.expm1(-dt))))
        self.g_proj = nn.Linear(h, nh * self.dv, weight_attr=normal(std),
                                bias_attr=False)
        self.o_norm = nn.RMSNorm(self.dv, epsilon=cfg.layer_norm_epsilon)
        self.out_proj = nn.Linear(
            nh * self.dv, h, bias_attr=False,
            weight_attr=normal(std / math.sqrt(2 * cfg.num_layers)))

    def forward(self, x, cache=None, valid_len=None):
        b, s = x.shape[0], x.shape[1]
        statics = {"heads": self.heads, "dk": self.dk, "dv": self.dv,
                   "neg_eigval": self.neg_eigval}
        weights = [self.conv_weight, self.A_log, self.dt_bias]
        qkv, ab = self.qkv_proj(x), self.ab_proj(x)
        new_cache = None
        if cache is None:
            o = apply("gated_delta_rule", _uncached_impl,
                      [qkv, ab, *weights], statics)
        else:
            window, state = cache
            if valid_len is None:
                valid_len = ops.full([], s, dtype="int32")
            o, new_window, new_state = apply(
                "gated_delta_rule_cached", _cached_impl,
                [qkv, ab, *weights, window, state, valid_len], statics)
            new_cache = (new_window, new_state)
        o = self.o_norm(ops.reshape(o, [b, s, self.heads, self.dv]))
        gate = ops.reshape(self.g_proj(x), [b, s, self.heads, self.dv])
        y = self.out_proj(ops.reshape(o * F.silu(gate),
                                      [b, s, self.heads * self.dv]))
        return y if cache is None else (y, new_cache)
