"""Plain reference of the Olmo-Hybrid decoder: gated delta-rule layers beside
full attention.

Straightforward `jax.numpy` in float32 at matmul precision "highest": one
sequence at a time, no kernel, no cache, no chunking, no batching; it imports
nothing of the program. The layers as `benchmarks/configs/olmo_hybrid_7b.json`
states them (x the block's input, no bias anywhere):

  linear-attention layer, per head h (d_k keys, d_v values)
    q~, k~, v~ = W_q x, W_k x, W_v x; each channel c of the three through
      conv_t = sum_{j<K} w[j, c] * u_{t-K+1+j}   (u_t = 0 for t < 0), SiLU
    q_t = q~_h / |q~_h|_2 * d_k^-1/2,  k_t = k~_h / |k~_h|_2
    beta_t  = 2 sigmoid(W_b x)_h            (`linear_allow_neg_eigval`)
    alpha_t = exp(-exp(A_log_h) softplus((W_a x)_h + dt_bias_h))
    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T,
      S_{-1} = 0;   o_t = S_t q_t
    y = W_o [ RMSNorm_{d_v}(o_t) * SiLU(W_g x)_h ]_h
  full-attention layer
    q, k = RMSNorm(W_q x), RMSNorm(W_k x) over the whole projection,
    causal softmax(q k^T / sqrt(head_dim)) v per head, no positions
  block, both kinds
    h = x + RMSNorm(mixer(x));  out = h + RMSNorm(W_down (SiLU(W_gate h)
    * W_up h));  a final RMSNorm, an untied head

Departures from the published description: the recurrence runs as a
`lax.scan` over positions (position by position, as written above); every
entry of the file's `assumed` is an inference from the family's convention,
and is noted where it is used below.

`quantized=True` is the control of the benchmark's `correct`: both operands
of every matrix multiplication (the projections, attention's two products,
the feed-forward, the head) rounded to float8 by `gpt_ref`'s recipe, the
nearest precision below the bfloat16 the configuration states; the
recurrence's own products stay float32 (they are sums over a state, not
matrix multiplications of weights). `state_dtype="bfloat16"` is the second
control: the state rounded to bfloat16 after every position, which is what
a program that stored it in the cache's dtype would do.

Memory: `served_logits` takes the bfloat16 weight values and upcasts a layer
at a time.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.gpt_ref import F32, _mm, layer_params

LINEAR = "linear_attention"
L2_EPS = 1e-6        # assumed: x / sqrt(sum x^2 + 1e-6), the family's l2norm


def kinds(model):
    period = model["layer_pattern"]
    return [period[i % len(period)] for i in range(model["num_layers"])]


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


def conv_silu(u, w):
    """u [s, C], w [K, C]: causal depthwise convolution over the last K
    positions (assumed: no bias), then SiLU."""
    k, s = w.shape[0], u.shape[0]
    padded = jnp.pad(u, ((k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[j:j + s] * w[j] for j in range(k)))


def delta_rule(q, k, v, alpha, beta, state_dtype=None):
    """The recurrence position by position: q, k [s, H, dk], v [s, H, dv],
    alpha, beta [s, H]; o [s, H, dv]."""
    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(S, x):
        qt, kt, vt, at, bt = x
        decayed = at[:, None, None] * S                      # alpha S_{t-1}
        err = vt - jnp.sum(decayed * kt[:, None, :], -1)     # v - a S k
        S = decayed + bt[:, None, None] * err[:, :, None] * kt[:, None, :]
        if state_dtype == "bfloat16":
            # `reduce_precision`, not a pair of casts: the chip's compiler
            # may keep the excess precision of a cast down and up again
            S = jax.lax.reduce_precision(S, exponent_bits=8,
                                         mantissa_bits=7)
        return S, jnp.sum(S * qt[:, None, :], -1)            # o = S q

    _, o = jax.lax.scan(step, jnp.zeros((heads, dv, dk), F32),
                        (q, k, v, alpha, beta))
    return o


def linear_attention(x, lp, model, mm, state_dtype=None):
    """x [s, hidden] -> [s, hidden]."""
    s = x.shape[0]
    nh, dk, dv = (model["linear_num_heads"], model["linear_key_head_dim"],
                  model["linear_value_head_dim"])
    qkv = conv_silu(mm("sh,hc->sc", x, lp["lin.qkv_proj.weight"]),
                    lp["lin.conv_weight"])
    q, k, v = jnp.split(qkv, [nh * dk, 2 * nh * dk], axis=-1)
    q = _l2(q.reshape(s, nh, dk)) / math.sqrt(dk)
    k = _l2(k.reshape(s, nh, dk))
    a, b = jnp.split(mm("sh,hc->sc", x, lp["lin.ab_proj.weight"]), 2,
                     axis=-1)
    beta = jax.nn.sigmoid(b) * (2.0 if model["linear_allow_neg_eigval"]
                                else 1.0)
    alpha = jnp.exp(-jnp.exp(lp["lin.A_log"])
                    * jax.nn.softplus(a + lp["lin.dt_bias"]))
    o = delta_rule(q, k, v.reshape(s, nh, dv), alpha, beta, state_dtype)
    # assumed: the output gate and its norm (RMSNorm over d_v, one weight
    # of d_v shared by the heads, then SiLU(W_g x))
    gate = jax.nn.silu(mm("sh,hc->sc", x, lp["lin.g_proj.weight"]))
    o = _rms(o, lp["lin.o_norm.weight"], model["layer_norm_epsilon"])
    return mm("sc,ch->sh", o.reshape(s, nh * dv) * gate,
              lp["lin.out_proj.weight"])


def full_attention(x, lp, model, mm):
    """x [s, hidden] -> [s, hidden]; no positions (assumed: `rope_theta`
    null means none)."""
    s = x.shape[0]
    nh, nkv, d = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    eps = model["layer_norm_epsilon"]
    qkv = mm("sh,hc->sc", x, lp["attn.qkv_proj.weight"])
    q, k, v = jnp.split(qkv, [nh * d, (nh + nkv) * d], axis=-1)
    # assumed: the norm over the whole projection, before the heads split
    q = _rms(q, lp["attn.q_norm.weight"], eps).reshape(s, nh, d)
    k = _rms(k, lp["attn.k_norm.weight"], eps).reshape(s, nkv, d)
    v = v.reshape(s, nkv, d)
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    scores = mm("qhd,khd->hqk", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = mm("hqk,khd->qhd", probs, v).reshape(s, nh * d)
    return mm("sc,ch->sh", att, lp["attn.out_proj.weight"])


def block(x, lp, kind, model, quantized=False, state_dtype=None):
    """One decoder layer on x [s, hidden]. assumed: the norm after each
    sublayer (the family's reordered norm)."""
    mm = _mm(quantized)
    eps = model["layer_norm_epsilon"]
    lp = {k: v.astype(F32) for k, v in lp.items()}
    mixed = linear_attention(x, lp, model, mm, state_dtype) \
        if kind == LINEAR else full_attention(x, lp, model, mm)
    h = x + _rms(mixed, lp["ln_1.weight"], eps)
    gate, up = jnp.split(mm("sh,hm->sm", h, lp["mlp.gate_up_proj.weight"]),
                         2, axis=-1)
    y = mm("sm,mh->sh", jax.nn.silu(gate) * up, lp["mlp.down_proj.weight"])
    return h + _rms(y, lp["ln_2.weight"], eps)


def _key(model):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()
                        if isinstance(v, (int, float, bool, list, tuple))))


@functools.partial(jax.jit, static_argnames=("kind", "model_key",
                                              "quantized", "state_dtype"))
def _block_jit(x, lp, kind, model_key, quantized, state_dtype):
    return block(x, lp, kind, dict(model_key), quantized, state_dtype)


@functools.partial(jax.jit, static_argnames=("eps", "quantized"))
def _head_rows(ln_w, head_w, x, rows, eps, quantized):
    y = _rms(x[rows], ln_w.astype(F32), eps)
    return _mm(quantized)("rh,hv->rv", y, head_w.astype(F32))


def served_logits(params, ids, rows, model, quantized=False,
                  state_dtype=None):
    """Logits [len(rows), vocab] at positions `rows` of one sequence `ids`
    (1-D), layer by layer. Both kinds of layer are causal, so the caller
    may pad `ids` at the end to a few fixed lengths."""
    x = params["transformer.wte.weight"][jnp.asarray(ids)].astype(F32)
    for i, kind in enumerate(kinds(model)):
        x = _block_jit(x, layer_params(params, i), kind, _key(model),
                       quantized, state_dtype)
    return _head_rows(params["transformer.ln_f.weight"],
                      params["lm_head.weight"], x, jnp.asarray(rows),
                      model["layer_norm_epsilon"], quantized)


def logits(params, ids, model, quantized=False, state_dtype=None):
    """[seq, vocab] logits of the full forward of one sequence."""
    ids = np.asarray(ids)
    return served_logits(params, ids, np.arange(len(ids)), model, quantized,
                         state_dtype)
