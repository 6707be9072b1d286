"""Plain reference of the Ling-flash decoder as ONE CHIP'S SHARE serves it:
per-channel gated delta-rule layers (Kimi Delta Attention) beside latent
attention, leading dense layers, then group-routed experts of which this
chip holds some, and a shared expert.

Straightforward `jax.numpy` in float32 at matmul precision "highest": one
sequence at a time, no kernel, no cache, no chunking, no batching; the
recurrence position by position, latent attention through the expanded heads
only, the experts as a loop over a token's choices. It imports nothing of
the program. The layers as `benchmarks/configs/ling_3p0_flash.json` states
them (x a block's normed input, no bias anywhere):

  delta-rule layer, per head h (d_k keys, d_v values)
    q~, k~, v~ = W_q x, W_k x, W_v x; each channel c of the three through
      conv_t = sum_{j<K} w[j, c] * u_{t-K+1+j}   (u_t = 0 for t < 0), SiLU
    q_t = q~_h / |q~_h|_2 * d_k^-1/2,  k_t = k~_h / |k~_h|_2
    beta_t = sigmoid(W_b x)_h
    g_t = bound * sigmoid(exp(A_log_h) * ((W_f x)_h + dt_bias_h))   [d_k]
    S' = S_{t-1} Diag(exp g_t);  S_t = S' + beta_t (v_t - S' k_t) k_t^T,
      S_{-1} = 0;   o_t = S_t q_t
    y = W_o [ RMSNorm_{d_v}(o_t) * sigmoid(W_g x)_h ]_h
  latent-attention layer
    q_h = RMSNorm((W_q x)_h) over the head's d_n + d_r, the last d_r rotated
    [c, k_r] = W_kva x; c = RMSNorm(c); k_r rotated, one for all heads
    [k_n, v]_h = (W_kvb c)_h;  causal softmax((q_n k_n + q_r k_r) /
    sqrt(d_n + d_r)) v per head, times sigmoid((W_gate x)_h);  y = W_o o
  feed-forward: layers below `first_k_dense` W_down (SiLU(W_gate h) * W_up h);
    the others, per token: s = sigmoid(W_r h) over ALL the router's experts,
    chosen by s + b: the groups scored by the sum of their two largest
    s + b, the best `moe_topk_group` groups kept, the top k of s + b inside
    them (the lower index of two equal scores first); weights s at the
    chosen over their sum, times `routed_scaling_factor`. Of the chosen,
    those this chip HOLDS (`experts_held` = first, count) add w_e times
    their SwiGLU; the absent ones add nothing. One shared expert is added
    to every token.
  block: h = x + mixer(RMSNorm(x)); out = h + ffn(RMSNorm(h)); a final
    RMSNorm, an untied head over the vocabulary's slice.

Departures from the published description: the recurrence runs as a
`lax.scan` over positions and the experts as a `lax.scan` over a token's k
choices under a `vmap` over tokens (a loop over choices, as written above);
every entry of the file's `assumed` is an inference from the family's
convention.

`quantized=True` is the control of the benchmark's `correct`: both operands
of every matrix multiplication (projections, attention's two products, the
experts, the head) rounded to float8 by `gpt_ref`'s recipe, the nearest
precision below the bfloat16 the configuration states; the router's scores
and the recurrence's own products stay float32. Two planted faults, for
what a tolerance could hide: `fault="mean_decay"` applies a head's decay as
its mean over d_k (the scalar rule under the new name), `fault="no_bias"`
/ `"no_groups"` leave the selection bias / the group limit out of the
router.

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
# the norms, the short convolution (assumed: depthwise, causal, no bias, then
# SiLU) and the L2 norm (assumed: x / sqrt(sum x^2 + 1e-6)) are the hybrid
# cell's reference's
from benchmarks.reference.olmo_hybrid_ref import (LINEAR, _l2, _rms,
                                                  conv_silu, kinds)


def delta_rule(q, k, v, g, beta):
    """The recurrence position by position: q, k, g [s, H, dk] (g the
    log-decay a key channel), v [s, H, dv], beta [s, H]; o [s, H, dv]."""
    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(S, x):
        qt, kt, vt, gt, bt = x
        decayed = S * jnp.exp(gt)[:, None, :]                # S Diag(e^g)
        err = vt - jnp.sum(decayed * kt[:, None, :], -1)     # v - S' k
        S = decayed + bt[:, None, None] * err[:, :, None] * kt[:, None, :]
        return S, jnp.sum(S * qt[:, None, :], -1)            # o = S q

    _, o = jax.lax.scan(step, jnp.zeros((heads, dv, dk), F32),
                        (q, k, v, g, beta))
    return o


def linear_attention(x, lp, model, mm, fault=None):
    """x [s, hidden] -> [s, hidden]."""
    s = x.shape[0]
    nh, dk, dv = (model["linear_num_heads"], model["linear_key_head_dim"],
                  model["linear_value_head_dim"])
    qkv = conv_silu(mm("sh,hc->sc", x, lp["lin.qkv_proj.weight"]),
                    lp["lin.conv_weight"])
    q, k, v = jnp.split(qkv, [nh * dk, 2 * nh * dk], axis=-1)
    q = _l2(q.reshape(s, nh, dk)) / math.sqrt(dk)
    k = _l2(k.reshape(s, nh, dk))
    f, b = jnp.split(mm("sh,hc->sc", x, lp["lin.ab_proj.weight"]),
                     [nh * dk], axis=-1)
    beta = jax.nn.sigmoid(b)
    g = model["linear_gate_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(lp["lin.A_log"])[:, None]
        * (f + lp["lin.dt_bias"]).reshape(s, nh, dk))
    if fault == "mean_decay":
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    o = delta_rule(q, k, v.reshape(s, nh, dv), g, beta)
    # assumed: RMSNorm over d_v (one weight shared by the heads), times
    # sigmoid(W_g x)
    gate = jax.nn.sigmoid(mm("sh,hc->sc", x, lp["lin.g_proj.weight"]))
    o = _rms(o, lp["lin.o_norm.weight"], model["layer_norm_epsilon"])
    return mm("sc,ch->sh", o.reshape(s, nh * dv) * gate,
              lp["lin.out_proj.weight"])


def _rotate(x, theta):
    """x [s, ..., d] at positions 0..s-1: the half-split rotation (assumed:
    the first d/2 channels against the last d/2)."""
    s, half = x.shape[0], x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (half,))
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def latent_attention(x, lp, model, mm):
    """x [s, hidden] -> [s, hidden], through the expanded heads."""
    s = x.shape[0]
    nh, r = model["num_heads"], model["kv_lora_rank"]
    dn, dr, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    eps, theta = model["layer_norm_epsilon"], model["rope_theta"]
    q = mm("sh,hc->sc", x, lp["attn.q_proj.weight"]).reshape(s, nh, dn + dr)
    q = _rms(q, lp["attn.q_norm.weight"], eps)
    q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], theta)], -1)
    ckr = mm("sh,hc->sc", x, lp["attn.kv_a_proj.weight"])
    c = _rms(ckr[:, :r], lp["attn.kv_norm.weight"], eps)
    k_r = _rotate(ckr[:, r:], theta)
    kv = mm("sr,rc->sc", c, lp["attn.kv_b_proj.weight"]).reshape(
        s, nh, dn + vd)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_r[:, None], (s, nh, dr))], -1)
    scores = mm("qhd,khd->hqk", q, k) / math.sqrt(dn + dr)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = mm("hqk,khd->qhd", probs, kv[..., dn:])
    # assumed: one sigmoid gate a head on the head's output
    o = o * jax.nn.sigmoid(mm("sh,hn->sn", x,
                              lp["attn.gate_proj.weight"]))[..., None]
    return mm("sc,ch->sh", o.reshape(s, nh * vd), lp["attn.out_proj.weight"])


def _top(x, k):
    """Indices of the k largest of x [n], the lower index of equals
    first."""
    return jnp.argsort(-x, stable=True)[:k]


def route(h, router_w, bias, model, fault=None):
    """h [hidden] -> (the k chosen experts' ids among all the router's,
    their weights)."""
    n, k = model["num_experts"], model["num_experts_per_tok"]
    ng, kg = model["moe_n_group"], model["moe_topk_group"]
    s = jax.nn.sigmoid(jnp.einsum("h,he->e", h, router_w,
                                  precision=jax.lax.Precision.HIGHEST))
    biased = s if fault == "no_bias" else s + bias
    if fault != "no_groups":
        grouped = biased.reshape(ng, n // ng)
        score = jnp.sum(-jnp.sort(-grouped, axis=-1)[:, :2], axis=-1)
        kept = jnp.zeros(ng, bool).at[_top(score, kg)].set(True)
        biased = jnp.where(kept[:, None], grouped, -jnp.inf).reshape(n)
    idx = _top(biased, k)
    w = s[idx]
    if model["norm_topk_prob"]:
        w = w / jnp.sum(w)
    return idx, w * model["routed_scaling_factor"]


def _swiglu(h, gate_up, down, mm):
    """h [..., hidden] through one SwiGLU (gate and up fused along the last
    axis of `gate_up`)."""
    gate, up = jnp.split(mm("...h,hm->...m", h, gate_up), 2, axis=-1)
    return mm("...m,mh->...h", jax.nn.silu(gate) * up, down)


def experts(h, lp, model, mm, fault=None, held=None):
    """h [s, hidden] -> the routed experts' part of the result as the chip
    that holds `held` = (first, count) computes it (default: the
    configuration's `experts_held`), and the choices: (y [s, hidden],
    ids [s, k])."""
    first, count = held or model["experts_held"]
    gate_up, down = lp["mlp.experts_gate_up"], lp["mlp.experts_down"]

    def token(ht):
        idx, w = route(ht, lp["mlp.router.weight"], lp["mlp.router_bias"],
                       model, fault)

        def choice(acc, x):
            e, we = x
            # an expert that is not held here is not computed: it adds
            # nothing (and seven choices of eight fall on such)
            return acc + jax.lax.cond(
                (e >= first) & (e < first + count),
                lambda: we * _swiglu(ht, gate_up[e - first],
                                     down[e - first], mm),
                lambda: jnp.zeros_like(ht)), None

        y, _ = jax.lax.scan(choice, jnp.zeros_like(ht), (idx, w))
        return y, idx

    return jax.lax.map(token, h)


def block(x, lp, index, model, quantized=False, fault=None):
    """Decoder layer `index` on x [s, hidden] -> (out, how many of each
    position's expert choices fell on experts held here [s], how many a
    position makes: 0 for a layer without experts)."""
    mm = _mm(quantized)
    eps = model["layer_norm_epsilon"]
    lp = {k: v.astype(F32) for k, v in lp.items()}
    y = _rms(x, lp["ln_1.weight"], eps)
    h = x + (linear_attention(y, lp, model, mm, fault)
             if kinds(model)[index] == LINEAR
             else latent_attention(y, lp, model, mm))
    y = _rms(h, lp["ln_2.weight"], eps)
    if index < model["first_k_dense"]:
        return h + _swiglu(y, lp["mlp.gate_up_proj.weight"],
                           lp["mlp.down_proj.weight"], mm), \
            jnp.zeros(x.shape[0], jnp.int32), 0
    routed, idx = experts(y, lp, model, mm, fault)
    first, count = model["experts_held"]
    local = jnp.sum((idx >= first) & (idx < first + count), axis=-1)
    shared = _swiglu(y, lp["mlp.shared.gate_up_proj.weight"],
                     lp["mlp.shared.down_proj.weight"], mm)
    return h + routed + shared, local, idx.shape[-1]


def _key(model):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()
                        if isinstance(v, (int, float, bool, str, list,
                                          tuple))))


def _unkey(model_key):
    return {k: list(v) if isinstance(v, tuple) else v for k, v in model_key}


@functools.partial(jax.jit, static_argnames=("index", "model_key",
                                              "quantized", "fault"))
def _block_jit(x, lp, index, model_key, quantized, fault):
    return block(x, lp, index, _unkey(model_key), quantized, fault)


@functools.partial(jax.jit, static_argnames=("eps", "quantized"))
def _head_rows(ln_w, head_w, x, rows, eps, quantized):
    y = _rms(x[rows], ln_w.astype(F32), eps)
    return _mm(quantized)("rh,hv->rv", y, head_w.astype(F32))


def served_logits(params, ids, rows, model, quantized=False, fault=None,
                  choices=None, real=None):
    """Logits [len(rows), vocab] at positions `rows` of one sequence `ids`
    (1-D), layer by layer. Every kind of layer is causal, so the caller may
    pad `ids` at the end to a few fixed lengths. `choices`, a list, gains
    one (local, made) pair of expert choices a layer, over the first `real`
    positions of `ids` (default: all)."""
    real = len(ids) if real is None else real
    x = params["transformer.wte.weight"][jnp.asarray(ids)].astype(F32)
    for i in range(model["num_layers"]):
        x, local, k = _block_jit(x, layer_params(params, i), i,
                                 _key(model), quantized, fault)
        if choices is not None and k:
            choices.append((int(jnp.sum(local[:real])), real * int(k)))
    return _head_rows(params["transformer.ln_f.weight"],
                      params["lm_head.weight"], x, jnp.asarray(rows),
                      model["layer_norm_epsilon"], quantized)


def logits(params, ids, model, quantized=False, fault=None):
    """[seq, vocab] logits of the full forward of one sequence."""
    ids = np.asarray(ids)
    return served_logits(params, ids, np.arange(len(ids)), model, quantized,
                         fault)
