"""Plain reference of the SDAR-MoE decoder and of generation by diffusion
over blocks.

Straightforward `jax.numpy` in float32 at matmul precision "highest": no
kernel, no cache, no batching tricks; it imports nothing of the program.
One layer, as `config.json` of JetLM/SDAR-30B-A3B-Chat (`model_type`
`sdar_moe`, a Qwen3-MoE-shaped pre-norm block) gives it, for a block length B:

    a = x + W_o Attn(q, k, v)     q = rope(rmsnorm_head(W_q rmsnorm(x)))
                                  k = rope(rmsnorm_head(W_k rmsnorm(x)))
                                  v = W_v rmsnorm(x)
      Attn: softmax(q k^T / sqrt(head_dim)) over the keys j of query i with
      floor(j / B) <= floor(i / B); each kv head serves heads / kv_heads
      query heads
    y = a + sum_{e in top_k(p)} (p_e / sum_top p) W_down,e (silu(W_gate,e h)
                                                            * W_up,e h)
      h = rmsnorm(a), p = softmax_f32(W_r h) over all experts

No bias anywhere; rotary positions in the half-rotation form; `rmsnorm_head`
has one learned weight of head_dim shared by the heads of a projection; a
final RMSNorm and an untied head. The experts run as a loop over the experts
that have a token. Departures from the published model: none in the layer;
generation (`generate`) follows the configuration file's `assumed` entries.

`quantized=True` is the control of the benchmark's `correct`: both operands
of every matrix multiplication rounded to float8 (`gpt_ref`'s recipe), the
nearest precision below the bfloat16 the configuration states.

Memory: `served_logits` takes the bfloat16 weight values and upcasts a layer
at a time, and an expert at a time inside a layer (all six layers in float32
are 15 GB).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.gpt_ref import F32, _mm, layer_params


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _rope(x, theta):
    """Half-rotation form on x [rows, seq, heads, d], positions 0..seq-1."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(y, lp, model, mm):
    b, s, _ = y.shape
    nh, nkv, d = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    eps, blk = model["layer_norm_epsilon"], model["block_attention"]
    qkv = mm("bsh,hk->bsk", y, lp["attn.qkv_proj.weight"])
    q, k, v = jnp.split(qkv, [nh * d, (nh + nkv) * d], axis=-1)
    q = _rope(_rms(q.reshape(b, s, nh, d), lp["attn.q_norm.weight"], eps),
              model["rope_theta"])
    k = _rope(_rms(k.reshape(b, s, nkv, d), lp["attn.k_norm.weight"], eps),
              model["rope_theta"])
    v = v.reshape(b, s, nkv, d)
    k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
    scores = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    i = jnp.arange(s)
    seen = (i[None, :] // blk) <= (i[:, None] // blk)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    att = mm("bhqk,bkhd->bqhd", probs, v).reshape(b, s, nh * d)
    return mm("bsh,hk->bsk", att, lp["attn.out_proj.weight"])


def route(h, lp, model, mm):
    """(chosen experts [T, k], their weights [T, k]) of h [T, hidden]."""
    p = jax.nn.softmax(mm("th,he->te", h, lp["mlp.router.weight"]), axis=-1)
    w, idx = jax.lax.top_k(p, model["num_experts_per_tok"])
    if model.get("norm_topk_prob", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    return idx, w


def experts(h, lp, model, mm):
    """h [T, hidden] through its chosen experts: a loop over the experts,
    those with no token skipped. The stacks stay in the dtype they came in
    and each expert is upcast where it is used."""
    idx, w = route(h, lp, model, mm)
    gate_up, down = lp["mlp.experts_gate_up"], lp["mlp.experts_down"]

    def body(e, acc):
        share = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)   # [T]

        def run(acc):
            gu = mm("th,hm->tm", h, gate_up[e].astype(F32))
            gate, up = jnp.split(gu, 2, axis=-1)
            out = mm("tm,mh->th", jax.nn.silu(gate) * up,
                     down[e].astype(F32))
            return acc + out * share[:, None]

        return jax.lax.cond(jnp.any(idx == e), run, lambda a: a, acc)

    return jax.lax.fori_loop(0, gate_up.shape[0], body, jnp.zeros_like(h))


def block(x, lp, model, quantized=False):
    """One decoder layer on x [rows, seq, hidden] under the block mask."""
    mm = _mm(quantized)
    eps = model["layer_norm_epsilon"]
    big = ("mlp.experts_gate_up", "mlp.experts_down")
    lp = {k: v if k in big else v.astype(F32) for k, v in lp.items()}
    a = x + attention(_rms(x, lp["ln_1.weight"], eps), lp, model, mm)
    h = _rms(a, lp["ln_2.weight"], eps)
    y = experts(h.reshape(-1, h.shape[-1]), lp, model, mm)
    return a + y.reshape(a.shape)


def _key(model):
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float, bool))))


@functools.partial(jax.jit, static_argnames=("model_key", "quantized"))
def _block_jit(x, lp, model_key, quantized):
    return block(x, lp, dict(model_key), quantized)


@functools.partial(jax.jit, static_argnames=("eps", "quantized"))
def _head_rows(ln_w, head_w, x, rows, eps, quantized):
    """x [n, seq, hidden], rows [n, r] -> logits [n, r, vocab]."""
    picked = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    y = _rms(picked, ln_w.astype(F32), eps)
    return _mm(quantized)("nrh,hv->nrv", y, head_w.astype(F32))


def served_logits(params, ids, rows, model, quantized=False):
    """Logits [n, r, vocab] at positions `rows` [n, r] of the sequences
    `ids` [n, seq], layer by layer. The caller pads `ids` with whole blocks
    (the block mask keeps them from every earlier position) to a few fixed
    lengths, so that a few compiled programs serve every request."""
    x = params["transformer.wte.weight"][jnp.asarray(ids)].astype(F32)
    for i in range(model["num_layers"]):
        x = _block_jit(x, layer_params(params, i), _key(model), quantized)
    return _head_rows(params["transformer.ln_f.weight"],
                      params["lm_head.weight"], x, jnp.asarray(rows),
                      model["layer_norm_epsilon"], quantized)


def logits(params, ids, model, quantized=False):
    """[rows, seq, vocab] logits of the full forward (small sizes)."""
    ids = np.asarray(ids)
    rows = np.broadcast_to(np.arange(ids.shape[1]), ids.shape)
    return served_logits(params, ids, rows, model, quantized)


def pick(conf, masked, count):
    """The `count` masked positions of highest confidence, ties to the
    lower position (`low_confidence_static`: the rest stay masked)."""
    cand = np.flatnonzero(masked)
    order = np.argsort(-np.asarray(conf, np.float32)[cand], kind="stable")
    return cand[order[:count]]


def generate(params, prompt, n, block_length, steps, mask_id, model,
             pad_to=None, quantized=False):
    """`n` tokens after `prompt` by diffusion over blocks, greedy, and for
    each the denoising pass (1-based) of its block that fixed it. The whole
    sequence is forwarded again in every pass. The prompt's remainder mod
    the block length opens the first block as positions already fixed."""
    bl, per = block_length, block_length // steps
    prompt = [int(t) for t in prompt]
    aligned = len(prompt) // bl * bl
    seq, rest = prompt[:aligned], prompt[aligned:]
    tokens, passes = [], []
    while len(tokens) < n:
        blk = rest + [mask_id] * (bl - len(rest))
        masked = np.array([False] * len(rest) + [True] * (bl - len(rest)))
        fixed_in = [0] * bl
        t = 0
        while masked.any():
            t += 1
            ids = np.asarray(seq + blk, np.int32)
            rows = np.arange(len(seq), len(seq) + bl)
            if pad_to:
                ids = np.concatenate(
                    [ids, np.zeros(pad_to - len(ids), np.int32)])
            lg = np.asarray(served_logits(params, ids[None], rows[None],
                                          model, quantized))[0]
            best = lg.argmax(-1)
            conf = np.exp(lg.max(-1) - np.asarray(
                jax.nn.logsumexp(lg, axis=-1)))
            for i in pick(conf, masked, per):
                blk[i], masked[i], fixed_in[i] = int(best[i]), False, t
        tokens += blk[len(rest):]
        passes += fixed_in[len(rest):]
        seq, rest = seq + blk, []
    return tokens[:n], passes[:n]
