"""Plain reference of the GPT decoder: forward, loss, gradients, AdamW.

Straightforward `jax.numpy` in float32 at matmul precision "highest": no
kernel, no cache, no batching tricks. It imports nothing of the program.
The block is the one of Brown et al. 2020 as the configuration files state
it: learned positions, pre-LayerNorm, causal multi-head attention, GELU
(tanh form), biases everywhere, the head tied to the token embedding.

`quantized=True` is the control of the benchmark's `correct`: the same
mathematics with both operands of every matrix multiplication rounded to
float8 (e4m3, scaled per tensor to its largest value), the nearest precision
below the bfloat16 the configurations state. It has to come out as not
correct.

Memory: training runs in blocks of rows with the layers checkpointed, serving
layer by layer, so that the reference fits beside nothing else on one chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _fp8(x, dtype=jnp.float8_e4m3fn, top=448.0):
    """x rounded to float8 at a per-tensor scale (its largest value lands
    on the format's largest)."""
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(F32) / scale


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(spec, a, b):
    """The float8 recipe: operands in e4m3 forward, the incoming gradient in
    e5m2 backward, accumulation in float32."""
    return _einsum(spec, _fp8(a), _fp8(b))


def _einsum_fp8_fwd(spec, a, b):
    qa, qb = _fp8(a), _fp8(b)
    return _einsum(spec, qa, qb), (qa, qb)


def _einsum_fp8_bwd(spec, res, g):
    qa, qb = res
    _, vjp = jax.vjp(functools.partial(_einsum, spec), qa, qb)
    return vjp(_fp8(g, jnp.float8_e5m2, 57344.0))


_einsum_fp8.defvjp(_einsum_fp8_fwd, _einsum_fp8_bwd)


def _mm(quantized):
    return _einsum_fp8 if quantized else _einsum


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def layer_params(params, i):
    p = f"transformer.layers.{i}."
    return {k[len(p):]: v for k, v in params.items() if k.startswith(p)}


def block(x, lp, *, num_heads, eps, quantized=False):
    """One decoder block on x [rows, seq, hidden], causal over seq."""
    mm = _mm(quantized)
    lp = {k: v.astype(F32) for k, v in lp.items()}
    b, s, h = x.shape
    hd = h // num_heads
    y = _layer_norm(x, lp["ln_1.weight"], lp["ln_1.bias"], eps)
    qkv = mm("bsh,hk->bsk", y, lp["attn.qkv_proj.weight"]) \
        + lp["attn.qkv_proj.bias"]
    q, k, v = (t.reshape(b, s, num_heads, hd)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = mm("bhqk,bkhd->bqhd", probs, v).reshape(b, s, h)
    x = x + mm("bsh,hk->bsk", att, lp["attn.out_proj.weight"]) \
        + lp["attn.out_proj.bias"]
    y = _layer_norm(x, lp["ln_2.weight"], lp["ln_2.bias"], eps)
    u = _gelu(mm("bsh,hm->bsm", y, lp["mlp.up_proj.weight"])
              + lp["mlp.up_proj.bias"])
    return x + mm("bsm,mh->bsh", u, lp["mlp.down_proj.weight"]) \
        + lp["mlp.down_proj.bias"]


@jax.jit
def _embed(wte, wpe, ids):
    return wte[ids].astype(F32) + wpe[:ids.shape[1]].astype(F32)[None]


def embed(params, ids):
    return _embed(params["transformer.wte.weight"],
                  params["transformer.wpe.weight"], ids)


def head(params, x, eps, quantized=False):
    y = _layer_norm(x, params["transformer.ln_f.weight"].astype(F32),
                    params["transformer.ln_f.bias"].astype(F32), eps)
    return _mm(quantized)("bsh,vh->bsv", y,
                          params["transformer.wte.weight"].astype(F32))


def hidden_states(params, ids, model, quantized=False, remat=False):
    blk = functools.partial(block, num_heads=model["num_heads"],
                            eps=model["layer_norm_epsilon"],
                            quantized=quantized)
    if remat:
        blk = jax.checkpoint(blk)
    x = embed(params, ids)
    for i in range(model["num_layers"]):
        x = blk(x, layer_params(params, i))
    return x


def logits(params, ids, model, quantized=False):
    """[rows, seq, vocab] logits of the full forward (small sizes)."""
    return head(params, hidden_states(params, ids, model, quantized),
                model["layer_norm_epsilon"], quantized)


def loss_sum(params, ids, model, quantized=False):
    """Sum over rows and positions of the next-token cross entropy."""
    x = hidden_states(params, ids, model, quantized, remat=True)[:, :-1]
    lg = head(params, x, model["layer_norm_epsilon"], quantized)
    lse = jax.nn.logsumexp(lg, axis=-1)
    # the label's logit by a masked sum: exact (one non-zero term a row),
    # and XLA:TPU's one-element-a-row gather is slow to compile and to run
    hit = jnp.arange(lg.shape[-1])[None, None, :] == ids[:, 1:, None]
    return jnp.sum(lse - jnp.sum(jnp.where(hit, lg, 0.0), axis=-1))


@functools.partial(jax.jit, static_argnames=("model_key", "quantized"))
def _loss_and_grad_rows(params, ids, model_key, quantized):
    return jax.value_and_grad(loss_sum)(params, ids, dict(model_key),
                                        quantized)


def loss_and_grads(params, ids, model, quantized=False, rows=4):
    """Mean loss over all rows and its gradients, in blocks of `rows`."""
    key = tuple(sorted(model.items()))
    total, grads = 0.0, None
    for r in range(0, ids.shape[0], rows):
        l, g = _loss_and_grad_rows(params, ids[r:r + rows], key, quantized)
        total = total + l
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    n = ids.shape[0] * (ids.shape[1] - 1)
    return total / n, jax.tree_util.tree_map(lambda g: g / n, grads)


@jax.jit
def clip_by_global_norm(grads, clip_norm):
    """(clipped grads, the norm before the clip)."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(norm, 1e-12))
    return {k: g * scale for k, g in grads.items()}, norm


@jax.jit
def adamw_update(params, grads, m, v, step, lr, beta1, beta2, eps, wd):
    """Decoupled weight decay on every leaf, bias-corrected moments."""
    t = step.astype(F32)
    out_p, out_m, out_v = {}, {}, {}
    for k, p in params.items():
        g = grads[k]
        out_m[k] = beta1 * m[k] + (1 - beta1) * g
        out_v[k] = beta2 * v[k] + (1 - beta2) * g * g
        mhat = out_m[k] / (1 - beta1 ** t)
        vhat = out_v[k] / (1 - beta2 ** t)
        out_p[k] = p * (1.0 - lr * wd) - lr * mhat / (jnp.sqrt(vhat) + eps)
    return out_p, out_m, out_v


def train_steps(params, batches, model, opt, quantized=False, rows=4):
    """Follow the optimizer over `batches`: per-step loss and gradient norm
    before the clip, then the first moment and the parameters at the end."""
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, gnorms = [], []
    for i, ids in enumerate(batches):
        loss, grads = loss_and_grads(params, jnp.asarray(ids), model,
                                     quantized, rows)
        grads, gn = clip_by_global_norm(grads, F32(opt["clip_norm"]))
        params, m, v = adamw_update(
            params, grads, m, v, jnp.asarray(i + 1, jnp.int32),
            F32(opt["learning_rate"]), F32(opt["beta1"]), F32(opt["beta2"]),
            F32(opt["epsilon"]), F32(opt["weight_decay"]))
        losses.append(float(loss))
        gnorms.append(float(gn))
    return {"losses": losses, "gnorms": gnorms, "moment1": m,
            "params": params}


@functools.partial(jax.jit, static_argnames=("num_heads", "eps",
                                              "quantized"))
def _block_jit(x, lp, num_heads, eps, quantized):
    return block(x, lp, num_heads=num_heads, eps=eps, quantized=quantized)


@functools.partial(jax.jit, static_argnames=("eps", "quantized"))
def _rows_logits(params_head, x, rows, eps, quantized):
    return head(params_head, x[rows][None], eps, quantized)[0]


def served_logits(params, ids, rows, model, quantized=False):
    """Logits [len(rows), vocab] at positions `rows` of one sequence `ids`
    (1-D), layer by layer. The caller pads `ids` and `rows` to fixed
    lengths, so that one compiled program serves every request."""
    x = embed(params, jnp.asarray(ids)[None])
    for i in range(model["num_layers"]):
        x = _block_jit(x, layer_params(params, i), model["num_heads"],
                       model["layer_norm_epsilon"], quantized)
    ph = {k: params[k] for k in ("transformer.ln_f.weight",
                                 "transformer.ln_f.bias",
                                 "transformer.wte.weight")}
    return _rows_logits(ph, x[0], jnp.asarray(rows),
                        model["layer_norm_epsilon"], quantized)
