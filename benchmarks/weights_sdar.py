"""Weights of the SDAR-MoE configuration from the seed, drawn on the device
a layer at a time.

One jitted program draws a whole layer (the layer's index is an argument, so
every layer reuses it) and another the embedding, the head and the final
norm: the float32 temporaries are then one layer's (2.5 GB at the served
size), not the model's. The program's model is given these values and the
plain reference makes the same ones again from the same seed. Names follow
the program's parameter names only because the values have to be put into
its model.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key

_LAYER = "transformer.layers.{}."


def shapes(model: dict) -> dict:
    """name -> (shape, kind, std): every parameter of the decoder."""
    h, v = model["hidden_size"], model["vocab_size"]
    d, m = model["head_dim"], model["moe_intermediate_size"]
    nh, nkv, ne = (model["num_heads"], model["num_kv_heads"],
                   model["num_experts"])
    std = model.get("initializer_range", 0.02)
    out_std = std / math.sqrt(2 * model["num_layers"])
    spec = {"transformer.wte.weight": ((v, h), "w", std),
            "transformer.ln_f.weight": ((h,), "scale", std),
            "lm_head.weight": ((h, v), "w", std)}
    for i in range(model["num_layers"]):
        p = _LAYER.format(i)
        spec.update({
            p + "ln_1.weight": ((h,), "scale", std),
            p + "attn.qkv_proj.weight": ((h, (nh + 2 * nkv) * d), "w", std),
            p + "attn.out_proj.weight": ((nh * d, h), "w", out_std),
            p + "attn.q_norm.weight": ((d,), "scale", std),
            p + "attn.k_norm.weight": ((d,), "scale", std),
            p + "ln_2.weight": ((h,), "scale", std),
            p + "mlp.router.weight": ((h, ne), "w", std),
            p + "mlp.experts_gate_up": ((ne, h, 2 * m), "w", std),
            p + "mlp.experts_down": ((ne, m, h), "w", out_std),
        })
    return spec


def _normal(key, shape, kind, std, dtype):
    x = std * jax.random.normal(key, shape, jnp.float32)
    return (1.0 + x if kind == "scale" else x).astype(dtype)


@functools.partial(jax.jit, static_argnames=("spec", "dtype"))
def _draw(key, index, spec, dtype):
    key = jax.random.fold_in(key, index)
    return {name: _normal(jax.random.fold_in(key, j), shape, kind, std,
                          dtype)
            for j, (name, shape, kind, std) in enumerate(spec)}


def make(model: dict, seed: int, dtype="float32") -> dict:
    """All weights, N(0, std) (norm weights 1 + N(0, std)), drawn in
    float32 and rounded once to `dtype`."""
    spec = shapes(model)
    key = seed_key(seed)
    first = _LAYER.format(0)
    singles = tuple((n,) + spec[n] for n in sorted(spec)
                    if ".layers." not in n)
    layer = tuple((n[len(first):],) + spec[n] for n in sorted(spec)
                  if n.startswith(first))
    out = dict(_draw(key, 0, singles, dtype))
    for i in range(model["num_layers"]):
        drawn = _draw(key, i + 1, layer, dtype)
        out.update({_LAYER.format(i) + n: v for n, v in drawn.items()})
    return out
