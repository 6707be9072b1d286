"""Weights from the seed, made on the device in one jitted call.

The benchmark owns them: the program's model is given these values, and the
plain reference makes the same ones again from the same seed, so neither
side takes anything the other has made. Names follow the program's parameter
names only because the values have to be put into its model.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def shapes(model: dict) -> dict:
    """name -> (shape, kind, std): every parameter of the decoder."""
    h, m = model["hidden_size"], model["intermediate_size"]
    layers, std = model["num_layers"], 0.02
    out_std = std / math.sqrt(2 * layers)
    spec = {"transformer.wte.weight": ((model["vocab_size"], h), "w", std),
            "transformer.wpe.weight":
                ((model["max_position_embeddings"], h), "w", std)}
    for i in range(layers):
        p = f"transformer.layers.{i}."
        spec.update({
            p + "ln_1.weight": ((h,), "scale", std),
            p + "ln_1.bias": ((h,), "w", std),
            p + "attn.qkv_proj.weight": ((h, 3 * h), "w", std),
            p + "attn.qkv_proj.bias": ((3 * h,), "w", std),
            p + "attn.out_proj.weight": ((h, h), "w", out_std),
            p + "attn.out_proj.bias": ((h,), "w", std),
            p + "ln_2.weight": ((h,), "scale", std),
            p + "ln_2.bias": ((h,), "w", std),
            p + "mlp.up_proj.weight": ((h, m), "w", std),
            p + "mlp.up_proj.bias": ((m,), "w", std),
            p + "mlp.down_proj.weight": ((m, h), "w", out_std),
            p + "mlp.down_proj.bias": ((h,), "w", std),
        })
    spec["transformer.ln_f.weight"] = ((h,), "scale", std)
    spec["transformer.ln_f.bias"] = ((h,), "w", std)
    return spec


def fused_parts(model: dict) -> dict:
    """name -> how many projections the leaf fuses along its last axis."""
    return {n: 3 for n in shapes(model) if ".qkv_proj." in n}


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds pass 2**31). The `rbg`
    generator: the chip's own random bits, far cheaper there than threefry
    to compile and to run; the same seed gives the same weights on the
    same kind of device."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)


def make(model: dict, seed: int, dtype="float32") -> dict:
    """All weights, N(0, std) (norm scales 1 + N(0, std)), drawn in float32
    and rounded once to `dtype`. Biases are not zero, so that a path that
    drops one is seen. The leaves of one kind (the same suffix in every
    layer) are drawn in one call and cut apart, which keeps the program
    small: 16 draws, not 300."""
    spec = shapes(model)
    layers = model["num_layers"]
    prefix = "transformer.layers.0."
    kinds = sorted(n[len(prefix):] for n in spec if n.startswith(prefix))
    singles = sorted(n for n in spec if ".layers." not in n)

    def normal(key, shape, kind, std):
        x = std * jax.random.normal(key, shape, jnp.float32)
        return (1.0 + x if kind == "scale" else x).astype(dtype)

    def draw(key):
        out = {}
        for i, n in enumerate(singles):
            out[n] = normal(jax.random.fold_in(key, i), *spec[n])
        for j, suffix in enumerate(kinds):
            shape, kind, std = spec[prefix + suffix]
            stack = normal(jax.random.fold_in(key, 1000 + j),
                           (layers,) + shape, kind, std)
            for layer in range(layers):
                out[f"transformer.layers.{layer}.{suffix}"] = stack[layer]
        return out

    return jax.jit(draw)(seed_key(seed))
