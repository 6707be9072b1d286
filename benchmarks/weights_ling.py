"""Weights of the Ling-flash configuration from the seed, drawn on the device
a layer at a time (`weights_sdar.py`'s scheme: one jitted program a kind of
layer, the layer's index an argument, so the float32 temporaries are one
layer's: an expert layer's 64 experts are 1.5 GB in float32). The program's
model is given these values and the plain reference makes the same ones
again from the same seed. Names follow the program's parameter names only
because the values have to be put into its model.

Beside the normal draws (N(0, std); norm weights 1 + N(0, std)) a delta-rule
layer has three leaves of their own kind, as the configuration file's
`assumed` states: the convolution's weights uniform on +-K^-1/2, `A_log` =
log of a value uniform on [1, 4), and `dt_bias` = logit(t / |bound|) of a
step t log-uniform on [1e-3, 1). The router's selection bias is N(0, std).

Only the experts HELD are drawn (`experts_held`): the router's weights and
bias keep the published width.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.flops_ling import LATENT, LINEAR, kinds
from benchmarks.weights import seed_key

_LAYER = "transformer.layers.{}."


def layer_shapes(model: dict, index: int) -> dict:
    """suffix -> (shape, kind of draw, scale) of layer `index`."""
    h = model["hidden_size"]
    std = model.get("initializer_range", 0.02)
    out_std = std / math.sqrt(2 * model["num_layers"])
    spec = {"ln_1.weight": ((h,), "scale", std),
            "ln_2.weight": ((h,), "scale", std)}
    if kinds(model)[index] == LINEAR:
        nh, dk, dv = (model["linear_num_heads"],
                      model["linear_key_head_dim"],
                      model["linear_value_head_dim"])
        kc = model["linear_conv_kernel_dim"]
        ch = nh * (2 * dk + dv)
        spec.update({
            "lin.qkv_proj.weight": ((h, ch), "w", std),
            "lin.conv_weight": ((kc, ch), "uniform", 1.0 / math.sqrt(kc)),
            "lin.ab_proj.weight": ((h, nh * dk + nh), "w", std),
            "lin.A_log": ((nh,), "a_log", 0.0),
            "lin.dt_bias": ((nh * dk,), "dt_bias",
                            -model["linear_gate_lower_bound"]),
            "lin.g_proj.weight": ((h, nh * dv), "w", std),
            "lin.o_norm.weight": ((dv,), "scale", std),
            "lin.out_proj.weight": ((nh * dv, h), "w", out_std)})
    else:
        assert kinds(model)[index] == LATENT
        nh, r = model["num_heads"], model["kv_lora_rank"]
        dn, dr, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                      model["v_head_dim"])
        spec.update({
            "attn.q_proj.weight": ((h, nh * (dn + dr)), "w", std),
            "attn.q_norm.weight": ((dn + dr,), "scale", std),
            "attn.kv_a_proj.weight": ((h, r + dr), "w", std),
            "attn.kv_norm.weight": ((r,), "scale", std),
            "attn.kv_b_proj.weight": ((r, nh * (dn + vd)), "w", std),
            "attn.gate_proj.weight": ((h, nh), "w", std),
            "attn.out_proj.weight": ((nh * vd, h), "w", out_std)})
    if index < model["first_k_dense"]:
        m = model["intermediate_size"]
        spec.update({"mlp.gate_up_proj.weight": ((h, 2 * m), "w", std),
                     "mlp.down_proj.weight": ((m, h), "w", out_std)})
    else:
        m, sm = (model["moe_intermediate_size"],
                 model["moe_shared_expert_intermediate_size"])
        ne, held = model["num_experts"], model["experts_held"][1]
        spec.update({
            "mlp.router.weight": ((h, ne), "w", std),
            "mlp.router_bias": ((ne,), "w", std),
            "mlp.experts_gate_up": ((held, h, 2 * m), "w", std),
            "mlp.experts_down": ((held, m, h), "w", out_std),
            "mlp.shared.gate_up_proj.weight": ((h, 2 * sm), "w", std),
            "mlp.shared.down_proj.weight": ((sm, h), "w", out_std)})
    return spec


def shapes(model: dict) -> dict:
    """name -> (shape, kind of draw, scale): every parameter."""
    h, v = model["hidden_size"], model["vocab_size"]
    std = model.get("initializer_range", 0.02)
    spec = {"transformer.wte.weight": ((v, h), "w", std),
            "transformer.ln_f.weight": ((h,), "scale", std),
            "lm_head.weight": ((h, v), "w", std)}
    for i in range(model["num_layers"]):
        spec.update({_LAYER.format(i) + n: s
                     for n, s in layer_shapes(model, i).items()})
    return spec


def _one(key, shape, kind, std, dtype):
    if kind == "uniform":
        x = jax.random.uniform(key, shape, jnp.float32, -std, std)
    elif kind == "a_log":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 4.0))
    elif kind == "dt_bias":
        # `std` carries |bound|: the logit of step / |bound|
        share = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), 0.0)) / std
        x = jnp.log(share) - jnp.log1p(-share)
    else:
        x = std * jax.random.normal(key, shape, jnp.float32)
        if kind == "scale":
            x = 1.0 + x
    return x.astype(dtype)


@functools.partial(jax.jit, static_argnames=("spec", "dtype"))
def _draw(key, index, spec, dtype):
    key = jax.random.fold_in(key, index)
    return {name: _one(jax.random.fold_in(key, j), shape, kind, std, dtype)
            for j, (name, shape, kind, std) in enumerate(spec)}


def make(model: dict, seed: int, dtype="float32") -> dict:
    """All weights, drawn in float32 and rounded once to `dtype`."""
    key = seed_key(seed)
    singles = {n: s for n, s in shapes(model).items()
               if ".layers." not in n}
    out = dict(_draw(key, 0, tuple((n,) + singles[n]
                                   for n in sorted(singles)), dtype))
    for i in range(model["num_layers"]):
        spec = layer_shapes(model, i)
        drawn = _draw(key, i + 1, tuple((n,) + spec[n]
                                        for n in sorted(spec)), dtype)
        out.update({_LAYER.format(i) + n: v for n, v in drawn.items()})
    return out
