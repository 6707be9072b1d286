"""Operation and byte counts of the SDAR-MoE forward and of generation by
diffusion over blocks, from a configuration's sizes.

As `flops.py`: what the mathematics needs, not what an implementation does.
A position runs `num_experts_per_tok` experts, never all of them; under the
block mask a query at position p attends to the keys of every block up to
its own, (floor(p / B) + 1) * B of them; the head is counted only where the
procedure needs logits (the B positions of a denoising pass; not a commit
pass, not a prompt chunk); a dispatch reads each distinct expert that any of
its positions chose once, whatever the program gathers.
"""
from __future__ import annotations


def matmul_params(model: dict) -> dict:
    """Weights that sit in matrix multiplications: a layer's attention, its
    router, one expert, and the head."""
    h, d = model["hidden_size"], model["head_dim"]
    nh, nkv = model["num_heads"], model["num_kv_heads"]
    return {"attention": h * (nh + 2 * nkv) * d + nh * d * h,
            "router": h * model["num_experts"],
            "expert": 3 * h * model["moe_intermediate_size"],
            "head": model["vocab_size"] * h}


def keys_attended(model: dict, position: int) -> int:
    """Keys a query at 0-based `position` attends to under the block mask."""
    b = model["block_attention"]
    return (position // b + 1) * b


def forward_flops(model: dict, positions: int, keys: int,
                  logits_rows: int) -> float:
    """One forward of `positions` positions that attend to `keys` keys in
    total (summed over the positions), logits for `logits_rows` of them."""
    mp = matmul_params(model)
    per_position = mp["attention"] + mp["router"] \
        + model["num_experts_per_tok"] * mp["expert"]
    attn = 4.0 * model["num_heads"] * model["head_dim"] * keys
    return model["num_layers"] * (2.0 * per_position * positions + attn) \
        + 2.0 * mp["head"] * logits_rows


def prompt_flops(model: dict, prompt_len: int) -> float:
    """Prefill of the whole blocks of a prompt: no logits are needed."""
    b = model["block_attention"]
    n = prompt_len // b * b
    keys = sum(keys_attended(model, p) for p in range(n))
    return forward_flops(model, n, keys, 0)


def block_forwards_flops(model: dict, forwards: int, commit_forwards: int,
                         context_tokens: int) -> float:
    """`forwards` forwards of one block each (B positions), of which
    `commit_forwards` need no logits; `context_tokens` is the keys one
    position of each forward attends to, summed over the forwards (every
    position of a block attends to the same keys: all up to its block's
    end)."""
    b = model["block_attention"]
    return forward_flops(model, forwards * b, context_tokens * b,
                         (forwards - commit_forwards) * b)


def layer_bytes_outside_experts(model: dict, itemsize: int = 2) -> float:
    mp = matmul_params(model)
    return float(mp["attention"] + mp["router"]) * itemsize


def expert_bytes(model: dict, itemsize: int = 2) -> float:
    return float(matmul_params(model)["expert"]) * itemsize


def head_bytes(model: dict, itemsize: int = 2) -> float:
    return float(matmul_params(model)["head"]) * itemsize


def kv_bytes_per_token(model: dict, itemsize: int = 2) -> float:
    """Keys and values of one cached token, all layers."""
    return 2.0 * model["num_layers"] * model["num_kv_heads"] \
        * model["head_dim"] * itemsize


def dispatch_bytes(model: dict, dispatches: int, distinct_experts: int,
                   head_dispatches: int, context_tokens: int,
                   itemsize: int = 2) -> float:
    """Least HBM traffic of `dispatches` decode dispatches: the weights
    outside the experts once a dispatch, each distinct expert a dispatch's
    positions chose once (`distinct_experts`: summed over layers and
    dispatches), the head once a dispatch that has a denoising pass, and the
    keys and values of the tokens the forwards attend to."""
    return dispatches * model["num_layers"] \
        * layer_bytes_outside_experts(model, itemsize) \
        + distinct_experts * expert_bytes(model, itemsize) \
        + head_dispatches * head_bytes(model, itemsize) \
        + context_tokens * kv_bytes_per_token(model, itemsize)
