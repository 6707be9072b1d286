"""Operation and byte counts of the Ling-flash forward as one chip's share
serves it (per-channel delta-rule layers beside latent attention, a share of
the experts), from a configuration's sizes.

As `flops.py`: the LEAST that any implementation of the mathematics must do,
never what this one does. A delta-rule head at one position decays its
d_v x d_k state channel by channel, reads it twice (S k, S q) and adds one
outer product: 7 d_v d_k operations whatever chunked form runs; its state
crosses HBM once in and once out a step a sequence. A latent-attention query
at position p meets p + 1 cached rows of r + d_r values, which cross HBM
once a step; absorbed, a head spends 2 (2 r + d_r) operations a row, and
that is what is counted for decode and prefill alike (a prefill through the
expanded heads does no less). An expert layer computes, and reads, only the
experts that were CHOSEN among those held: the reader hands in the count of
distinct experts chosen from the engine's counters, not the 64 a schedule
may scan. The head is counted only where a token is chosen; the weights
once a dispatch.
"""
from __future__ import annotations

from benchmarks.flops_olmo_hybrid import (LINEAR, kinds,  # noqa: F401
                                          prompt_chunks)

LATENT = "latent_attention"


def counts(model: dict) -> tuple:
    """(delta-rule layers, latent-attention layers, expert layers)."""
    k = kinds(model)
    return (k.count(LINEAR), k.count(LATENT),
            model["num_layers"] - model["first_k_dense"])


def matmul_params(model: dict) -> dict:
    """Weights that sit in matrix multiplications, by piece."""
    h = model["hidden_size"]
    lh, dk, dv = (model["linear_num_heads"], model["linear_key_head_dim"],
                  model["linear_value_head_dim"])
    nh, r = model["num_heads"], model["kv_lora_rank"]
    dn, dr, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    m, sm = (model["moe_intermediate_size"],
             model["moe_shared_expert_intermediate_size"])
    return {
        # q, k, v; the decay's and beta's inputs; the output gate; W_o
        "linear": h * lh * (2 * dk + dv) + h * (lh * dk + lh)
        + 2 * h * lh * dv,
        # W_q, W_kva, the head gates, W_o (W_kvb apart: absorbed or not,
        # it meets every query once)
        "latent": h * nh * (dn + dr) + h * (r + dr) + h * nh + nh * vd * h,
        "latent_kvb": r * nh * (dn + vd),
        "dense_mlp": 3 * h * model["intermediate_size"],
        "router": h * model["num_experts"],
        "shared": 3 * h * sm,
        "expert": 3 * h * m,
        "head": model["vocab_size"] * h}


def fixed_params(model: dict) -> float:
    """Matmul weights a token meets whatever it chooses: the mixers, the
    dense feed-forwards, the routers and shared experts (no expert, no
    head, no embedding)."""
    mp = matmul_params(model)
    n_lin, n_lat, n_exp = counts(model)
    return float(n_lin * mp["linear"]
                 + n_lat * (mp["latent"] + mp["latent_kvb"])
                 + model["first_k_dense"] * mp["dense_mlp"]
                 + n_exp * (mp["router"] + mp["shared"]))


def total_params(model: dict) -> float:
    """Every parameter held here (norms and the small vectors left out)."""
    mp = matmul_params(model)
    _, _, n_exp = counts(model)
    return fixed_params(model) + 2.0 * mp["head"] \
        + n_exp * model["experts_held"][1] * mp["expert"]


def rule_flops_per_position(model: dict) -> float:
    """The delta rule of one position, one layer: 7 d_v d_k a head, and the
    convolution's K products and sums a channel."""
    lh, dk, dv = (model["linear_num_heads"], model["linear_key_head_dim"],
                  model["linear_value_head_dim"])
    return 7.0 * lh * dv * dk \
        + 2.0 * model["linear_conv_kernel_dim"] * lh * (2 * dk + dv)


def forward_flops(model: dict, positions, logits_rows: int,
                  local_choices: float) -> float:
    """One forward of tokens at the given 0-based `positions` (in a latent
    layer a token at position p meets p + 1 rows), logits for `logits_rows`
    of them, `local_choices` (token, expert) pairs that fell on experts
    held here, summed over the expert layers."""
    mp = matmul_params(model)
    n_lin, n_lat, _ = counts(model)
    n = len(positions)
    rows = float(sum(positions)) + n
    per_row = 2.0 * model["num_heads"] * (2 * model["kv_lora_rank"]
                                          + model["qk_rope_head_dim"])
    return (2.0 * fixed_params(model) * n
            + n_lin * rule_flops_per_position(model) * n
            + n_lat * per_row * rows
            + 2.0 * mp["expert"] * local_choices
            + 2.0 * mp["head"] * logits_rows)


def state_bytes_per_sequence(model: dict, window_itemsize: int = 2) -> float:
    """One sequence's recurrent cache over all delta-rule layers: the
    float32 state and the convolution's window."""
    lh, dk, dv = (model["linear_num_heads"], model["linear_key_head_dim"],
                  model["linear_value_head_dim"])
    n_lin, _, _ = counts(model)
    window = (model["linear_conv_kernel_dim"] - 1) * lh * (2 * dk + dv) \
        * window_itemsize
    return float(n_lin * (lh * dv * dk * 4 + window))


def latent_bytes_per_token(model: dict, itemsize: int = 2) -> float:
    """The latent rows of one cached token over the latent layers."""
    _, n_lat, _ = counts(model)
    return float(n_lat * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
                 * itemsize)


def expert_bytes(model: dict, itemsize: int = 2) -> float:
    """One expert's weights."""
    return matmul_params(model)["expert"] * itemsize


def decode_bytes(model: dict, steps: int, sequence_steps: int,
                 context_tokens: int, distinct_experts: int,
                 itemsize: int = 2) -> float:
    """Least HBM traffic of `steps` decode steps that advance
    `sequence_steps` sequences in all: every weight outside the experts and
    the head slice once a step, `distinct_experts` experts' weights (the
    distinct held experts chosen, summed over layers and steps), each
    advanced sequence's state read and written once, and the latent rows of
    the `context_tokens` tokens the queries meet in total."""
    fixed = (fixed_params(model) + matmul_params(model)["head"]) * itemsize
    return steps * fixed \
        + distinct_experts * expert_bytes(model, itemsize) \
        + 2.0 * sequence_steps * state_bytes_per_sequence(model, itemsize) \
        + context_tokens * latent_bytes_per_token(model, itemsize)


def chunk_flops(model: dict, start: int, tokens: int,
                local_choices: float) -> float:
    """One prompt chunk of `tokens` positions from `start`; logits for its
    last position only."""
    return forward_flops(model, range(start, start + tokens), 1,
                         local_choices)


def chunk_bytes(model: dict, start: int, tokens: int,
                distinct_experts: float, itemsize: int = 2) -> float:
    """Least HBM traffic of one prompt chunk: every weight outside the
    experts once, the experts chosen in it, the sequence's state in and
    out, the rows of the `start` earlier tokens read and the chunk's own
    written."""
    fixed = (fixed_params(model) + matmul_params(model)["head"]) * itemsize
    return fixed + distinct_experts * expert_bytes(model, itemsize) \
        + 2.0 * state_bytes_per_sequence(model, itemsize) \
        + (start + tokens) * latent_bytes_per_token(model, itemsize)
