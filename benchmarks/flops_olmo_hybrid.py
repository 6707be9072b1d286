"""Operation and byte counts of the Olmo-Hybrid forward (gated delta-rule
layers beside full attention), from a configuration's sizes.

As `flops.py`: what the mathematics needs, not what an implementation does.
A delta-rule head at one position decays its d_v x d_k state, reads it twice
(S k, S q) and adds one outer product: 7 d_v d_k operations, whatever chunked
form a program runs; its state crosses HBM once in and once out a step, a
sequence; a full layer's query at position p attends to p + 1 keys; the head
is counted only where a token is chosen; the weights once a dispatch.
"""
from __future__ import annotations

LINEAR = "linear_attention"


def kinds(model: dict) -> list:
    period = model["layer_pattern"]
    return [period[i % len(period)] for i in range(model["num_layers"])]


def counts(model: dict) -> tuple:
    """(linear layers, full layers)."""
    k = kinds(model)
    return k.count(LINEAR), len(k) - k.count(LINEAR)


def matmul_params(model: dict) -> dict:
    """Weights that sit in matrix multiplications: a layer's mixer by kind,
    its feed-forward, and the head."""
    h, m = model["hidden_size"], model["intermediate_size"]
    nh, nkv, d = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    lh, dk, dv = (model["linear_num_heads"], model["linear_key_head_dim"],
                  model["linear_value_head_dim"])
    return {"linear": h * lh * (2 * dk + dv) + h * 2 * lh + 2 * h * lh * dv,
            "full": h * (nh + 2 * nkv) * d + nh * d * h,
            "mlp": 3 * h * m,
            "head": model["vocab_size"] * h}


def layer_params(model: dict) -> float:
    """Matmul weights of all layers (no head, no embedding)."""
    mp = matmul_params(model)
    n_lin, n_full = counts(model)
    return float(n_lin * (mp["linear"] + mp["mlp"])
                 + n_full * (mp["full"] + mp["mlp"]))


def rule_flops_per_position(model: dict) -> float:
    """The delta rule of one position, one layer: 7 d_v d_k a head, and the
    convolution's K products and sums a channel."""
    lh, dk, dv = (model["linear_num_heads"], model["linear_key_head_dim"],
                  model["linear_value_head_dim"])
    return 7.0 * lh * dv * dk \
        + 2.0 * model["linear_conv_kernel_dim"] * lh * (2 * dk + dv)


def forward_flops(model: dict, positions, logits_rows: int) -> float:
    """One forward of tokens at the given 0-based `positions` (in a full
    layer a token at position p attends to p + 1 keys), logits for
    `logits_rows` of them."""
    mp = matmul_params(model)
    n_lin, n_full = counts(model)
    n = len(positions)
    keys = float(sum(positions)) + n
    return (2.0 * layer_params(model) * n
            + n_lin * rule_flops_per_position(model) * n
            + n_full * 4.0 * model["num_heads"] * model["head_dim"] * keys
            + 2.0 * mp["head"] * logits_rows)


def weight_bytes(model: dict, itemsize: int = 2) -> float:
    """Every matmul weight once, the head included (the embedding is read
    a row a token, counted nowhere)."""
    return (layer_params(model) + matmul_params(model)["head"]) * itemsize


def state_bytes_per_sequence(model: dict, window_itemsize: int = 2) -> float:
    """One sequence's recurrent cache over all delta-rule layers: the
    float32 state and the convolution's window."""
    lh, dk, dv = (model["linear_num_heads"], model["linear_key_head_dim"],
                  model["linear_value_head_dim"])
    n_lin, _ = counts(model)
    window = (model["linear_conv_kernel_dim"] - 1) * lh * (2 * dk + dv) \
        * window_itemsize
    return float(n_lin * (lh * dv * dk * 4 + window))


def kv_bytes_per_token(model: dict, itemsize: int = 2) -> float:
    """Keys and values of one cached token, the full layers only."""
    _, n_full = counts(model)
    return 2.0 * n_full * model["num_kv_heads"] * model["head_dim"] \
        * itemsize


def decode_bytes(model: dict, steps: int, sequence_steps: int,
                 context_tokens: int, itemsize: int = 2) -> float:
    """Least HBM traffic of `steps` decode steps that advance
    `sequence_steps` sequences in all (the sum of the steps' batch sizes):
    every weight and the head once a step, each advanced sequence's state
    read and written once, and the keys and values of the
    `context_tokens` tokens the full layers' queries attend to in total."""
    return steps * weight_bytes(model, itemsize) \
        + 2.0 * sequence_steps * state_bytes_per_sequence(model, itemsize) \
        + context_tokens * kv_bytes_per_token(model, itemsize)


def chunk_flops(model: dict, start: int, tokens: int) -> float:
    """One prompt chunk of `tokens` positions from `start`; logits for its
    last position only."""
    return forward_flops(model, range(start, start + tokens), 1)


def chunk_bytes(model: dict, start: int, tokens: int,
                itemsize: int = 2) -> float:
    """Least HBM traffic of one prompt chunk: every weight once, the
    sequence's state in and out, the rows of the `start` earlier tokens
    read and the chunk's own written."""
    return weight_bytes(model, itemsize) \
        + 2.0 * state_bytes_per_sequence(model, itemsize) \
        + (start + tokens) * kv_bytes_per_token(model, itemsize)


def prompt_chunks(prompt_len: int, chunk: int) -> list:
    """(start, tokens) of the chunks a prompt is prefilled in."""
    return [(s, min(chunk, prompt_len - s))
            for s in range(0, prompt_len, chunk)]
