"""Operation and byte counts of the algorithm, from a configuration's sizes.

The yardstick: what the mathematics needs, not what an implementation does.
Attention is counted over the causal lower triangle only (diagonal included),
the head only where logits are needed, recomputation never. A kernel that
skips masked blocks, or a step that batches its weights, can then never read
over 100 % of a peak.
"""
from __future__ import annotations


def matmul_params(model: dict) -> dict:
    """Weights that sit in matrix multiplications: per layer and the head."""
    h, m = model["hidden_size"], model["intermediate_size"]
    return {"layer": 3 * h * h + h * h + 2 * h * m,
            "head": model["vocab_size"] * h}


def attention_pairs_causal(seq_len: int) -> int:
    """(query, key) pairs of one causal sequence: the lower triangle."""
    return seq_len * (seq_len + 1) // 2


def train_flops_per_step(model: dict, batch: int, seq_len: int) -> float:
    """Forward + backward of one optimizer step, no recomputation. A matmul
    over T tokens with P weights is 2PT forward and 4PT backward; the head
    (tied) sees seq_len - 1 positions a row, as the loss shifts by one;
    attention is two matmuls forward (QK^T, PV) and four backward, each
    2 * head_dim flops a (query, key) pair a head."""
    mp = matmul_params(model)
    layers = model["num_layers"]
    dense = 6.0 * (layers * mp["layer"] * batch * seq_len
                   + mp["head"] * batch * (seq_len - 1))
    return dense + flash_train_flops(model, batch, seq_len)


def flash_train_flops(model: dict, batch: int, seq_len: int) -> float:
    """The attention matmuls of one step: 2 forward + 4 backward, each
    2 * hidden flops a causal pair (all heads together), every layer."""
    pairs = attention_pairs_causal(seq_len)
    return 6.0 * 2.0 * model["hidden_size"] * pairs * batch \
        * model["num_layers"]


def flash_train_bytes(model: dict, batch: int, seq_len: int,
                      itemsize: int = 2) -> float:
    """Least HBM traffic of the attention of one step: forward reads q, k, v
    and writes o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    per_tensor = batch * seq_len * model["hidden_size"] * itemsize
    return 12.0 * per_tensor * model["num_layers"]


def serve_flops(model: dict, positions, logits_rows: int) -> float:
    """One forward of tokens at the given 0-based `positions` (a token at
    position p attends to p + 1 keys), logits for `logits_rows` of them."""
    mp = matmul_params(model)
    layers, h = model["num_layers"], model["hidden_size"]
    n = len(positions)
    keys = float(sum(positions)) + n
    return (2.0 * layers * mp["layer"] * n + 2.0 * mp["head"] * logits_rows
            + 4.0 * h * keys * layers)


def weight_bytes(model: dict, itemsize: int = 2) -> float:
    """Every matmul weight once (the tied head included)."""
    mp = matmul_params(model)
    return float(model["num_layers"] * mp["layer"] + mp["head"]) * itemsize


def kv_bytes_per_token(model: dict, itemsize: int = 2) -> float:
    """Keys and values of one cached token, all layers."""
    return 2.0 * model["num_layers"] * model["hidden_size"] * itemsize


def decode_bytes(model: dict, steps: int, context_tokens: int,
                 itemsize: int = 2) -> float:
    """Least HBM traffic of `steps` decode steps of the algorithm: every
    weight once a step for the whole batch, plus the keys and values of the
    `context_tokens` tokens that the steps' queries attend to in total."""
    return steps * weight_bytes(model, itemsize) \
        + context_tokens * kv_bytes_per_token(model, itemsize)


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """(least seconds, which bound) for work of `flops` and `nbytes`."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
