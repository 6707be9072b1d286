"""What a resident sequence holds of the two kinds of cache, the window's
mean: the KV blocks in use (their rows' bytes over the full layers) plus the
state slots in use (`lin_state_bytes`), over the sequences resident, from
the engine's `stats()` sampled through the window."""
from benchmarks import flops_olmo_hybrid as fl


def read(ctx):
    samples = ctx["counters"].get("cache_samples")
    if not samples:
        return None
    block_bytes = ctx["mix"]["engine"]["block_size"] \
        * fl.kv_bytes_per_token(ctx["model"])
    per_seq = [(s["kv_blocks_in_use"] * block_bytes + s["lin_state_bytes"])
               / s["resident"] for s in samples if s["resident"]]
    if not per_seq:
        return None
    return sum(per_seq) / len(per_seq) / 1e6
