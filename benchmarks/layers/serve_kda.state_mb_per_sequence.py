"""What a resident sequence holds of the two kinds of cache, the window's
mean: the state slots in use (`lin_state_bytes`) plus the latent rows in use
(`mla_rows_in_use` token rows of `mla_row_bytes` over the latent layers),
over the sequences resident, from the engine's `stats()` sampled through the
window."""


def read(ctx):
    samples = ctx["counters"].get("stats_samples")
    if not samples or "mla_rows_in_use" not in samples[0]:
        return None
    per_seq = [(s["mla_rows_in_use"] * s["mla_row_bytes"]
                + s["lin_state_bytes"]) / s["resident"]
               for s in samples if s["resident"]]
    if not per_seq:
        return None
    return sum(per_seq) / len(per_seq) / 1e6
