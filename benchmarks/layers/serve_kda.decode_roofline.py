"""Decode step against the memory roofline: the bytes a step needs (every
weight outside the experts and the head slice once, the DISTINCT held
experts that were chosen, from the engine's `moe_distinct_experts` less its
prompt chunks' part, each advanced sequence's recurrent state and window
read and written once, the latent rows the queries meet) over the HBM
bandwidth, over the device time of the decode executables in the trace
(`trace_names.decode`). The same work whatever implements the step: a
schedule that scans all the experts it holds reads more and shows it here."""
from benchmarks import flops_ling as fl


def read(ctx):
    s = ctx["scope"]
    snaps = ctx["counters"]["snaps"]
    if not s or not ctx["peaks"] or "trace0" not in snaps \
            or "moe_chunk_distinct_experts" not in snaps.get("trace1", {}):
        return None
    a, b = snaps["trace0"], snaps["trace1"]
    steps = b["steps"] - a["steps"]
    if not s["decode_s"] or not steps:
        return None

    def grown(key):
        return b[key] - a[key]

    context = sum(p + 1 for p in s["decode_positions"])
    least = fl.decode_bytes(
        ctx["model"], steps, grown("step_active"), context,
        grown("moe_distinct_experts") - grown("moe_chunk_distinct_experts")
    ) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (s["decode_s"] * ctx["cell"]["chips"])
