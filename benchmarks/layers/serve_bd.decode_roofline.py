"""Block-diffusion decode dispatches against the memory roofline: the bytes
the procedure needs a dispatch (the weights outside the experts once, each
distinct expert that any position of the dispatch chose once, the head once
unless every sequence commits, the keys and values of the tokens attended)
over the HBM bandwidth, over the device time of the step programs in the
trace (`trace_names.decode`). The same work whatever implements the step."""
from benchmarks import flops_sdar


def read(ctx):
    s = ctx["scope"]
    snaps = ctx["counters"]["snaps"]
    if not s or not ctx["peaks"] or "trace0" not in snaps \
            or "trace1" not in snaps or "bd_forwards" not in snaps["trace1"]:
        return None
    a, b = snaps["trace0"], snaps["trace1"]
    steps = b["steps"] - a["steps"]
    if not s["decode_s"] or not steps:
        return None
    least = flops_sdar.dispatch_bytes(
        ctx["model"], steps,
        b["moe_distinct_experts"] - a["moe_distinct_experts"],
        b["bd_head_dispatches"] - a["bd_head_dispatches"],
        b["bd_context_tokens"] - a["bd_context_tokens"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (s["decode_s"] * ctx["cell"]["chips"])
