"""Decode step against the memory roofline: the bytes a step needs (every
weight and the head once, each advanced sequence's recurrent state and
window read and written once, the keys and values the full layers' queries
attend to) over the HBM bandwidth, over the device time of the decode
executables in the trace (`trace_names.decode`). The same work whatever
implements the step."""
from benchmarks import flops_olmo_hybrid as fl


def read(ctx):
    s = ctx["scope"]
    snaps = ctx["counters"]["snaps"]
    if not s or not ctx["peaks"] or "trace0" not in snaps \
            or "trace1" not in snaps \
            or "lin_step_tokens" not in snaps["trace1"]:
        return None
    a, b = snaps["trace0"], snaps["trace1"]
    steps = b["steps"] - a["steps"]
    if not s["decode_s"] or not steps:
        return None
    context = sum(p + 1 for p in s["decode_positions"])
    least = fl.decode_bytes(
        ctx["model"], steps, b["step_active"] - a["step_active"],
        context) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (s["decode_s"] * ctx["cell"]["chips"])
