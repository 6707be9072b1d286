"""Decode step against the memory roofline: the bytes the algorithm needs
(every weight once a step for the whole batch, plus the keys and values of
the tokens its queries attend to) over the HBM bandwidth, over the device
time of the decode executables in the trace (the programs that the mix
names under `trace_names.decode`). It reads the same work whatever
implements the step."""
from benchmarks import flops


def read(ctx):
    s = ctx["scope"]
    snaps = ctx["counters"]["snaps"]
    if not s or not ctx["peaks"] or "trace0" not in snaps \
            or "trace1" not in snaps:
        return None
    device_s = s["decode_s"]
    steps = snaps["trace1"]["steps"] - snaps["trace0"]["steps"]
    if not device_s or not steps:
        return None
    context = sum(p + 1 for p in s["decode_positions"])
    least = flops.decode_bytes(ctx["model"], steps, context) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (device_s * ctx["cell"]["chips"])
