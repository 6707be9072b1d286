"""The longest scheduler round of the window, or the longest stretch an idle
wait of the scheduler left work waiting (`work_waited_s`): a stall reads in
seconds here, a sound run a step and a chunk."""
from benchmarks import program_spans as ps


def read(ctx):
    rounds, _ = ps.rounds_of(ctx)
    if not rounds:
        return None
    c = ctx["counters"]
    waits = [r.attrs.get("work_waited_s", 0.0) for r in ps.inside(
        ps.of_window(ctx), c["t_open"], c["t_close"], ps.IDLE)]
    return max([r.t1 - r.t0 for r in rounds] + waits) * 1e3
