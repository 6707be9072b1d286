"""Host time of the train engine per optimizer step over the window: the
`engine.dispatch` root spans wholly inside it (one a `train_batches` call,
from inside the call) over the steps they name. The program-side twin of
`train.host_ms_per_step`, which times the same call from outside; it also
writes the log's `[spans]` line."""
from benchmarks import program_spans as ps


def read(ctx):
    ps.report(ctx)
    rows = ps.of_window(ctx)
    if rows is None:
        return None
    c = ctx["counters"]
    calls = ps.inside(rows, c["t_open"], c["t_close"], ps.DISPATCH,
                      roots=True)
    steps = sum(r.attrs.get("steps", 0) for r in calls)
    if not steps:
        return None
    return sum(r.t1 - r.t0 for r in calls) * 1e3 / steps
