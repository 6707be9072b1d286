"""Host time of the scheduler per decode step over the window: the time of
the `decode.round` spans wholly inside it less their `.fetch` spans (where
the host only waits for the device), over the decode steps those rounds
ran. From the program's own spans; it also writes the log's `[spans]`
line."""
from benchmarks import program_spans as ps


def read(ctx):
    ps.report(ctx)
    rounds, rows = ps.rounds_of(ctx)
    steps = [r for r in rows or () if r.name == ps.ROUND + ".decode"]
    if not rounds or not steps:
        return None
    waited = sum(r.t1 - r.t0 for r in rows if r.name.endswith(".fetch"))
    return (sum(r.t1 - r.t0 for r in rounds) - waited) * 1e3 / len(steps)
