"""p95 over the window of the scheduler's `decode.round.prefill` spans, which
exist only in rounds that ran a prompt chunk: what a chunk adds to the gap
between two tokens of the running batch."""
from benchmarks import program_spans as ps


def read(ctx):
    _, rows = ps.rounds_of(ctx)
    return ps.p95_ms([r for r in rows or ()
                      if r.name == ps.ROUND + ".prefill"])
