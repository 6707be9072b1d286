"""p95 over the window of the scheduler's `decode.round.decode` spans: one
decode step as the scheduler pays for it (grow, pack, hand-off, the device's
step, deliver)."""
from benchmarks import program_spans as ps


def read(ctx):
    _, rows = ps.rounds_of(ctx)
    return ps.p95_ms([r for r in rows or ()
                      if r.name == ps.ROUND + ".decode"])
