"""The share of the device's idle time in the traced stretch that no
scheduler phase covers: what falls to a round's own time outside its phases,
or to no span of the program at all, each gap divided by what covers it. The
program's spans are put on the profiler's clock by the offset measured in
the run (`program_spans.offset_of`)."""
from benchmarks import program_spans as ps


def read(ctx):
    idle = ps.idle_of(ctx)
    if not idle or not idle[1]:
        return None
    split = idle[1]
    unnamed = split.get(ps.UNNAMED, 0.0) + split.get(ps.ROUND, 0.0)
    return 100.0 * unnamed / sum(split.values())
