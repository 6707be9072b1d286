"""The program's own spans, for the per-layer readers that say
`"source": "program_span"`.

The program records scheduler-phase spans (`decode.round*`, `decode.idle_wait`,
`engine.dispatch`, `engine::*`, `host.gc`) into its flight recorder on
`perf_counter` time, in rings that hold a whole window and outlive the engine.
This module reads an interval of them (`window_spans`), measures the offset
between `perf_counter` and the profiler's clock from the benchmark's own
`bench::` spans, which exist on both (`clock_offset`) — or, where a traced
stretch holds fewer than three of those, as the serving cell's does, from the
device's program launches and the host's waits for them
(`offset_from_launches`) — takes self time by name
(`self_times`, `self_segments`) and names the device's idle gaps by the phase
that covers most of each (`idle_by_phase`, with `trace_reader.merge` / `gaps`
/ `attribute` as they are).

Against a program that has no such store (a parent commit), and where a ring
wrapped inside the interval, `window_spans` returns None and so does every
reader: a partial window is never summed.
"""
from __future__ import annotations

import bisect
import collections
import json

import numpy as np

from benchmarks import trace_reader as tr

Row = collections.namedtuple(
    "Row", "name t0 t1 id parent trace thread attrs")

ROUND = "decode.round"
IDLE = "decode.idle_wait"
DISPATCH = "engine.dispatch"
#: the names that can cover an idle gap of the device
PHASES = (ROUND, IDLE, "host.gc", DISPATCH, "engine::")
UNNAMED = "unnamed"
NS = 1e9


def window_spans(t0, t1, prefix=None):
    """Rows of the program's spans that overlap the `perf_counter` interval
    [t0, t1), oldest first; None where the program keeps no such store or a
    ring wrapped inside the interval."""
    try:
        from paddle_tpu.obs import flight
    except ImportError:
        return None
    rec = flight.recorder()
    if not hasattr(rec, "spans_between"):
        return None
    spans, wrapped = rec.spans_between(t0, t1, prefix)
    if wrapped:
        return None
    return [Row(s.name, flight.perf_of(s.t0), flight.perf_of(s.t1),
                s.span_id, s.parent_id, s.trace_id, s.thread,
                s.attrs or {}) for s in spans]


def of_window(ctx):
    """The spans of the measured window and of the traced stretch in it,
    read once a run and shared by the readers (`ctx` is theirs to share)."""
    if "program_spans" not in ctx:
        c, tracer = ctx["counters"], ctx.get("tracer")
        lo = min(c["t_open"], getattr(tracer, "t_start", None)
                 or c["t_open"])
        ctx["program_spans"] = window_spans(lo, c["t_close"] + 1.0)
    return ctx["program_spans"]


def inside(rows, lo, hi, name=None, roots=False):
    """Rows wholly inside [lo, hi], by exact name, roots only if asked."""
    return [r for r in rows if r.t0 >= lo and r.t1 <= hi
            and (name is None or r.name == name)
            and (not roots or r.parent is None)]


def in_traces(rows, roots):
    """Rows that belong to the traces of the given root rows."""
    wanted = {r.trace for r in roots}
    return [r for r in rows if r.trace in wanted]


# ---------------------------------------------------------------------------
# the two clocks
# ---------------------------------------------------------------------------

def paired_offsets(records, host_spans, limit_ns=1e6) -> list:
    """The offsets `profiler_ns - perf_counter_ns` that the benchmark's
    spans propose: they are in `records` as `(name, t0, t1)` by
    `perf_counter` and in `host_spans` as `(name, start_ns, duration_ns)` by
    the profiler. Every pair of the same name and duration proposes an
    offset; the one under which most profiler spans land on a record within
    `limit_ns` wins, and the offsets of its pairs are returned. (A record
    runs ~0.2 ms longer than its annotation while a profiler session is on:
    the annotation is made inside it.)"""
    host = [(n, int(s), int(d)) for n, s, d in host_spans]
    recs = [(n, a * NS, (b - a) * NS) for n, a, b in records]

    def same(h, r):
        return h[0] == r[0] and abs(h[2] - r[2]) <= max(5e5, 0.02 * h[2])

    best = []
    for h0 in host:
        for r0 in recs:
            if not same(h0, r0):
                continue
            guess = h0[1] - r0[1]
            pairs = []
            for h in host:
                near = [h[1] - r[1] for r in recs if same(h, r)
                        and abs(h[1] - r[1] - guess) <= limit_ns]
                if near:
                    pairs.append(min(near, key=lambda o: abs(o - guess)))
            if len(pairs) > len(best) or (
                    len(pairs) == len(best) and pairs
                    and np.ptp(pairs) < np.ptp(best)):
                best = pairs
    return best


def clock_offset(records, host_spans, min_pairs=3, limit_ns=1e6):
    """`(offset_ns, residual_ns, pairs)` such that a `perf_counter` reading
    `t` is `t * 1e9 + offset_ns` on the profiler's clock, from the
    benchmark's spans on both (`paired_offsets`): the median over the pairs
    is the offset and the widest deviation from it the residual. None with
    fewer than `min_pairs` pairs or a residual over `limit_ns` (the other
    two values then still say why)."""
    best = paired_offsets(records, host_spans, limit_ns)
    if not best:
        return None, None, 0
    offset = float(np.median(best))
    residual = float(np.max(np.abs(np.asarray(best) - offset)))
    if len(best) < min_pairs or residual > limit_ns:
        return None, residual, len(best)
    return offset, residual, len(best)


def offset_from_launches(launches, waits, min_pairs=3, limit_ns=1e6):
    """`(offset_ns, residual_ns, pairs)` from the device's side. `launches`
    are the `(start_ns, end_ns)` of one program's whole launches on the
    profiler's clock, in order; `waits` the `(t0, t1)` of the host's waits
    for that program by `perf_counter` (the `.fetch` spans), in order, of
    which the launches' own are a run. A wait ends when its launch has
    ended and the value is back on the host: `end_ns = t1 * 1e9 + offset -
    lag`, with a lag that is never negative. The run is found where those
    differences are most nearly constant; the offset is the largest of them
    (the pair that lagged least), the residual its distance from their
    median. The offset is short of the true one by that least lag, 2.1 ms on
    the v5e (my chip run, PR 26): `offset_of` levels it by the benchmark's
    spans where the trace holds even one."""
    n = len(launches)
    if n < min_pairs or len(waits) < n:
        return None, None, min(n, len(waits))
    ends = np.asarray([e for _, e in launches], float)
    back = np.asarray([t1 for _, t1 in waits], float) * NS
    best, best_spread = None, np.inf
    for k in range(len(back) - n + 1):
        diffs = ends - back[k:k + n]
        spread = float(np.percentile(diffs, 90) - np.percentile(diffs, 10))
        if spread < best_spread:
            best, best_spread = diffs, spread
    offset = float(np.max(best))
    residual = offset - float(np.median(best))
    if residual > limit_ns:
        return None, residual, n
    return offset, residual, n


def offset_of(ctx, limit_ns=1e6):
    """`(offset_ns, residual_ns, pairs, source)` of the run. From the spans
    `ctx` holds on both clocks (those of the traced stretch, with some slack
    at its ends) where there are three. A serving cell's traced stretch
    holds fewer (a `bench::submit` a request): there the launches of the
    decode step's programs against the scheduler's waits for them give the
    alignment, its pairs and its residual, and the benchmark's one or two
    spans, where they lie within 10 ms above it, the level."""
    if "clock_offset" in ctx:
        return ctx["clock_offset"]
    trace, tracer = ctx.get("trace"), ctx.get("tracer")
    out = (None, None, 0, None)
    if trace and getattr(tracer, "t_start", None) is not None:
        lo, hi = tracer.t_start - 0.5, (tracer.t_stop or 0) + 0.5
        records = [r for r in list(ctx["spans"].records)
                   if r[2] >= lo and r[1] <= hi]
        out = clock_offset(records, trace["host_spans"],
                           limit_ns=limit_ns) + ("bench_spans",)
        rows = of_window(ctx)
        wanted = ctx["mix"].get("trace_names", {}).get("decode")
        if out[0] is None and rows and wanted and trace["devices"]:
            # the trace cuts the launches at its ends: leave them out
            runs = sorted((s, s + d) for n, s, d in
                          trace["devices"][0]["modules"] if n in wanted)[1:-1]
            waits = [(r.t0, r.t1) for r in rows
                     if r.name == ROUND + ".decode.fetch"]
            offset, residual, pairs = offset_from_launches(
                runs, waits, limit_ns=limit_ns)
            out = (offset, residual, pairs, "device_launches")
            few = paired_offsets(records, trace["host_spans"], limit_ns)
            if offset is not None and few and np.ptp(few) <= limit_ns \
                    and all(0 <= o - offset <= 10 * limit_ns for o in few):
                out = (float(np.median(few)),
                       max(residual, float(np.ptp(few))), pairs + len(few),
                       "device_launches+bench_spans")
    ctx["clock_offset"] = out
    return out


# ---------------------------------------------------------------------------
# self time, and the device's idle gaps by phase
# ---------------------------------------------------------------------------

def _ns(t):
    return int(round(t * NS))


def self_segments(rows) -> list:
    """`(name, start_ns, duration_ns)` for every stretch of a row that no
    child row covers, in `perf_counter` nanoseconds: the rows' self time as
    intervals. Children are found by their parent's id, wherever they
    ran."""
    kids = collections.defaultdict(list)
    for r in rows:
        if r.parent is not None:
            kids[r.parent].append(r)
    out = []
    for r in rows:
        lo, hi = _ns(r.t0), _ns(r.t1)
        covered = tr.merge([(k.name, _ns(k.t0), _ns(k.t1) - _ns(k.t0))
                            for k in kids.get(r.id, ())])
        out += [(r.name, a, b - a) for a, b in tr.gaps(covered, lo, hi)]
    return out


def self_times(rows) -> dict:
    """name -> seconds of self time (duration less what child rows
    cover)."""
    out = {}
    for name, _, d in self_segments(rows):
        out[name] = out.get(name, 0.0) + d / NS
    return out


def idle_by_phase(devices, segments, lo, hi, offset_ns=0.0) -> tuple:
    """`(whole, split)`, both name -> seconds of the devices' idle time in
    [lo, hi) (profiler nanoseconds), averaged over the devices. In `whole`
    each gap goes to the segment that covers most of it
    (`trace_reader.attribute`); in `split` it is divided among the segments
    by what each covers of it. What no segment covers is `UNNAMED`.
    `segments` are on `perf_counter` nanoseconds and moved by `offset_ns`;
    a gap is held against those that can overlap it only, found by
    bisection (a step's thousands of operations leave as many gaps)."""
    moved = sorted((s + offset_ns, s + offset_ns + d, n)
                   for n, s, d in segments)
    starts = [m[0] for m in moved]
    ends = list(np.maximum.accumulate([m[1] for m in moved])) if moved \
        else []
    devs = [d for d in devices if d["ops"]]
    whole, split = {}, {}
    for dev in devs:
        for gap in tr.gaps(tr.merge(dev["ops"]), lo, hi):
            near = [(n, s, e - s) for s, e, n in moved[
                bisect.bisect_right(ends, gap[0]):
                bisect.bisect_left(starts, gap[1])]]
            name = tr.attribute(gap, near, default=UNNAMED)
            whole[name] = whole.get(name, 0.0) + (gap[1] - gap[0]) / NS
            left = gap[1] - gap[0]
            for n, s, d in near:
                part = max(0.0, min(gap[1], s + d) - max(gap[0], s))
                split[n] = split.get(n, 0.0) + part / NS
                left -= part
            split[UNNAMED] = split.get(UNNAMED, 0.0) + max(0.0, left) / NS
    return tuple({n: v / len(devs) for n, v in d.items() if v}
                 for d in (whole, split))


def idle_of(ctx):
    """The traced stretch's idle seconds by phase, as `idle_by_phase` gives
    them; None without a device trace, the program's spans or a clock
    offset."""
    if "idle_by_phase" not in ctx:
        rows, scope, trace = of_window(ctx), ctx.get("scope"), \
            ctx.get("trace")
        offset = offset_of(ctx)[0]
        if rows is None or not scope or not trace or offset is None:
            ctx["idle_by_phase"] = None
        else:
            ctx["idle_by_phase"] = idle_by_phase(
                trace["devices"], self_segments(
                    [r for r in rows if r.name.startswith(PHASES)]),
                scope["lo"], scope["hi"], offset)
    return ctx["idle_by_phase"]


def rounds_of(ctx):
    """The scheduler rounds wholly inside the measured window, and every
    row of their traces; (None, None) without the program's spans."""
    rows = of_window(ctx)
    if rows is None:
        return None, None
    c = ctx["counters"]
    rounds = inside(rows, c["t_open"], c["t_close"], ROUND, roots=True)
    return rounds, in_traces(rows, rounds)


def p95_ms(rows):
    return float(np.percentile([r.t1 - r.t0 for r in rows], 95)) * 1e3 \
        if rows else None


# ---------------------------------------------------------------------------
# the log's line
# ---------------------------------------------------------------------------

def report(ctx):
    """One `[spans] {...}` line in the run's log: self time by name over
    the window, the device's idle seconds by phase over the traced stretch
    (each gap whole to its widest cover, and split by cover), the clock
    offset with its residual and its distance from the wall
    clock's, the spans read, and the requests' queue wait."""
    rows = of_window(ctx)
    if rows is None:
        print("[spans] " + json.dumps({"spans_read": None, "why": (
            "the program keeps no window of spans, or a ring wrapped "
            "inside the window")}), flush=True)
        return
    c = ctx["counters"]
    mine = [r for r in inside(rows, c["t_open"], c["t_close"])
            if r.name.startswith(PHASES)]
    offset, residual, pairs, source = offset_of(ctx)
    idle = idle_of(ctx) or (None, None)

    def by_size(d):
        return d and {n: round(s, 6) for n, s in sorted(
            d.items(), key=lambda kv: -kv[1])}

    line = {
        "window_self_s": by_size(self_times(mine)),
        "traced_idle_s": by_size(idle[0]), "traced_idle_split_s":
            by_size(idle[1]),
        "clock_offset_ns": offset, "clock_pairs": pairs,
        "clock_source": source,
        "clock_residual_us": None if residual is None else residual / 1e3,
        "spans_read": len(rows), "wrapped": False, "dropped_in_window": 0}
    if offset is not None:
        from paddle_tpu.obs import flight

        # 0 where the profiler stamps with the wall clock, as `wall_of`
        line["offset_from_wall_clock_ms"] = (
            offset - (flight.wall_of(0.0)) * NS) / 1e6
    waits = [r.attrs["queue_wait_s"] for r in inside(
        rows, c["t_open"], c["t_close"] + 1.0, "decode.sequence")
        if "queue_wait_s" in r.attrs]
    if waits:
        line["queue_wait_ms"] = {"n": len(waits),
                                 "mean": float(np.mean(waits)) * 1e3,
                                 "max": float(np.max(waits)) * 1e3}
    print("[spans] " + json.dumps(line), flush=True)
