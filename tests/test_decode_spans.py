"""Scheduler-phase spans of the decode engine and the train engine
(docs/observability.md, "Reading an idle gap"): the phases tile a round,
worker-side spans name their round, a window of spans is read whole after
the engine is gone, a sequence's span decomposes, a slow round says so by
itself, and with tracing off nothing is made at all.

One tiny engine per test, demo weights, a handful of tokens: each compiles
the same few executables, which jax caches within the process.
"""
import collections
import gc
import logging
import threading
import time

import numpy as np
import pytest

from paddle_tpu.inference.decode import demo
from paddle_tpu.inference.decode import engine as engine_mod
from paddle_tpu.obs import flight, trace
from paddle_tpu.obs.flight import FlightRecorder, Span

ROUND = "decode.round"


@pytest.fixture(autouse=True)
def _clean_tracing():
    was = trace.enabled()
    trace.enable()
    trace.set_sample_rate(1.0)
    flight.recorder().reset()
    yield
    flight.recorder().reset()
    (trace.enable if was else trace.disable)()


def serve(n=3, max_new=6, **over):
    """Run `n` requests through a fresh tiny engine and shut it down;
    returns (engine, perf_counter interval that holds its whole life)."""
    t0 = time.perf_counter()
    eng = demo.tiny_engine(1, **over)
    streams = [eng.submit(demo.demo_prompt(3 + i, 8 + 8 * (i % 2)), max_new)
               for i in range(n)]
    outs = [s.result() for s in streams]
    assert all(len(o) == max_new for o in outs)
    eng.shutdown()
    return eng, (t0, time.perf_counter())


def by_name(spans):
    out = collections.defaultdict(list)
    for s in spans:
        out[s.name].append(s)
    return out


def children(spans, parent):
    return sorted((s for s in spans if s.parent_id == parent.span_id),
                  key=lambda s: s.t0)


def union(spans):
    total, at = 0.0, -np.inf
    for s in sorted(spans, key=lambda s: s.t0):
        total += max(0.0, s.t1 - max(s.t0, at))
        at = max(at, s.t1)
    return total


# ---------------------------------------------------------------------------
# rounds and phases
# ---------------------------------------------------------------------------

def test_phases_tile_every_round_and_siblings_never_overlap():
    eng, (t0, t1) = serve()
    spans, wrapped = flight.recorder().spans_between(t0, t1, "decode.")
    assert not wrapped
    names = by_name(spans)
    rounds = names[ROUND]
    assert len(rounds) == eng.stats()["rounds"] >= 6
    assert [r.attrs["round"] for r in rounds] == list(
        range(1, len(rounds) + 1))
    allowed = {ROUND, "decode.idle_wait", ROUND + ".admit"} | {
        f"{ROUND}.{kind}{suffix}" for kind in ("prefill", "decode")
        for suffix in ("", ".grow", ".pack", ".handoff", ".enqueue",
                       ".fetch", ".deliver")} - {ROUND + ".prefill.grow"}
    assert {n for n in names if n.startswith((ROUND, "decode.idle"))} \
        <= allowed
    # coverage is judged over all rounds together: with six test workers
    # on one host a single round (or phase) can lose a few hundred
    # microseconds to the scheduler between two spans, which says nothing
    # of the tiling
    round_time = round_covered = phase_time = phase_covered = 0.0
    for r in rounds:
        assert r.parent_id is None
        assert {"round", "active", "prefilling", "waiting"} <= set(r.attrs)
        kids = children(spans, r)
        assert {k.name for k in kids} <= {
            ROUND + ".admit", ROUND + ".prefill", ROUND + ".decode"}
        assert all(k.trace_id == r.trace_id for k in kids)
        round_time += r.t1 - r.t0
        round_covered += union(kids)
        for a, b in zip(kids, kids[1:]):
            assert a.t1 <= b.t0 + 1e-6
        # a phase's own children tile it too, on the scheduler thread
        for phase in kids:
            inner = [k for k in children(spans, phase)
                     if k.thread == phase.thread]
            for a, b in zip(inner, inner[1:]):
                assert a.t1 <= b.t0 + 1e-6
            if phase.name != ROUND + ".admit":
                phase_time += phase.t1 - phase.t0
                phase_covered += union(inner)
    assert round_covered >= 0.95 * round_time, (round_covered, round_time)
    assert phase_covered >= 0.9 * phase_time, (phase_covered, phase_time)
    # `.enqueue` and `.fetch` run on the worker under the scheduler's
    # `.handoff`, so that its self time is the hand-off alone
    for hand in names[ROUND + ".decode.handoff"]:
        kids = children(spans, hand)
        assert [k.name for k in kids] == [ROUND + ".decode.enqueue",
                                          ROUND + ".decode.fetch"]
        assert all(k.thread != hand.thread for k in kids)
        assert hand.t0 <= kids[0].t0 and kids[-1].t1 <= hand.t1
    # at most a dozen scheduler-side spans a round, nothing per token
    per_round = collections.Counter(
        s.trace_id for s in spans if s.name.startswith(ROUND))
    assert max(per_round.values()) <= 15


def test_worker_spans_name_the_round_that_submitted_them():
    _, (t0, t1) = serve()
    spans, _ = flight.recorder().spans_between(t0, t1, "decode.")
    names = by_name(spans)
    rounds = {r.attrs["round"]: r for r in names[ROUND]}
    steps = names["decode.step"]
    chunks = names["decode.prefill"] + names["decode.prefill_chunk"]
    assert steps and chunks
    for s in steps + chunks:
        r = rounds[s.attrs["round"]]
        assert r.t0 <= s.t0 and s.t1 <= r.t1
        # they stay in the traces they were in: a step's own, a request's
        assert s.trace_id != r.trace_id
    assert all(s.parent_id is None for s in steps)
    seqs = {s.trace_id for s in names["decode.sequence"]}
    assert all(c.trace_id in seqs for c in chunks)


def test_sequence_span_decomposes_into_wait_prefill_decode():
    eng, (t0, t1) = serve(n=4)
    spans, _ = flight.recorder().spans_between(t0, t1, "decode.sequence")
    assert len(spans) == 4
    for s in spans:
        a = s.attrs
        parts = a["queue_wait_s"] + a["prefill_s"] + a["decode_s"]
        assert abs(parts - (s.t1 - s.t0)) < 1e-3
        assert min(a["queue_wait_s"], a["prefill_s"], a["decode_s"]) >= 0
        assert a["chunks"] >= 1
        assert 1 <= a["round_admitted"] <= a["round_finished"] \
            <= eng.stats()["rounds"]
    # a fresh registry: the process-wide one is shared with other engines
    assert eng._h_queue_wait.name == "decode.queue_wait_seconds"
    assert eng._h_queue_wait.count >= 4


def test_a_sequence_cancelled_in_the_queue_is_all_queue_wait():
    gate = threading.Event()

    def hold(tag, ids, info):
        gate.wait(5.0)

    eng = demo.tiny_engine(1, fault_hook=hold, decode_buckets=(1,))
    first = eng.submit(demo.demo_prompt(1, 8), 2)
    second = eng.submit(demo.demo_prompt(2, 8), 2)
    time.sleep(0.05)
    second.cancel()
    gate.set()
    first.result()
    eng.shutdown()
    done = [s for s in flight.recorder().spans_between(
        0, time.perf_counter(), "decode.sequence")[0]
        if s.status == "cancelled"]
    assert len(done) == 1
    a = done[0].attrs
    assert a["prefill_s"] == 0 and a["decode_s"] == 0
    assert abs(a["queue_wait_s"] - (done[0].t1 - done[0].t0)) < 1e-3


def test_stats_publish_the_occupancy_counters_and_rounds():
    eng, _ = serve()
    st = eng.stats()
    assert st["step_slots"] == eng._step_slots > 0
    assert st["step_active"] == eng._step_active > 0
    assert st["occupancy"] == st["step_active"] / st["step_slots"]
    assert st["rounds"] >= st["steps"] and st["slow_rounds"] == 0


# ---------------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------------

def test_a_window_is_read_whole_after_shutdown_and_thread_death():
    eng, (t0, t1) = serve()
    assert not eng._thread.is_alive()
    spans, wrapped = flight.recorder().spans_between(t0, t1, ROUND)
    assert not wrapped
    rounds = [s for s in spans if s.name == ROUND]
    assert len(rounds) == eng.stats()["rounds"]
    # the scheduler's and the worker's rings hold a window, not 512 spans
    caps = {r.thread_name: r.cap for r in flight.recorder()._all_rings()}
    assert caps["DecodeEngine-scheduler"] == flight.ENGINE_RING_SPANS
    assert max(c for n, c in caps.items()
               if n.startswith("ServingPool-worker")) \
        == flight.ENGINE_RING_SPANS
    # other threads register and sweep the dead ones: the reserved rings
    # are kept, and the window still reads whole
    ts = [threading.Thread(target=lambda: trace.root_span("t").end())
          for _ in range(20)]
    for t in ts:
        t.start()
        t.join()
    again, wrapped = flight.recorder().spans_between(t0, t1, ROUND)
    assert not wrapped and len(again) == len(spans)
    # an interval before the engine was built holds nothing of it
    assert flight.recorder().spans_between(t0 - 10, t0, ROUND) == ([], False)


def test_a_wrap_is_reported_for_the_interval_it_fell_in():
    rec = FlightRecorder(ring_spans=8)
    base = time.perf_counter()
    for i in range(20):
        rec.record(Span(1, 100 + i, None, f"s{i}", flight.wall_of(base + i),
                        flight.wall_of(base + i + 0.5)))
    assert rec.dropped_wraps == 12
    # spans 0..11 were overwritten: an interval that reaches into them
    # is not whole, one that starts after the last dropped span is
    spans, wrapped = rec.spans_between(base + 5, base + 30)
    assert wrapped and [s.name for s in spans] == [
        f"s{i}" for i in range(12, 20)]
    spans, wrapped = rec.spans_between(base + 12, base + 30)
    assert not wrapped and len(spans) == 8
    spans, wrapped = rec.spans_between(base + 14.2, base + 16.1, "s1")
    assert not wrapped and [s.name for s in spans] == ["s14", "s15", "s16"]
    assert rec.spans_between(base + 14, base + 16, ("s14", "s15"))[0] \
        == spans[:2]


def test_reserve_grows_the_callers_ring_and_keeps_what_it_held():
    rec = FlightRecorder(ring_spans=4)
    for i in range(6):
        rec.record(Span(1, i + 1, None, f"s{i}", float(i), i + 0.5))
    rec.reserve(16)
    for i in range(6, 12):
        rec.record(Span(1, i + 1, None, f"s{i}", float(i), i + 0.5))
    ring = rec._all_rings()[0]
    assert ring.cap == 16
    assert [s.name for s in ring.snapshot()] == [
        f"s{i}" for i in range(2, 12)]
    rec.reserve(8)                       # never shrinks
    assert ring.cap == 16 and rec.stats()["spans_held"] == 10


def test_reserved_rings_of_dead_threads_are_kept_in_a_bounded_number():
    rec = FlightRecorder(ring_spans=4)

    def writer(i, reserve):
        if reserve:
            rec.reserve(64)
        rec.record(Span(1, i + 1, None, f"w{i}", 1.0, 2.0))

    for i in range(flight.RESERVED_RINGS_KEPT + 3):
        t = threading.Thread(target=writer, args=(i, True))
        t.start()
        t.join()
    for i in range(40):
        t = threading.Thread(target=writer, args=(100 + i, False))
        t.start()
        t.join()
    names = [s.name for r in rec._all_rings() for s in r.snapshot()]
    kept = [n for n in names if int(n[1:]) < 100]
    # the newest reserved rings outlive forty short-lived threads
    assert len(kept) >= flight.RESERVED_RINGS_KEPT
    assert f"w{flight.RESERVED_RINGS_KEPT + 2}" in kept
    assert rec.stats()["retired_rings"] <= 16 + flight.RESERVED_RINGS_KEPT


# ---------------------------------------------------------------------------
# a slow round says so by itself
# ---------------------------------------------------------------------------

def test_a_round_held_for_over_a_second_is_pinned_counted_and_logged(
        caplog):
    calls = {"decode": 0}

    def hook(tag, ids, info):
        if tag == "decode":
            calls["decode"] += 1
            if calls["decode"] == 12:
                time.sleep(1.2)

    caplog.set_level(logging.WARNING, logger=engine_mod.__name__)
    eng = demo.tiny_engine(1, fault_hook=hook)
    # warm rounds first: a round is slow against the median of its past
    eng.submit(demo.demo_prompt(1, 8), 20).result()
    st = eng.stats()
    eng.shutdown()
    assert calls["decode"] >= 12 and st["slow_rounds"] == 1
    pinned = [p for p in flight.recorder().postmortems()
              if p[1] == "slow_round"]
    assert len(pinned) == 1
    spans = flight.recorder().spans_for(pinned[0][0])
    root = [s for s in spans if s.name == ROUND][0]
    assert root.t1 - root.t0 >= 1.2
    assert ROUND + ".decode.handoff" in {s.name for s in spans}
    lines = [r.getMessage() for r in caplog.records
             if "slow round" in r.getMessage()]
    assert len(lines) == 1
    assert f"slow round {root.attrs['round']}:" in lines[0]
    assert "decode.round.decode.handoff" in lines[0]
    assert "bucket 1" in lines[0] and "members [1]" in lines[0]


def test_slow_round_thresholds_are_the_documented_constants():
    assert engine_mod._SLOW_ROUND_S == 1.0
    assert engine_mod._SLOW_ROUND_X == 4.0
    assert engine_mod._SLOW_ROUND_HISTORY == 64


def test_a_cold_first_round_is_not_judged_slow(monkeypatch, caplog):
    # the first rounds of a cold engine compile for seconds: with no past
    # to compare with, nothing is pinned
    monkeypatch.setattr(engine_mod, "_SLOW_ROUND_S", 0.0)
    monkeypatch.setattr(engine_mod, "_SLOW_ROUND_X", 1e9)
    eng, _ = serve(n=1)
    assert eng.stats()["slow_rounds"] == 0
    assert not [p for p in flight.recorder().postmortems()
                if p[1] == "slow_round"]


def test_work_left_waiting_by_an_idle_scheduler_is_judged_like_a_round(
        caplog):
    eng = demo.tiny_engine(1)
    eng.submit(demo.demo_prompt(1, 8), 12).result()   # a past to compare
    caplog.set_level(logging.WARNING, logger=engine_mod.__name__)
    idle = (trace.open_span("decode.idle_wait"), time.perf_counter() - 5.0)
    eng._end_idle(idle, oldest=time.perf_counter() - 2.5)
    assert eng.stats()["slow_rounds"] == 1
    eng.shutdown()
    assert any("slow idle wait" in r.getMessage() for r in caplog.records)
    waited = [s.attrs["work_waited_s"] for s in
              flight.recorder().spans_between(0, time.perf_counter(),
                                              "decode.idle_wait")[0]
              if s.attrs and s.attrs.get("work_waited_s", 0) > 1]
    assert len(waited) == 1 and 2.4 < waited[0] < 3.0


def test_generation_two_collections_are_host_gc_spans():
    trace.watch_gc()
    trace.watch_gc()                     # idempotent
    assert gc.callbacks.count(trace._on_gc) == 1
    t0 = time.perf_counter()
    with trace.root_span("holder") as root:
        gc.collect()
    gc.collect(0)                        # younger generations: nothing
    spans, _ = flight.recorder().spans_between(t0, time.perf_counter(),
                                               "host.gc")
    assert len(spans) == 1
    assert spans[0].trace_id == root.trace_id
    assert spans[0].parent_id == root.ctx.span_id
    assert "collected" in spans[0].attrs
    t0 = time.perf_counter()
    gc.collect()                         # outside any span: a root
    spans, _ = flight.recorder().spans_between(t0, time.perf_counter(),
                                               "host.gc")
    assert len(spans) == 1 and spans[0].parent_id is None


# ---------------------------------------------------------------------------
# off means off
# ---------------------------------------------------------------------------

def test_with_tracing_off_no_span_is_made_and_no_ring_reserved(monkeypatch):
    trace.disable()
    made = []
    monkeypatch.setattr(trace, "_OpenSpan", lambda *a, **k: made.append(a))
    monkeypatch.setattr(flight.Span, "__init__",
                        lambda self, *a, **k: made.append(a))
    eng, _ = serve()
    assert made == []
    rec = flight.recorder()
    assert rec.recorded == 0 and rec._all_rings() == []
    assert rec.stats()["spans_held"] == 0
    # the scheduler still counts its rounds and would still log a stall
    assert eng.stats()["rounds"] >= 6
    assert trace.detached() is trace.null_span()
    assert trace.span("x", profile=True) is trace.null_span()


def test_profile_spans_are_annotations_with_the_pt_prefix(monkeypatch):
    seen = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("in", self.name))

        def __exit__(self, *exc):
            seen.append(("out", self.name))

    monkeypatch.setattr(trace, "_annotation_cls", Ann)
    with trace.root_span("decode.round", profile=True):
        with trace.span("decode.round.admit", profile=True):
            pass
        with trace.span("quiet"):
            trace.event("decode.step_join")
    assert trace.PROFILE_PREFIX == "pt::"
    assert seen == [("in", "pt::decode.round"),
                    ("in", "pt::decode.round.admit"),
                    ("out", "pt::decode.round.admit"),
                    ("out", "pt::decode.round")]


def test_detached_hides_the_context_from_a_callee_and_restores_it():
    with trace.root_span("outer") as outer:
        with trace.detached():
            assert trace.current() is None
            assert trace.span("child") is trace.null_span()
        assert trace.current() is outer.ctx
        with trace.span("child") as child:
            assert child.parent_id == outer.ctx.span_id


def test_a_span_can_be_backdated_to_a_reading_taken_before_it():
    t0 = time.perf_counter() - 0.25
    with trace.root_span("late", t0=t0) as sp:
        pass
    assert 0.25 <= sp.duration < 0.35
    got = flight.recorder().spans_for(sp.trace_id)[0]
    assert abs((got.t1 - got.t0) - sp.duration) < 1e-6


# ---------------------------------------------------------------------------
# the train engine through the same door
# ---------------------------------------------------------------------------

def _mlp_engine():
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn

    class MLP(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(8, 16)
            self.fc2 = nn.Linear(16, 8)

        def forward(self, x):
            return self.fc2(paddle.nn.functional.relu(self.fc1(x)))

        def loss(self, x, y):
            return ((self.forward(x) - y) ** 2).mean()

    paddle.seed(0)
    model = MLP()
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    rng = np.random.RandomState(0)
    xy = tuple(paddle.to_tensor(rng.randn(8, 8).astype("float32"))
               for _ in range(2))
    return dist.parallelize(model, opt, mesh=dist.build_mesh(dp=8)), xy


def test_every_train_call_is_an_engine_dispatch_root_with_three_children():
    from paddle_tpu import profiler

    eng, xy = _mlp_engine()
    before = eng._h_dispatch.snapshot()     # a process-wide histogram
    t0 = time.perf_counter()
    eng.train_batch(*xy)
    eng.train_batches([xy] * 3)
    eng.train_batches([xy] * 3)
    assert not profiler.host_recording()
    spans, wrapped = flight.recorder().spans_between(
        t0, time.perf_counter(), "engine")
    assert not wrapped
    roots = [s for s in spans if s.name == "engine.dispatch"]
    assert [(r.attrs["steps"], r.attrs["cold"]) for r in roots] == [
        (1, True), (3, True), (3, False)]
    for r in roots:
        assert r.parent_id is None
        kids = children(spans, r)
        assert [k.name for k in kids] == [
            "engine::device_put", "engine::dispatch", "engine::write_back"]
        assert r.t0 <= kids[0].t0 and kids[-1].t1 <= r.t1
        for a, b in zip(kids, kids[1:]):
            assert a.t1 <= b.t0 + 1e-6
    # the calling thread keeps a window of them
    ring = [r for r in flight.recorder()._all_rings()
            if r.thread_name == threading.current_thread().name][0]
    assert ring.cap == flight.ENGINE_RING_SPANS
    # one interval, timed once: the histogram took the span's duration
    h = eng._h_dispatch.snapshot()
    took = sum(s.t1 - s.t0 for s in spans if s.name == "engine::dispatch")
    assert h["count"] - before["count"] == 3
    assert abs(h["sum"] - before["sum"] - took) < 1e-4


def test_train_calls_make_no_span_with_tracing_off():
    trace.disable()
    eng, xy = _mlp_engine()
    before = eng._h_dispatch.count
    eng.train_batches([xy] * 2)
    assert flight.recorder().recorded == 0
    assert eng._h_dispatch.count == before + 1   # the histogram stays on
