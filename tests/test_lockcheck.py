"""Lock-order/race checker FSM (paddle_tpu/analysis/lockcheck.py):
acquisition-order cycle detection (a real two-thread AB/BA interleaving),
held-across-blocking and held-across-wait probes, RLock reentrancy (must
NOT report), recursive plain-Lock acquire (fails loudly instead of
deadlocking), condition-variable held-set bookkeeping, and a ServingPool
run under the enabled checker. All cross-thread coordination is
event-based — no sleeps (tier-1 budget)."""
import threading

import numpy as np
import pytest

from paddle_tpu.analysis import lockcheck, locks
from paddle_tpu.analysis.lockcheck import (
    InstrumentedCondition, InstrumentedLock, InstrumentedRLock,
    LockOrderError, _Registry,
)


@pytest.fixture
def reg():
    return _Registry()

# the `checker` fixture (enable globally, reset, restore) lives in
# conftest.py — shared with test_batching's pool-discipline test


# ---------------------------------------------------------------------------
# ordering cycles
# ---------------------------------------------------------------------------

def test_ab_ba_two_thread_cycle_detected(reg):
    """Thread 1 nests A->B, thread 2 nests B->A (sequenced by an event so
    neither blocks): the classic latent deadlock must surface as a cycle
    even though the fatal interleaving never fired."""
    A, B = InstrumentedLock("A", reg), InstrumentedLock("B", reg)
    first_done = threading.Event()

    def t1():
        with A:
            with B:
                pass
        first_done.set()

    def t2():
        assert first_done.wait(5)
        with B:
            with A:
                pass

    th1, th2 = threading.Thread(target=t1), threading.Thread(target=t2)
    th1.start(); th2.start(); th1.join(5); th2.join(5)
    assert any(set(c) == {"A", "B"} for c in reg.cycles())


def test_consistent_order_is_clean(reg):
    A, B, C = (InstrumentedLock(n, reg) for n in "ABC")
    for _ in range(3):
        with A:
            with B:
                with C:
                    pass
    assert reg.cycles() == []
    assert reg.violations == []
    # edges recorded in the direction acquired
    assert "B" in reg.edges["A"] and "C" in reg.edges["B"]


def test_three_lock_ring_cycle(reg):
    A, B, C = (InstrumentedLock(n, reg) for n in "ABC")
    for outer, inner in ((A, B), (B, C), (C, A)):
        with outer:
            with inner:
                pass
    assert any(set(c) == {"A", "B", "C"} for c in reg.cycles())


def test_two_distinct_cycles_over_same_nodes_both_reported(reg):
    """A->B->C->A and A->C->B->A are different ordering hazards; the
    dedup must key on the rotated path, not the node set."""
    A, B, C = (InstrumentedLock(n, reg) for n in "ABC")
    for chain in ((A, B, C), (A, C, B)):
        first, second, third = chain
        with first:
            with second:
                with third:
                    pass
        # close each ring: third -> first
        with third:
            with first:
                pass
    cycles = [c for c in reg.cycles() if len(c) == 4]
    assert ["A", "B", "C", "A"] in cycles
    assert ["A", "C", "B", "A"] in cycles


def test_same_name_different_instances_self_loop(reg):
    """Two instances sharing a name that nest form a self-loop — a real
    hazard (same-class instances need an ordering discipline)."""
    r1 = InstrumentedLock("serving.request", reg)
    r2 = InstrumentedLock("serving.request", reg)
    with r1:
        with r2:
            pass
    assert ["serving.request", "serving.request"] in reg.cycles()


# ---------------------------------------------------------------------------
# blocking probes
# ---------------------------------------------------------------------------

def test_lock_held_across_blocking_call_reported(reg):
    G = InstrumentedLock("guard", reg)
    with G:
        reg.note_blocking("xla.dispatch")    # simulated dispatch under G
    vio = [v for v in reg.violations if v.kind == "held-across-blocking"]
    assert len(vio) == 1
    assert "guard" in vio[0].message and "xla.dispatch" in vio[0].message


def test_blocking_after_release_is_clean(reg):
    G = InstrumentedLock("guard", reg)
    with G:
        pass
    reg.note_blocking("xla.dispatch")
    assert reg.violations == []


def test_public_blocking_region_path(checker):
    L = locks.new_lock("guard")
    assert locks.is_checked(L)
    with L:
        with locks.blocking_region("aot.compile"):
            pass
    with pytest.raises(LockOrderError) as ei:
        checker.assert_clean()
    assert "held-across-blocking" in str(ei.value)
    assert ei.value.report["violations"]


def test_blocking_region_noop_when_disabled():
    was_enabled = lockcheck.enabled()
    lockcheck.disable()
    lockcheck.reset()
    try:
        L = locks.new_lock("plain")
        assert not locks.is_checked(L)       # plain threading.Lock
        with L:
            with locks.blocking_region("anything"):
                pass
        assert lockcheck.report()["violations"] == []
    finally:
        if was_enabled:                      # restore env-driven mode
            lockcheck.enable()


# ---------------------------------------------------------------------------
# reentrancy
# ---------------------------------------------------------------------------

def test_rlock_reentrancy_not_reported(reg):
    R = InstrumentedRLock("R", reg)
    with R:
        with R:
            with R:
                assert reg.held_names() == ["R"]  # one entry, not three
    assert reg.held_names() == []
    assert reg.violations == []
    assert reg.cycles() == []
    assert reg.acquire_counts["R"] == 1          # outermost pair only


def test_rlock_nested_under_lock_single_edge(reg):
    A = InstrumentedLock("A", reg)
    R = InstrumentedRLock("R", reg)
    with A:
        with R:
            with R:
                pass
    assert reg.edges == {"A": {"R": reg.edges["A"]["R"]}}
    assert reg.cycles() == []


def test_recursive_plain_lock_acquire_raises(reg):
    L = InstrumentedLock("L", reg)
    with L:
        with pytest.raises(RuntimeError, match="re-acquired"):
            L.acquire()
    assert any(v.kind == "recursive-acquire" for v in reg.violations)


def test_recursive_acquire_with_timeout_recorded_not_raised(reg):
    """A finite timeout means the call does return (False) — keep that
    contract, but the deadlock pattern must still land in the report."""
    L = InstrumentedLock("L", reg)
    with L:
        assert L.acquire(timeout=0.01) is False
    assert any(v.kind == "recursive-acquire" for v in reg.violations)
    # non-blocking try-acquire is a legitimate pattern: no violation
    reg.violations.clear()
    with L:
        assert L.acquire(blocking=False) is False
    assert not any(v.kind == "recursive-acquire" for v in reg.violations)


# ---------------------------------------------------------------------------
# condition variables
# ---------------------------------------------------------------------------

def test_condition_wait_releases_held_set(reg):
    """While a consumer waits, the cv lock must NOT appear held for that
    thread — and a producer thread can take it, hand over an item, and
    wake the consumer. Event-sequenced, no sleeps."""
    L = InstrumentedLock("q", reg)
    cv = InstrumentedCondition(L)
    state = {"item": None, "waiting": threading.Event(),
             "held_during_wait": None}

    def consumer():
        with cv:
            state["waiting"].set()
            while state["item"] is None:
                cv.wait(5)
        state["got"] = state["item"]

    def producer():
        assert state["waiting"].wait(5)
        with cv:  # acquirable because the waiter released it
            state["held_during_wait"] = reg.held_names()
            state["item"] = 42
            cv.notify()

    tc, tp = threading.Thread(target=consumer), threading.Thread(
        target=producer)
    tc.start(); tp.start(); tc.join(5); tp.join(5)
    assert state["got"] == 42
    assert state["held_during_wait"] == ["q"]    # producer's view only
    assert reg.held_names() == []
    assert reg.violations == []


def test_other_lock_held_across_wait_reported(reg):
    L = InstrumentedLock("q", reg)
    X = InstrumentedLock("outer", reg)
    cv = InstrumentedCondition(L)
    with X:
        with cv:
            cv.wait(0.01)                        # times out immediately
    vio = [v for v in reg.violations if v.kind == "held-across-wait"]
    assert len(vio) == 1 and "outer" in vio[0].message


def test_wait_for_predicate(reg):
    cv = InstrumentedCondition(InstrumentedLock("q", reg))
    box = {}

    def setter():
        with cv:
            box["v"] = 1
            cv.notify_all()

    t = threading.Thread(target=setter)
    with cv:
        t.start()
        assert cv.wait_for(lambda: "v" in box, timeout=5)
    t.join(5)
    assert reg.violations == []


def test_wait_without_lock_does_not_plant_phantom_hold(reg):
    """cv.wait() without holding the lock raises (host misuse) but must
    NOT leave a phantom entry in the held-set — that would fabricate
    recursive-acquire / held-across-blocking reports in unrelated code."""
    L = InstrumentedLock("q", reg)
    cv = InstrumentedCondition(L)
    with pytest.raises(RuntimeError):
        cv.wait(0.01)
    assert reg.held_names() == []
    with L:                       # must not be flagged recursive-acquire
        pass
    reg.note_blocking("probe")    # and no phantom held-across-blocking
    assert [v for v in reg.violations
            if v.kind in ("recursive-acquire",
                          "held-across-blocking")] == []


def test_cross_thread_lock_handoff_clears_acquirer(reg):
    """threading.Lock permits acquire in A / release in B (handoff). The
    acquirer's held-set must be cleared by the cross-thread release, or
    A later sees a false recursive-acquire and phantom blocking reports."""
    L = InstrumentedLock("handoff", reg)
    acquired, released = threading.Event(), threading.Event()
    result = {}

    def acquirer():
        L.acquire()
        acquired.set()
        assert released.wait(5)
        result["held_after"] = reg.held_names()
        with L:                    # must not raise recursive-acquire
            pass
        result["reacquire_ok"] = True

    t = threading.Thread(target=acquirer)
    t.start()
    assert acquired.wait(5)
    L.release()                    # handoff: released by the main thread
    released.set()
    t.join(5)
    assert result["held_after"] == []
    assert result.get("reacquire_ok")
    assert reg.violations == []


# ---------------------------------------------------------------------------
# report / assert_clean / long holds
# ---------------------------------------------------------------------------

def test_long_hold_is_warning_only(reg):
    reg.hold_threshold_s = 0.0                   # any hold triggers it
    L = InstrumentedLock("slow", reg)
    with L:
        pass
    warns = [v for v in reg.violations if v.kind == "long-hold"]
    assert warns and all(v.warning for v in warns)


def test_assert_clean_raises_on_cycle(checker):
    A, B = locks.new_lock("A"), locks.new_lock("B")
    with A:
        with B:
            pass
    with B:
        with A:
            pass
    with pytest.raises(LockOrderError) as ei:
        checker.assert_clean()
    assert any(set(c) == {"A", "B"} for c in ei.value.report["cycles"])
    checker.reset()
    checker.assert_clean()                       # reset clears everything


def test_report_shape(reg):
    L = InstrumentedLock("a", reg)
    with L:
        pass
    rep = reg.report()
    assert rep["locks"]["a"]["acquires"] == 1
    assert rep["locks"]["a"]["max_hold_ms"] >= 0
    assert rep["cycles"] == [] and rep["violations"] == []


def test_a_gc_callback_that_locks_inside_the_recorder_does_not_hang(reg):
    """A garbage collection can start at any allocation, the recorder's own
    critical sections included, and its callbacks (the tracer's `host.gc`
    span) take instrumented locks on that very thread: the recorder's
    guard has to let its own thread back in. Stood in for by a `_held`
    that takes another instrumented lock, as such a callback would."""
    inner = InstrumentedLock("obs.trace", reg)
    outer = InstrumentedLock("decode.engine", reg)
    plain_held, busy = reg._held, []

    def held_with_a_collection():
        if not busy:
            busy.append(True)
            with inner:             # the callback's span takes its lock
                pass
            busy.pop()
        return plain_held()

    reg._held = held_with_a_collection
    done = threading.Event()

    def run():
        with outer:
            pass
        done.set()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert done.wait(5), "the recorder's guard deadlocked on its own thread"
    assert reg.cycles() == [] and reg.violations == []


# ---------------------------------------------------------------------------
# the serving pool under the enabled checker (fake layer: no XLA compile)
# ---------------------------------------------------------------------------

class _Out:
    def __init__(self, a):
        self._a = a

    def numpy(self):
        return self._a


class _FakeLayer:
    input_spec = [{"shape": [2], "dtype": "float32"}]
    num_outputs = 1

    def __call__(self, x):
        return _Out(np.asarray(x) * 2.0)


def test_serving_pool_lock_discipline_clean(checker):
    """Construct a ServingPool AFTER enable(): all its named locks are
    instrumented. A burst of requests plus shutdown must leave no
    ordering cycles and no lock held across the execute blocking region.
    (The full fault-injection run does the same end-to-end over a real
    model in tests/test_serving_fault_injection.py.)"""
    from paddle_tpu.inference import Predictor, ServingPool

    pool = ServingPool(
        predictor=Predictor(None, _shared_layer=_FakeLayer()),
        size=2, max_queue_depth=32, default_timeout=5.0)
    try:
        futs = [pool.submit(lambda p: p.run([np.ones(2, np.float32)]))
                for _ in range(12)]
        for f in futs:
            out, = f.result()
            np.testing.assert_allclose(out, np.full(2, 2.0))
    finally:
        pool.shutdown(5)
    rep = checker.assert_clean()
    observed = set(rep["locks"])
    assert {"serving.pool", "serving.request"} <= observed
