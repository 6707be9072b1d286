"""paddle_tpu.analysis.lockcheck — runtime lock-order / race checker.

The serving runtime, dynamic batcher, prefetch daemons and checkpoint
machinery are thread-heavy (worker pools, supervisors, condition
variables, timers). The classic failure modes there are silent until
production:

* **lock-order inversion** — thread 1 takes A then B, thread 2 takes B
  then A: a latent deadlock that only fires under the right interleaving;
* **blocking under a lock** — an XLA dispatch, `queue` wait or file write
  performed while holding a hot lock serializes the whole pool (and, if
  the blocked call needs the same lock to make progress, deadlocks);
* **long holds** — a convoy: everything else piles up on one mutex.

This module is the dynamic half of `paddle_tpu.analysis` (the static
half is tracelint). It is **opt-in**: set ``PADDLE_TPU_LOCKCHECK=1`` in
the environment (before the locks are constructed) or call ``enable()``
programmatically. When off, `analysis.locks.new_lock(name)` returns a
plain `threading.Lock` — zero overhead in production.

When on, every named lock is wrapped so the checker can record, per
thread, the set of locks currently held, and globally:

* the **acquisition-order graph**: an edge A→B each time B is acquired
  while A is held (first witness site + thread kept per edge). Cycles in
  this graph are potential deadlocks — reported by ``report()`` /
  ``assert_clean()`` even if the fatal interleaving never fired. Edges
  are per lock *name*, so two instances of the same name nesting (e.g.
  two request locks) form a self-loop cycle — also a real hazard unless
  instances are ordered.
* **held-across-blocking violations**: framework blocking points (XLA
  dispatch, compile-cache file IO, atomic writes) are annotated with
  ``locks.blocking_region("label")``; entering one while holding any
  checked lock is recorded.
* **held-across-wait**: `Condition.wait` releases its own lock but any
  OTHER checked lock still held during the wait is recorded the same way.
* **long holds** (warning only): a release more than
  ``PADDLE_TPU_LOCKCHECK_HOLD_S`` (default 0.5) seconds after acquire.

A same-thread re-acquire of a non-reentrant checked lock raises
immediately (the uninstrumented program would deadlock right there);
RLock reentrancy is understood and never reported.

Usage in tests / harnesses::

    from paddle_tpu.analysis import lockcheck
    lockcheck.enable()           # or PADDLE_TPU_LOCKCHECK=1 in the env
    ... construct pools, run the workload ...
    lockcheck.assert_clean()     # raises LockOrderError with the report

``report()`` returns the raw dict (cycles, violations, per-lock stats);
``reset()`` clears all recorded state (the enable flag stays).
"""
from __future__ import annotations

import os
import sys
import threading
import time

__all__ = [
    "enable", "disable", "enabled", "report", "reset", "assert_clean",
    "cycles", "violations", "LockOrderError", "Violation",
    "InstrumentedLock", "InstrumentedRLock", "InstrumentedCondition",
    "registry",
]

_ENV = "PADDLE_TPU_LOCKCHECK"
_ENV_HOLD = "PADDLE_TPU_LOCKCHECK_HOLD_S"

# case-insensitive off-values: an operator exporting FALSE/off/no to
# disable the checker must not silently get full instrumentation
_enabled = os.environ.get(_ENV, "").strip().lower() not in (
    "", "0", "false", "off", "no")


def enable():
    """Turn checking on for locks constructed AFTER this call."""
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def enabled():
    return _enabled


class LockOrderError(AssertionError):
    """Raised by assert_clean(); carries the full report dict."""

    def __init__(self, message, report):
        super().__init__(message)
        self.report = report


class Violation:
    __slots__ = ("kind", "message", "thread", "warning")

    def __init__(self, kind, message, thread, warning=False):
        self.kind = kind
        self.message = message
        self.thread = thread
        self.warning = warning

    def to_dict(self):
        return {"kind": self.kind, "message": self.message,
                "thread": self.thread, "warning": self.warning}

    def __repr__(self):
        tag = "warning" if self.warning else "violation"
        return f"[{tag}:{self.kind}] ({self.thread}) {self.message}"


def _caller_site():
    """file:line of the first frame outside this package (cheap: only
    walked when a NEW edge or a violation is recorded)."""
    f = sys._getframe(2)
    pkg = os.path.dirname(__file__)
    while f is not None:
        fn = f.f_code.co_filename
        if not fn.startswith(pkg):
            return f"{os.path.basename(fn)}:{f.f_lineno}"
        f = f.f_back
    return "<unknown>"


class _Registry:
    """Global recorder. Its own guard is a RAW threading lock — never an
    instrumented one (the recorder must not observe itself) — and a
    REENTRANT one: an allocation inside a critical section can start a
    garbage collection, whose callbacks (`obs.trace.watch_gc` opens a
    span) take instrumented locks on this very thread and so come back
    in here. Under a plain Lock that thread waited for itself, and every
    other thread's next acquisition for it."""

    def __init__(self):
        self._mu = threading.RLock()
        self._tls = threading.local()
        self._live = {}        # lock -> (acquirer's held list, entry)
        self.edges = {}        # name -> {name: {"thread","site"}}
        self.violations = []
        self.acquire_counts = {}
        self.max_hold_s = {}
        self.hold_threshold_s = float(
            os.environ.get(_ENV_HOLD, "0.5") or "0.5")

    # -- per-thread held list --------------------------------------------
    def _held(self):
        held = getattr(self._tls, "held", None)
        if held is None:
            held = self._tls.held = []
        return held

    def held_names(self):
        with self._mu:
            return [lock.name for lock, _ in self._held()]

    # -- events -----------------------------------------------------------
    # A held list is normally touched only by its own thread, but a
    # cross-thread Lock handoff release mutates the ACQUIRER's list, so
    # every read/write of any held list happens under _mu — otherwise a
    # handoff racing an acquire could snapshot a just-released lock into
    # an ordering edge (fabricating a cycle) or hide a genuine hold from
    # note_blocking.

    def on_acquire_attempt(self, lock, fail=True):
        """Called BEFORE blocking on a non-reentrant lock: a same-thread
        re-acquire would deadlock the uninstrumented program, so fail
        loudly here instead of hanging the test suite. With a finite
        timeout the call does eventually return False, so the pattern is
        recorded as a violation but the timeout semantics are kept."""
        with self._mu:
            mine = lock in [h for h, _ in self._held()]
        if mine:
            v = Violation(
                "recursive-acquire",
                f"thread re-acquired non-reentrant lock "
                f"'{lock.name}' it already holds "
                + ("(guaranteed deadlock)" if fail else
                   "(deadlock without the timeout)")
                + f" at {_caller_site()}",
                threading.current_thread().name)
            with self._mu:
                self.violations.append(v)
            if fail:
                raise RuntimeError("lockcheck: " + v.message)

    def on_acquired(self, lock):
        held = self._held()
        entry = (lock, time.monotonic())
        with self._mu:
            new_edges = [(h.name, lock.name) for h, _ in held
                         if h is not lock]
            held.append(entry)
            self._live[lock] = (held, entry)
            self.acquire_counts[lock.name] = \
                self.acquire_counts.get(lock.name, 0) + 1
            for a, b in new_edges:
                targets = self.edges.setdefault(a, {})
                if b not in targets:
                    targets[b] = {
                        "thread": threading.current_thread().name,
                        "site": _caller_site()}

    def on_release(self, lock, cross_thread=True):
        """Clear the recorded hold; True when one was actually cleared."""
        held = self._held()
        with self._mu:
            entry = None
            for i in range(len(held) - 1, -1, -1):
                if held[i][0] is lock:
                    entry = held[i]
                    break
            if entry is not None:
                owner_held = held
            elif cross_thread:
                # threading.Lock permits acquire in thread A / release
                # in thread B (handoff). The hold was recorded in the
                # ACQUIRING thread's list — clear it there, or A carries
                # a phantom hold that later fabricates recursive-acquire
                # and held-across-blocking reports
                rec = self._live.get(lock)
                if rec is None:
                    # never saw the acquire (e.g. enable() raced
                    # construction) — ignore rather than crash the host
                    return False
                owner_held, entry = rec
            else:
                return False
            try:
                owner_held.remove(entry)
            except ValueError:
                return False           # lost a race with another release
            self._live.pop(lock, None)
            dur = time.monotonic() - entry[1]
            if dur > self.max_hold_s.get(lock.name, 0.0):
                self.max_hold_s[lock.name] = dur
            if dur > self.hold_threshold_s:
                self.violations.append(Violation(
                    "long-hold",
                    f"'{lock.name}' held for {dur * 1e3:.0f}ms "
                    f"(threshold "
                    f"{self.hold_threshold_s * 1e3:.0f}ms), "
                    f"released at {_caller_site()}",
                    threading.current_thread().name,
                    warning=True))
        return True

    def note_blocking(self, label):
        held = self.held_names()
        if held:
            with self._mu:
                self.violations.append(Violation(
                    "held-across-blocking",
                    f"blocking region '{label}' entered while holding "
                    f"{held} at {_caller_site()}",
                    threading.current_thread().name))

    def note_wait(self, cond_lock):
        others = [n for n in self.held_names() if n != cond_lock.name]
        if others:
            with self._mu:
                self.violations.append(Violation(
                    "held-across-wait",
                    f"Condition('{cond_lock.name}').wait() while still "
                    f"holding {others} at {_caller_site()}",
                    threading.current_thread().name))

    # -- analysis ---------------------------------------------------------
    def cycles(self):
        """Elementary cycles in the name-level acquisition-order graph
        (iterative DFS; the graph is tiny — tens of names)."""
        with self._mu:
            graph = {a: sorted(bs) for a, bs in self.edges.items()}
        found, seen = [], set()
        for start in sorted(graph):
            stack = [(start, [start])]
            while stack:
                node, path = stack.pop()
                for nxt in graph.get(node, ()):
                    if nxt == start:
                        # canonical ROTATION of the ordered path — a node
                        # set would merge A->B->C->A with A->C->B->A,
                        # which are two distinct ordering hazards
                        i = path.index(min(path))
                        canon = tuple(path[i:] + path[:i])
                        if canon not in seen:
                            seen.add(canon)
                            found.append(path + [start])
                    elif nxt not in path and nxt > start:
                        # only explore nodes > start: each cycle is
                        # discovered once, from its smallest member
                        stack.append((nxt, path + [nxt]))
        return found

    def report(self):
        with self._mu:
            vio = [v.to_dict() for v in self.violations]
            edges = {a: {b: dict(w) for b, w in bs.items()}
                     for a, bs in self.edges.items()}
            stats = {n: {"acquires": self.acquire_counts.get(n, 0),
                         "max_hold_ms": round(
                             self.max_hold_s.get(n, 0.0) * 1e3, 3)}
                     for n in sorted(self.acquire_counts)}
        return {"cycles": self.cycles(), "violations": vio,
                "edges": edges, "locks": stats}

    def reset(self):
        with self._mu:
            self._live = {}
            self.edges = {}
            self.violations = []
            self.acquire_counts = {}
            self.max_hold_s = {}


_registry = _Registry()


def registry():
    return _registry


# --------------------------------------------------------------------------
# instrumented primitives (constructed via analysis.locks.new_* when the
# checker is enabled)
# --------------------------------------------------------------------------

class InstrumentedLock:
    """threading.Lock wrapper reporting to the global registry."""

    _reentrant = False

    def __init__(self, name, reg=None):
        self.name = name
        self._reg = reg or _registry
        self._inner = threading.Lock()

    def acquire(self, blocking=True, timeout=-1):
        if blocking:
            self._reg.on_acquire_attempt(self, fail=timeout == -1)
        ok = self._inner.acquire(blocking, timeout)
        if ok:
            self._reg.on_acquired(self)
        return ok

    def release(self):
        self._reg.on_release(self)
        self._inner.release()

    def locked(self):
        return self._inner.locked()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def __repr__(self):
        return f"<InstrumentedLock '{self.name}'>"


class InstrumentedRLock:
    """threading.RLock wrapper: only the OUTERMOST acquire/release pair
    is reported, so reentrancy never shows up as ordering or recursion."""

    _reentrant = True

    def __init__(self, name, reg=None):
        self.name = name
        self._reg = reg or _registry
        self._inner = threading.RLock()
        self._owner = None          # ident; only mutated by the owner
        self._depth = 0

    def acquire(self, blocking=True, timeout=-1):
        me = threading.get_ident()
        if self._owner == me:        # reentrant fast path, we own it
            self._inner.acquire()
            self._depth += 1
            return True
        ok = self._inner.acquire(blocking, timeout)
        if ok:
            self._owner = me
            self._depth = 1
            self._reg.on_acquired(self)
        return ok

    def release(self):
        if self._owner != threading.get_ident():
            raise RuntimeError("cannot release un-acquired lock")
        self._depth -= 1
        if self._depth == 0:
            self._owner = None
            self._reg.on_release(self)
        self._inner.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def __repr__(self):
        return f"<InstrumentedRLock '{self.name}'>"


class InstrumentedCondition:
    """Condition over an InstrumentedLock. The real threading.Condition
    runs on the RAW inner lock (its `_is_owned` probe would corrupt the
    wrapper's bookkeeping), while acquire/release/wait go through the
    wrapper so held-sets stay truthful across waits."""

    def __init__(self, lock):
        # plain Lock only: RLock wait() semantics (full release of a
        # nested hold) can't be mirrored in the wrapper's bookkeeping
        if not isinstance(lock, InstrumentedLock):
            raise TypeError("InstrumentedCondition needs an "
                            f"InstrumentedLock, got {type(lock).__name__}")
        self.lock = lock
        self._reg = lock._reg
        self._cond = threading.Condition(lock._inner)

    def acquire(self, *a, **kw):
        return self.lock.acquire(*a, **kw)

    def release(self):
        self.lock.release()

    def __enter__(self):
        self.lock.acquire()
        return self

    def __exit__(self, *exc):
        self.lock.release()
        return False

    def wait(self, timeout=None):
        self._reg.note_wait(self.lock)
        # the wait releases (and on wake re-acquires) the inner lock:
        # mirror that in the held-set so hold-times and ordering edges
        # seen by OTHER acquisitions during the wait stay correct.
        # cross_thread=False: Condition.wait only ever releases the
        # CALLER's hold — and only restore what was actually cleared,
        # else waiting without the lock (inner wait raises) would plant
        # a phantom hold that poisons every later report on this thread
        released = self._reg.on_release(self.lock, cross_thread=False)
        try:
            return self._cond.wait(timeout)
        finally:
            if released:
                self._reg.on_acquired(self.lock)

    def wait_for(self, predicate, timeout=None):
        end = None if timeout is None else time.monotonic() + timeout
        result = predicate()
        while not result:
            remaining = None if end is None else end - time.monotonic()
            if remaining is not None and remaining <= 0:
                break
            self.wait(remaining)
            result = predicate()
        return result

    def notify(self, n=1):
        self._cond.notify(n)

    def notify_all(self):
        self._cond.notify_all()

    def __repr__(self):
        return f"<InstrumentedCondition over '{self.lock.name}'>"


# --------------------------------------------------------------------------
# module-level conveniences
# --------------------------------------------------------------------------

def report():
    return _registry.report()


def cycles():
    return _registry.cycles()


def violations(include_warnings=False):
    with _registry._mu:
        vs = list(_registry.violations)
    if not include_warnings:
        vs = [v for v in vs if not v.warning]
    return vs


def reset():
    _registry.reset()


def assert_clean(allow_warnings=True):
    """Raise LockOrderError if any cycle or (non-warning) violation was
    recorded. The exception message embeds the findings; `.report` has
    the full dict."""
    rep = report()
    problems = []
    for cyc in rep["cycles"]:
        problems.append("acquisition-order cycle: " + " -> ".join(cyc))
    for v in rep["violations"]:
        if v["warning"] and allow_warnings:
            continue
        problems.append(f"{v['kind']} ({v['thread']}): {v['message']}")
    if problems:
        raise LockOrderError(
            "lockcheck found {} problem(s):\n  {}".format(
                len(problems), "\n  ".join(problems)), rep)
    return rep
