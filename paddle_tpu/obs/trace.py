"""paddle_tpu.obs.trace — Dapper-style distributed request tracing.

The metrics registry (PR 8) says *how slow* the p99 is; this layer says
*which request* and *where* — queue, batch formation, retry loop,
failover hop, prefill, or the XLA dispatch itself. A request is a
**trace** (one 64-bit id minted at the root), each timed region a
**span** (own id, parent link, name, attrs, typed-error status), and
finished spans land in the always-on flight recorder (obs.flight).

Design points:

* **Context propagation** — a per-thread context STACK
  (`current()` / `span()` push-pop). Cross-thread handoff is explicit:
  the admitting side captures `current()` (e.g. onto the serving
  pool's `_Request`), the executing side re-enters it with
  `span_in(ctx, name)` / `attach(ctx)`. Cross-process handoff rides
  `ctx.to_wire()` / `from_wire()` (three plain values — they pickle
  into the replica transport's request payload).

* **Deterministic sampling** — the sampling DECISION is a pure
  function of the trace id (`PADDLE_TPU_TRACE_SAMPLE`, default 1.0),
  made once at the root and carried on the context: every process and
  thread a trace touches agrees without coordination, so a sampled
  trace is always COMPLETE.

* **Zero overhead off** — ``PADDLE_TPU_TRACE=0`` reduces every probe
  to one module-flag check: `span()`/`root_span()` return a shared
  no-op singleton, `current()` is never consulted by instrumentation,
  and histogram exemplars (obs.metrics) stay dark. Mirrors the
  lockcheck/tpu-san opt-out contract — but tracing defaults ON (the
  flight recorder is cheap enough to leave on in production).

* **Postmortems** — the typed serving failures that matter
  (`RequestFailed` / `DeadlineExceeded` / `ReplicaDead` /
  `SwapFailed` carry a ``_trace_postmortem = True`` class flag) pin
  their trace into the flight recorder's retained buffer at
  construction (`note_failure`) or at the request's result slot
  (`pin_failure`), and gain a ``.trace_id`` attribute so the caller
  holding the exception can fetch the causal record
  (``/traces/<id>`` or ``tools/trace_dump.py``).

* **On the profiler's clock** — a span opened with ``profile=True``
  (the decode engine's rounds and phases, the train engine's dispatch
  spans) is also a `jax.profiler.TraceAnnotation` named
  ``pt::<span name>``: a profile captured from a live server shows the
  rounds and their phases on the host plane, above the device's
  operations. With no profiler session the annotation is a flag check
  in C++ (~0.4 us); it is kept off events and per-token sites.

The ``obs.trace`` named lock guards only the shared id generator;
span creation otherwise touches per-thread state. See
docs/observability.md ("Distributed tracing") for the workflow.
"""
from __future__ import annotations

import gc
import os
import random
import sys
import threading
import time

from ..analysis import locks as _locks
from . import flight as _flight

__all__ = [
    "TraceContext", "enabled", "enable", "disable", "sample_rate",
    "set_sample_rate", "current", "current_wire", "span", "root_span",
    "span_in", "attach", "detached", "event", "event_in", "open_span",
    "null_span", "note_failure", "pin_failure", "reserve_ring",
    "watch_gc", "PROFILE_PREFIX",
]

#: a `profile=True` span is the profiler annotation `pt::<span name>`
PROFILE_PREFIX = "pt::"


def _env_flag(name, default="1"):
    return os.environ.get(name, default).strip().lower() not in (
        "0", "false", "off", "no")


_enabled = _env_flag("PADDLE_TPU_TRACE")
_sample_rate = float(os.environ.get("PADDLE_TPU_TRACE_SAMPLE", "1.0"))

#: deterministic sampling modulus: a trace is sampled iff
#: trace_id % _SAMPLE_MOD < rate * _SAMPLE_MOD
_SAMPLE_MOD = 1 << 20

_id_lock = _locks.new_lock("obs.trace")
_id_rng = random.Random(int.from_bytes(os.urandom(16), "big"))

_tls = threading.local()


def enabled():
    """True when tracing probes are live (PADDLE_TPU_TRACE, default on)."""
    return _enabled


def enable():
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def sample_rate():
    return _sample_rate


def set_sample_rate(rate):
    global _sample_rate
    _sample_rate = float(rate)


def _new_id():
    with _id_lock:
        v = _id_rng.getrandbits(64)
    return v or 1


def _sampled(trace_id):
    if _sample_rate >= 1.0:
        return True
    if _sample_rate <= 0.0:
        return False
    # Fibonacci-hash the id before thresholding so the decision is
    # uniform for ANY id distribution (sequential test ids included),
    # while staying a pure function of the trace id — every process
    # and thread agrees without coordination
    h = (trace_id * 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF
    return (h >> 44) < _sample_rate * _SAMPLE_MOD


class TraceContext:
    """(trace_id, span_id, sampled): where in which trace the current
    code is executing. Immutable; child spans derive new contexts."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id, span_id, sampled):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled

    @property
    def trace_id_hex(self):
        return f"{self.trace_id:016x}"

    @property
    def span_id_hex(self):
        return f"{self.span_id:016x}"

    def to_wire(self):
        """Plain picklable tuple for cross-process propagation."""
        return (self.trace_id, self.span_id, self.sampled)

    @classmethod
    def from_wire(cls, wire):
        if wire is None:
            return None
        t, s, samp = wire
        return cls(int(t), int(s), bool(samp))

    def __repr__(self):
        return (f"TraceContext({self.trace_id_hex}/{self.span_id_hex}"
                f"{'' if self.sampled else ' unsampled'})")


def _stack():
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


def current():
    """The innermost active TraceContext on this thread, or None."""
    s = getattr(_tls, "stack", None)
    return s[-1] if s else None


def current_wire():
    """`current().to_wire()` or None — the cross-process handoff value."""
    ctx = current()
    return None if ctx is None else ctx.to_wire()


_annotation_cls = None


def _annotate(name):
    """Enter `pt::<name>` on the profiler's timeline (None while jax is
    not loaded: no profiler session can be running then)."""
    global _annotation_cls
    if _annotation_cls is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation

        _annotation_cls = TraceAnnotation
    ann = _annotation_cls(PROFILE_PREFIX + name)
    ann.__enter__()
    return ann


class _NullSpan:
    """Shared no-op for every untraced probe: ``with span(...)`` costs a
    flag check and two trivial method calls."""

    __slots__ = ()
    ctx = None
    trace_id = None
    trace_id_hex = None
    span_id_hex = None
    recorded = False
    duration = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_attr(self, key, value):
        pass

    def end(self, error=None, status=None):
        pass


_NULL = _NullSpan()


def null_span():
    """The shared no-op span (for call sites that pick between a real
    span and nothing without an if/else around the `with` body)."""
    return _NULL


class _OpenSpan:
    """A live span: entered (pushed) now, recorded into the flight
    recorder at exit/end when its trace is sampled. Exceptions leaving
    the ``with`` body stamp the span's status with the error type."""

    __slots__ = ("name", "ctx", "parent_id", "attrs", "_t0", "_thread",
                 "_pushed", "_extra_pop", "recorded", "duration", "_ann")

    def __init__(self, name, ctx, parent_id, attrs, extra_pop=False,
                 profile=False, t0=None):
        self.name = name
        self.ctx = ctx
        self.parent_id = parent_id
        self.attrs = dict(attrs) if attrs else None
        # `t0` backdates the span to a perf_counter reading its opener
        # took before it knew a span was due (the scheduler's loop top)
        self._t0 = time.perf_counter() if t0 is None else t0
        self._thread = threading.current_thread().name
        self._pushed = True
        self._extra_pop = extra_pop  # attach-style: a foreign parent ctx
        self.recorded = False        # was pushed under this span
        self.duration = None         # seconds, once ended
        self._ann = _annotate(name) if profile else None

    # -- identity ----------------------------------------------------------
    @property
    def trace_id(self):
        return self.ctx.trace_id

    @property
    def trace_id_hex(self):
        return self.ctx.trace_id_hex

    @property
    def span_id_hex(self):
        return self.ctx.span_id_hex

    def set_attr(self, key, value):
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value

    # -- lifecycle ---------------------------------------------------------
    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.end(error=exc)
        return False

    def end(self, error=None, status=None):
        """Pop the context and (when sampled) record the finished span.
        Idempotent. `error` may be an exception instance or message."""
        if self._pushed:
            self._pushed = False
            s = _stack()
            if s and s[-1] is self.ctx:
                s.pop()
                # attach-style spans pushed their foreign parent too —
                # pop it ONLY when our own pop landed (an imbalanced
                # stack must never lose someone else's entry)
                if self._extra_pop and s:
                    s.pop()
        if self.recorded:
            return
        self.recorded = True
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        t1 = time.perf_counter()
        self.duration = t1 - self._t0
        if not self.ctx.sampled:
            return
        if status is None:
            status = "ok" if error is None else (
                type(error).__name__ if isinstance(error, BaseException)
                else "error")
        err = None
        if error is not None:
            err = str(error) if not isinstance(error, type) else None
        _flight.recorder().record(_flight.Span(
            self.ctx.trace_id, self.ctx.span_id, self.parent_id,
            self.name, _flight.wall_of(self._t0), _flight.wall_of(t1),
            attrs=self.attrs, status=status, error=err,
            thread=self._thread))


def span(name, attrs=None, profile=False, t0=None):
    """Child span of the CURRENT context; the shared no-op when tracing
    is off or no trace is active (instrumentation call sites stay free
    outside a traced request). `profile` / `t0`: see `_OpenSpan`."""
    if not _enabled:
        return _NULL
    parent = current()
    if parent is None:
        return _NULL
    ctx = TraceContext(parent.trace_id, _new_id(), parent.sampled)
    _stack().append(ctx)
    return _OpenSpan(name, ctx, parent.span_id, attrs, profile=profile,
                     t0=t0)


def root_span(name, attrs=None, sampled=None, profile=False, t0=None):
    """Mint a trace (new trace id, deterministic sampling decision) —
    or a child span when a context is already active, so a traced
    caller's hop nests instead of forking a second trace.

    `sampled=` overrides the hash decision for a FRESH trace: a link
    trace (a formed batch, a decode step) minted on behalf of sampled
    member traces must itself be sampled, or the members' back-links
    would dangle at sub-1.0 sample rates."""
    if not _enabled:
        return _NULL
    parent = current()
    if parent is not None:
        ctx = TraceContext(parent.trace_id, _new_id(), parent.sampled)
        pid = parent.span_id
    else:
        tid = _new_id()
        ctx = TraceContext(tid, _new_id(),
                           _sampled(tid) if sampled is None
                           else bool(sampled))
        pid = None
    _stack().append(ctx)
    return _OpenSpan(name, ctx, pid, attrs, profile=profile, t0=t0)


def open_span(name, attrs=None, parent=None):
    """A long-lived span NOT tied to this thread's stack (e.g. a decode
    sequence whose life spans many scheduler rounds): nothing is
    pushed; finish it explicitly with `.end(error=...)`. `parent` is an
    explicit TraceContext (default: `current()`)."""
    if not _enabled:
        return _NULL
    if parent is None:
        parent = current()
    if parent is not None:
        ctx = TraceContext(parent.trace_id, _new_id(), parent.sampled)
        pid = parent.span_id
    else:
        tid = _new_id()
        ctx = TraceContext(tid, _new_id(), _sampled(tid))
        pid = None
    sp = _OpenSpan(name, ctx, pid, attrs)
    sp._pushed = False          # detached: no stack entry to pop
    return sp


def span_in(name, ctx, attrs=None, profile=False):
    """Child span under an EXPLICIT context (cross-thread handoff): the
    executing thread both attaches `ctx` and opens the child in one
    push, popping both at exit."""
    if not _enabled or ctx is None:
        return _NULL
    s = _stack()
    s.append(ctx)
    child = TraceContext(ctx.trace_id, _new_id(), ctx.sampled)
    s.append(child)
    return _OpenSpan(name, child, ctx.span_id, attrs, extra_pop=True,
                     profile=profile)


class _Attach:
    __slots__ = ("ctx",)

    def __init__(self, ctx):
        self.ctx = ctx

    def __enter__(self):
        _stack().append(self.ctx)
        return self.ctx

    def __exit__(self, *exc):
        s = _stack()
        if s:
            s.pop()
        return False


def attach(ctx):
    """Re-enter a captured context on this thread (no span recorded):
    spans opened inside become its children."""
    if not _enabled or ctx is None:
        return _NULL
    return _Attach(ctx)


_DETACHED = _Attach(None)


def detached():
    """Run a block with NO current context on this thread: a callee that
    captures `current()` for a hand-off (the serving pool's admission)
    gets None, so an internal executor's own spans stay out of the
    caller's trace."""
    return _DETACHED if _enabled else _NULL


def event(name, attrs=None):
    """Zero-duration child span of the current context ("something
    happened here"): admission stamps, first-token marks, batch links."""
    sp = span(name, attrs)
    sp.end()
    return sp


def event_in(name, ctx, attrs=None):
    """`event()` under an explicit context (cross-thread)."""
    sp = span_in(name, ctx, attrs)
    sp.end()
    return sp


# ---------------------------------------------------------------------------
# long-lived threads and the collector
# ---------------------------------------------------------------------------

def reserve_ring():
    """Called by a long-lived engine thread (a scheduler, a step-pool
    worker, a trainer's dispatch thread): its spans go to a ring that
    holds a whole window and outlives the thread (`flight.reserve`)."""
    if _enabled:
        _flight.recorder().reserve()


_gc_t0 = None


def _on_gc(phase, info):
    """`gc.callbacks` hook: a generation-2 collection is one `host.gc`
    span — a child of whatever span the collecting thread is in, else a
    root — so host self-time with no child has its next suspect on
    record. Younger generations are not recorded."""
    global _gc_t0
    if info.get("generation") != 2:
        return
    if phase == "start":
        _gc_t0 = time.perf_counter()
        return
    t0, _gc_t0 = _gc_t0, None
    if t0 is not None:
        root_span("host.gc", attrs={"collected": info.get("collected", 0)},
                  t0=t0).end()


def watch_gc():
    """Record generation-2 collections as `host.gc` spans from now on
    (idempotent; the decode engine turns it on with its scheduler)."""
    if _enabled and _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


# ---------------------------------------------------------------------------
# postmortem capture
# ---------------------------------------------------------------------------

def note_failure(exc):
    """Called by the typed serving errors' constructors (class flag
    ``_trace_postmortem``): pin the CURRENT trace's causal record into
    the flight recorder's retained buffer and stamp the exception with
    its trace id. No-op without an active sampled trace."""
    if not _enabled:
        return
    ctx = current()
    if ctx is None or not ctx.sampled:
        return
    exc.trace_id = ctx.trace_id_hex
    _flight.recorder().pin(ctx.trace_id, reason=type(exc).__name__)


def pin_failure(ctx, exc):
    """Explicit postmortem pin for a failure resolved AWAY from the
    traced thread (a pool worker failing a request whose context lives
    on the request object). Honors the same class flag; idempotent
    with `note_failure` (one pinned record per trace)."""
    if not _enabled or ctx is None or not ctx.sampled:
        return
    if not getattr(type(exc), "_trace_postmortem", False):
        return
    if getattr(exc, "trace_id", None) is None:
        exc.trace_id = ctx.trace_id_hex
    _flight.recorder().pin(ctx.trace_id, reason=type(exc).__name__)
