"""paddle_tpu.profiler — host+device tracing and step timing.

Reference analog: `paddle.profiler.Profiler` (profiler/profiler.py:346)
with its `make_scheduler` CLOSED→READY→RECORD state machine (:117),
RecordEvent host spans feeding `HostEventRecorder` (platform/profiler/
host_tracer.h:26), ChromeTracingLogger export, summary statistics
(profiler_statistic.py), and the `profiler.timer` ips benchmark hooks
(timer.py:109,283).

TPU-native split: device-side tracing belongs to XLA — `jax.profiler`
captures XPlane/TensorBoard traces of the compiled programs — while this
module records the HOST side (eager op dispatch, data loading, user spans)
in the native ring-buffer recorder (paddle_tpu/native/host_tracer.cc) and
exports chrome-trace JSON plus per-op summaries. Both can run together:
`Profiler(targets={ProfilerTarget.CPU, ProfilerTarget.TPU})` wraps a
jax.profiler trace session around the RECORD window.
"""
from __future__ import annotations

import ctypes
import enum
import json
import os
import threading
import time
from collections import defaultdict

from ..obs import trace as _obs_trace

__all__ = [
    "Profiler", "ProfilerState", "ProfilerTarget", "RecordEvent",
    "make_scheduler", "export_chrome_tracing", "benchmark",
    "host_recording", "profiled_span",
]

# module flag flipped by Profiler's record window; hot paths (the
# distributed engine's dispatch/device_put/write-back spans) consult it so
# un-profiled runs never touch the native tracer
_cpu_recording = False


def host_recording():
    """True while a Profiler with the CPU target is inside its RECORD
    window (host spans are being captured)."""
    return _cpu_recording


def profiled_span(name, histogram=None):
    """The shared gate for hot-path instrumentation (the distributed
    engine's `engine::device_put` / `engine::dispatch` /
    `engine::write_back`, the serving batcher's form/pad/dispatch/
    scatter stages). What one call site records, and where:

    * inside an active trace context (`obs.trace`; the train engine
      opens an `engine.dispatch` root a `train_batch[es]` call, the
      serving pool one a batch) — a child trace span in the flight
      recorder, which is also the profiler annotation `pt::<name>`;
    * with `histogram=` (a `paddle_tpu.obs` Histogram) — the duration,
      observed on EVERY pass;
    * while a `Profiler` with the CPU target records — a native
      `RecordEvent` for the `paddle.profiler` chrome trace.

    The interval is timed once: the histogram takes the trace span's
    own duration where there is one. Outside all three the call returns
    a no-op context and never touches the native tracer (not even its
    first-use build); with ``PADDLE_TPU_TRACE=0`` the tracing path is
    one flag check."""
    traced = _obs_trace.enabled() and _obs_trace.current() is not None
    if histogram is not None or traced:
        return _TimedSpan(name, histogram, traced)
    if _cpu_recording:
        return RecordEvent(name)
    from contextlib import nullcontext

    return nullcontext()


class _TimedSpan:
    """`profiled_span` with a histogram and/or under a trace: one timed
    interval feeding the child trace span and the histogram, plus the
    native RecordEvent while a profiler records."""

    __slots__ = ("name", "histogram", "_traced", "_ev", "_t0", "_tspan")

    def __init__(self, name, histogram, traced=False):
        self.name = name
        self.histogram = histogram
        self._traced = traced
        self._tspan = None

    def __enter__(self):
        self._ev = RecordEvent(self.name) if _cpu_recording else None
        if self._ev is not None:
            self._ev.begin()
        if self._traced:
            self._tspan = _obs_trace.span(self.name, profile=True)
        else:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._tspan is not None:
            self._tspan.end(error=exc)
            took, ctx = self._tspan.duration, self._tspan.ctx
        else:
            took, ctx = time.perf_counter() - self._t0, None
        if self.histogram is not None:
            # the bucket exemplar carries the span's own trace id
            self.histogram.observe(took, ctx=ctx)
        if self._ev is not None:
            self._ev.end()
        return False

from ..native import build_and_load


def _lib():
    lib = build_and_load("host_tracer")
    if not getattr(lib, "_pht_ready", False):
        lib.pht_name_id.restype = ctypes.c_uint32
        lib.pht_name_id.argtypes = [ctypes.c_char_p]
        lib.pht_begin_id.argtypes = [ctypes.c_uint32]
        lib.pht_begin.argtypes = [ctypes.c_char_p]
        lib.pht_span.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                 ctypes.c_int64]
        lib.pht_now_ns.restype = ctypes.c_int64
        lib.pht_dump_json.restype = ctypes.c_void_p
        lib.pht_dump_json.argtypes = [ctypes.c_int]
        lib.pht_dump_raw.restype = ctypes.c_int64
        lib.pht_dump_raw.argtypes = [ctypes.POINTER(ctypes.c_char_p)]
        lib.pht_get_name.restype = ctypes.c_void_p
        lib.pht_get_name.argtypes = [ctypes.c_uint32]
        lib.pht_free.argtypes = [ctypes.c_void_p]
        lib._pht_ready = True
    return lib


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3  # last record step of a cycle: trace is returned


class ProfilerTarget(enum.Enum):
    CPU = 0    # host spans (native recorder)
    TPU = 1    # XLA device trace via jax.profiler
    GPU = 1    # alias for API parity
    CUSTOM_DEVICE = 1


def make_scheduler(*, closed, ready, record, repeat=0, skip_first=0):
    """Step-indexed profiling window generator (reference
    profiler.py:117): skip_first steps CLOSED, then cycles of
    closed/ready/record; the final RECORD step of each cycle returns
    RECORD_AND_RETURN so handlers fire."""
    cycle = closed + ready + record
    if record <= 0:
        raise ValueError("record steps must be positive")

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        n_cycle, pos = divmod(s, cycle)
        if repeat > 0 and n_cycle >= repeat:
            return ProfilerState.CLOSED
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def _default_scheduler(step: int) -> ProfilerState:
    return ProfilerState.RECORD  # always on between start() and stop()


class RecordEvent:
    """User/host span (reference: paddle.profiler.RecordEvent). Usable as a
    context manager or begin()/end() pair; nests correctly per thread."""

    def __init__(self, name: str):
        self.name = name
        self._id = None

    def begin(self):
        lib = _lib()
        if self._id is None:
            self._id = lib.pht_name_id(self.name.encode())
        lib.pht_begin_id(self._id)

    def end(self):
        _lib().pht_end()

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


# hook installed into core.dispatch while recording: spans every eager op.
# RecordEvents are cached per op name (begin/end state lives in the native
# per-thread stack, not the instance, so sharing is safe).
_op_events: dict = {}


def _op_span_hook(name: str):
    ev = _op_events.get(name)
    if ev is None:
        ev = RecordEvent(f"op::{name}")
        _op_events[name] = ev
    return ev


class Profiler:
    """Reference: paddle.profiler.Profiler (profiler.py:346).

    with Profiler(scheduler=make_scheduler(closed=1, ready=1, record=3)) as p:
        for batch in loader:
            train_step(batch)
            p.step()
    p.summary()
    """

    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, profile_memory=False, record_shapes=False):
        self.targets = set(targets) if targets else {ProfilerTarget.CPU}
        if scheduler is None:
            self._schedule = _default_scheduler
        elif callable(scheduler):
            self._schedule = scheduler
        else:  # (start, end) tuple parity
            lo, hi = scheduler
            self._schedule = make_scheduler(
                closed=max(0, lo), ready=0, record=hi - lo, repeat=1)
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._step_times = []
        self._last_step_t = None
        self._device_trace_dir = None
        self._device_tracing = False

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        self.current_state = self._schedule(self.step_num)
        self._transition(ProfilerState.CLOSED, self.current_state)
        self._last_step_t = time.perf_counter()
        return self

    def stop(self):
        self._transition(self.current_state, ProfilerState.CLOSED)
        self.current_state = ProfilerState.CLOSED

    def step(self):
        now = time.perf_counter()
        if self._last_step_t is not None:
            self._step_times.append(now - self._last_step_t)
        self._last_step_t = now
        old = self.current_state
        self.step_num += 1
        new = self._schedule(self.step_num)
        self._transition(old, new)
        self.current_state = new

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- state machine -----------------------------------------------------
    def _recording(self, st):
        return st in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)

    def _transition(self, old, new):
        if self.timer_only:
            return
        returning = old is ProfilerState.RECORD_AND_RETURN
        if returning and self.on_trace_ready is not None:
            self.on_trace_ready(self)
        # a cycle boundary (RECORD_AND_RETURN -> next cycle's RECORD) must
        # close and reopen the recorder, or traces accumulate across cycles
        if self._recording(old) and (not self._recording(new) or returning):
            self._end_record()
        if self._recording(new) and (not self._recording(old) or returning):
            self._begin_record()

    def _begin_record(self):
        if ProfilerTarget.CPU in self.targets:
            lib = _lib()
            lib.pht_clear()
            lib.pht_enable()
            from ..core import dispatch

            dispatch.set_profile_hook(_op_span_hook)
            global _cpu_recording
            _cpu_recording = True
        if ProfilerTarget.TPU in self.targets and not self._device_tracing:
            import jax

            self._device_trace_dir = self._device_trace_dir or \
                os.environ.get("PADDLE_TPU_TRACE_DIR", "/tmp/paddle_tpu_trace")
            try:
                jax.profiler.start_trace(self._device_trace_dir)
                self._device_tracing = True
            except Exception:  # tpu-lint: disable=TL007 — backend can't
                # trace (already tracing / unsupported): profile host-only
                self._device_tracing = False

    def _end_record(self):
        if ProfilerTarget.CPU in self.targets:
            global _cpu_recording
            _cpu_recording = False
            _lib().pht_disable()
            from ..core import dispatch

            dispatch.set_profile_hook(None)
        if self._device_tracing:
            import jax

            try:
                jax.profiler.stop_trace()
            finally:
                self._device_tracing = False

    # -- export / stats ----------------------------------------------------
    def export_chrome_tracing(self, path: str):
        """Write recorded host spans as a chrome://tracing file."""
        lib = _lib()
        p = lib.pht_dump_json(os.getpid())
        try:
            body = ctypes.string_at(p).decode()
        finally:
            lib.pht_free(p)
        with open(path, "w") as f:
            f.write('{"traceEvents":%s}' % body)
        return path

    def events(self):
        """[(tid, name, t0_ns, t1_ns)] of recorded host spans."""
        import struct

        lib = _lib()
        out = ctypes.c_char_p()
        n = lib.pht_dump_raw(ctypes.byref(out))
        raw = ctypes.string_at(out, n * 28)
        lib.pht_free(out)
        names = {}
        evs = []
        for i in range(n):
            tid, nid, t0, t1 = struct.unpack_from("<QIqq", raw, i * 28)
            if nid not in names:
                np_ = lib.pht_get_name(nid)
                names[nid] = ctypes.string_at(np_).decode()
                lib.pht_free(np_)
            evs.append((tid, names[nid], t0, t1))
        return evs

    def summary(self, sorted_by="total", max_rows=40):
        """Per-name aggregate table of host spans (reference:
        profiler_statistic summary). Returns the formatted string."""
        agg = defaultdict(lambda: [0, 0.0, 0.0])  # name -> [calls, total, max]
        for _, name, t0, t1 in self.events():
            d = (t1 - t0) / 1e6
            a = agg[name]
            a[0] += 1
            a[1] += d
            a[2] = max(a[2], d)
        rows = sorted(agg.items(), key=lambda kv: -kv[1][1])[:max_rows]
        lines = [f"{'name':<44} {'calls':>7} {'total(ms)':>11} "
                 f"{'avg(ms)':>9} {'max(ms)':>9}"]
        for name, (calls, total, mx) in rows:
            lines.append(f"{name[:44]:<44} {calls:>7} {total:>11.3f} "
                         f"{total / calls:>9.3f} {mx:>9.3f}")
        if self._step_times:
            ts = self._step_times
            sps = len(ts) / sum(ts)
            lines.append(
                f"steps: {len(ts)}  avg {sum(ts) / len(ts) * 1e3:.2f} ms"
                f"  steps/sec {sps:.2f}")
            # publish into the process metrics registry: the profiler's
            # measured steps/sec is THE training-throughput gauge the
            # obs exporters (and the SLO gate) read — single source of
            # truth with the printed summary
            from ..obs.metrics import registry as _obs_registry

            _obs_registry().gauge(
                "profiler.steps_per_sec",
                help="steps/sec over the profiler's last step window"
            ).set(sps)
        out = "\n".join(lines)
        print(out)
        return out


def export_chrome_tracing(dir_name: str, worker_name: str | None = None):
    """Handler factory for Profiler(on_trace_ready=...) (reference parity)."""
    os.makedirs(dir_name, exist_ok=True)

    def handler(prof: Profiler):
        name = worker_name or f"host_{os.getpid()}"
        prof.export_chrome_tracing(
            os.path.join(dir_name, f"{name}_step{prof.step_num}.json"))

    return handler


# --------------------------------------------------------------------------
# Throughput timer (reference: paddle.profiler.timer — benchmark().begin()/
# step()/end() reporting ips / steps per second).
# --------------------------------------------------------------------------


class _Benchmark:
    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        self._t0 = None
        self._last = None
        self._steps = 0
        self._items = 0
        self._durs = []

    def begin(self):
        self.reset()
        self._t0 = self._last = time.perf_counter()

    def step(self, num_samples=None):
        with self._lock:
            now = time.perf_counter()
            if self._last is not None:
                self._durs.append(now - self._last)
            self._last = now
            self._steps += 1
            if num_samples:
                self._items += int(num_samples)

    def end(self):
        return self.report()

    def report(self):
        total = (self._last - self._t0) if self._t0 is not None else 0.0
        sps = self._steps / total if total > 0 else 0.0
        out = {
            "steps": self._steps,
            "total_s": total,
            "steps_per_sec": sps,
            "ips": (self._items / total) if total > 0 and self._items else sps,
        }
        if self._durs:
            ds = sorted(self._durs)
            out["step_ms_p50"] = ds[len(ds) // 2] * 1e3
            out["step_ms_max"] = ds[-1] * 1e3
        return out


_benchmark = _Benchmark()


def benchmark():
    return _benchmark


class SortedKeys:
    """Reference: profiler/profiler_statistic.py SortedKeys — summary sort
    orders."""
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView:
    """Reference: profiler/profiler.py SummaryView — which summary tables
    to print."""
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def export_protobuf(path):
    """Reference: profiler.export_protobuf — the chrome-trace JSON is this
    runtime's interchange format; protobuf emission delegates to it with
    the same file contract."""
    raise NotImplementedError(
        "export_protobuf: this runtime exports chrome-trace JSON "
        "(Profiler.export / chrome_trace); load it with the same tooling "
        "that consumes the reference's exported traces")


def load_profiler_result(path):
    """Reference: profiler.load_profiler_result — reload an exported
    trace. Loads the chrome-trace JSON this profiler exports."""
    import json
    with open(path) as f:
        return json.load(f)
