"""paddle_tpu.inference.batching — dynamic request batching for serving.

The resilient runtime (serving.py) gave every request a deadline and every
member a supervisor, but each request still runs the exported module at
its own shape: one full XLA dispatch per request. Under concurrent
traffic that is the dominant serving cost — device utilization collapses
while the host pays dispatch overhead N times for work one program could
do. Adaptive batching with bounded queueing delay (Clipper, NSDI'17) plus
bucketed batch formation (Orca, OSDI'22 keeps padded waste bounded) is
the canonical fix; this module brings both to `paddle_tpu.inference`:

* **`BatchConfig`** — the policy knobs: `buckets` (allowed batch sizes;
  a formed batch is padded up to the smallest bucket that fits, so only
  `len(buckets)` executables ever exist per model), `max_wait_ms` (the
  bounded queueing delay a request may spend waiting for batchmates) and
  `deadline_margin_ms` (flush early when the earliest request deadline
  in the forming batch gets within this margin).

* **`DynamicBatcher`** — batch execution over one exported layer:
  validates request feeds against the exported `input_spec`, forms the
  stacked+padded arrays, dispatches the bucket's AOT executable
  (`TranslatedLayer.batched_call`, backed by jit.aot's in-memory and
  persistent compile caches), and scatters per-request output slices
  back. Padding replicates a real example (never zeros — NaN-safe for
  arbitrary models) and padded rows are dropped before anything is
  returned, so per-request results are **bit-identical** to unbatched
  execution (the bucket executable runs exactly the exported program per
  example — see jit/aot.py).

`ServingPool(..., batching=BatchConfig(...))` wires this into the
supervised worker loop: workers gather batchmates from the admission
queue (deadline-aware), a transient batch failure is retried as split
singles so one poison request can't fail its batchmates, and
`pool.warmup()` precompiles every bucket before traffic. Each of
form / pad / dispatch / scatter emits a `serving::batch_*` host span
when a Profiler is recording (`profiler.profiled_span`).
"""
from __future__ import annotations

import time

import numpy as np

from ..analysis import locks as _locks
from ..analysis import runtime_san as _san
from ..obs import trace as _otrace

__all__ = ["BatchConfig", "DynamicBatcher"]


def _span(name):
    from .. import profiler

    return profiler.profiled_span(name)


class BatchConfig:
    """Policy for dynamic batch formation.

    Args:
        buckets: allowed batch sizes, ascending (default ``(1, 2, 4, 8,
            16)``). A formed batch of n requests is padded to the
            smallest bucket >= n; n larger than the top bucket is split
            across dispatches by the gather loop (it never collects more
            than ``max(buckets)``).
        max_wait_ms: longest a dequeued request may wait for batchmates
            before a partial batch is flushed (the Clipper-style bounded
            queueing delay). 0 disables waiting — batches still form
            from whatever is already queued.
        deadline_margin_ms: flush the forming batch early when the
            earliest request deadline in it has at most this much budget
            left (so batching can never turn a comfortable deadline into
            a DeadlineExceeded).
        cache: optional `jit.aot.CompileCache` override for the
            persistent executable cache (default: the process-wide cache
            honoring ``$JAX_COMPILATION_CACHE_DIR``).
    """

    def __init__(self, buckets=(1, 2, 4, 8, 16), max_wait_ms=2.0,
                 deadline_margin_ms=5.0, cache=None):
        bs = sorted({int(b) for b in buckets})
        if not bs or bs[0] < 1:
            raise ValueError(f"buckets must be positive ints, got {buckets}")
        self.buckets = tuple(bs)
        if max_wait_ms < 0 or deadline_margin_ms < 0:
            raise ValueError("max_wait_ms / deadline_margin_ms must be >= 0")
        self.max_wait_ms = float(max_wait_ms)
        self.deadline_margin_ms = float(deadline_margin_ms)
        self.cache = cache

    def __repr__(self):
        return (f"BatchConfig(buckets={self.buckets}, "
                f"max_wait_ms={self.max_wait_ms}, "
                f"deadline_margin_ms={self.deadline_margin_ms})")


class DynamicBatcher:
    """Bucketed batch execution over one exported `TranslatedLayer`.

    Thread-safe: `execute` may be called concurrently from several pool
    workers (each on its own member — the executable itself is immutable
    and shared). All counters live here so `ServingPool.stats()["batch"]`
    is one coherent snapshot.
    """

    def __init__(self, layer, config=None, clock=time.monotonic):
        if not hasattr(layer, "batched_call"):
            raise TypeError(
                "dynamic batching needs an exported TranslatedLayer "
                f"(got {type(layer).__name__}: no batched_call) — load the "
                "artifact with paddle_tpu.jit.load / inference.Config")
        self.layer = layer
        self.config = config or BatchConfig()
        self._clock = clock
        self._lock = _locks.new_lock("serving.batcher")
        # obs histograms, installed by the owning ServingPool when its
        # registry is on (None otherwise): per-request queue wait and
        # per-dispatch execute time land in the same families the
        # unbatched path observes (docs/observability.md)
        self.h_queue_wait = None
        self.h_execute = None
        # counters (guarded by _lock)
        self._formed = 0
        self._requests = 0
        self._padded = 0
        self._occupancy_sum = 0.0
        self._by_bucket: dict = {}
        self._flushes = {"full": 0, "wait": 0, "deadline": 0, "drain": 0}
        self._splits = 0
        self._split_requests = 0
        self._queue_wait_ms = 0.0
        self._queue_wait_max_ms = 0.0
        self._execute_ms = 0.0

    # -- policy ------------------------------------------------------------
    @property
    def max_bucket(self):
        return self.config.buckets[-1]

    def bucket_for(self, n):
        """Smallest configured bucket that fits n requests."""
        for b in self.config.buckets:
            if b >= n:
                return b
        raise ValueError(
            f"batch of {n} exceeds the largest bucket {self.max_bucket}")

    def validate(self, feeds):
        """Canonicalize one request's feeds against the exported
        input_spec: right arity, exact shapes, and a CAST to the spec
        dtype (mirroring what the unbatched path's jnp.asarray does under
        disabled x64 — float64 feeds land as float32 either way). A shape
        or arity mismatch raises ValueError (a malformed *request*) at
        admission time, before anything is queued."""
        spec = self.layer.input_spec
        if len(feeds) != len(spec):
            raise ValueError(
                f"expected {len(spec)} input(s) per request, got "
                f"{len(feeds)}")
        out = []
        for i, (f, s) in enumerate(zip(feeds, spec)):
            arr = np.asarray(f)
            want = tuple(s["shape"])
            if arr.shape != want:
                raise ValueError(
                    f"input {i} has shape {tuple(arr.shape)} but the "
                    f"exported program expects {want} — batching stacks "
                    f"whole examples; reshape the feed to the exported "
                    f"input_spec")
            out.append(np.ascontiguousarray(arr, dtype=np.dtype(s["dtype"])))
        return out

    def warmup(self, buckets=None):
        """Compile (or cache-load) every bucket executable up front so
        the pool takes traffic with zero compile stalls. Returns the
        warmed bucket list."""
        bs = self.config.buckets if buckets is None else sorted(
            {int(b) for b in buckets})
        for b in bs:
            if b > 0:
                self.layer.batched_call(b, cache=self.config.cache)
        return list(bs)

    # -- execution ---------------------------------------------------------
    def execute(self, requests):
        """Run one formed batch: pad to the bucket, dispatch the bucket's
        AOT executable once, scatter per-request output slices. Returns a
        list (aligned with `requests`) of per-request results, each the
        same `list of np outputs` shape `Predictor.run` returns. Raises
        whatever the dispatch raised — the pool's split/retry machinery
        classifies it."""
        n = len(requests)
        bucket = self.bucket_for(n)
        now = self._clock()

        # A formed batch serves N DIFFERENT traces, so the batch itself
        # is its own trace (a span can't have N parents): the batch span
        # links every member trace id, and each member's trace receives
        # a `serving.batch_member` event pointing back at the batch —
        # bidirectional batch-span <-> member-span linkage. The existing
        # profiled_span sites below nest under the batch span for free.
        members = ([r for r in requests
                    if r.ctx is not None and r.ctx.sampled]
                   if _otrace.enabled() else [])
        # the batch trace inherits the members' sampling (sampled=True
        # here — `members` keeps only sampled ctxs): a back-link to a
        # trace that recorded nothing would dangle
        bspan = _otrace.null_span() if not members else _otrace.root_span(
            "serving.batch",
            attrs={"bucket": bucket, "n": n,
                   "links": [r.ctx.trace_id_hex for r in members]},
            sampled=True)
        try:
            for r in members:
                _otrace.event_in(
                    "serving.batch_member", r.ctx,
                    attrs={"request": r.id,
                           "batch_trace": bspan.trace_id_hex,
                           "batch_span": bspan.span_id_hex})
            with _span("serving::batch_form"):
                columns = list(zip(*(r.feeds for r in requests)))
            with _span("serving::batch_pad"):
                pad = bucket - n
                if pad:
                    # replicate the last real example: real data, so
                    # padded lanes can never poison numerics (no
                    # zeros/NaN paths)
                    columns = [col + (col[-1],) * pad for col in columns]
                stacked = [np.stack(col) for col in columns]

            fn = self.layer.batched_call(bucket, cache=self.config.cache)
            t0 = time.perf_counter()
            with _span("serving::batch_dispatch"):
                outs = fn(*stacked)
                # the result readback IS the batch's deliverable — a
                # sanctioned sync inside the pool's batch_dispatch hot
                # region
                with _san.allow_host_sync("serving.batch_fetch"):
                    outs = [np.asarray(o) for o in outs]  # sync + copy
            exec_ms = (time.perf_counter() - t0) * 1e3
            if self.h_execute is not None:
                self.h_execute.observe(exec_ms / 1e3)

            with _span("serving::batch_scatter"):
                # copy, don't slice: a view would pin the whole
                # bucket-sized stacked buffer for as long as the caller
                # keeps one result
                results = [[o[j].copy() for o in outs] for j in range(n)]
        except BaseException as exc:
            bspan.end(error=exc)
            raise
        else:
            bspan.end()

        with self._lock:
            self._formed += 1
            self._requests += n
            self._padded += pad
            self._occupancy_sum += n / bucket
            self._by_bucket[bucket] = self._by_bucket.get(bucket, 0) + 1
            self._execute_ms += exec_ms
            for r in requests:
                if r.enqueued_at is not None:
                    w = max(0.0, (now - r.enqueued_at) * 1e3)
                    self._queue_wait_ms += w
                    self._queue_wait_max_ms = max(self._queue_wait_max_ms, w)
                    if self.h_queue_wait is not None and r.attempts == 1:
                        # first attempt only: a retried request's stamp
                        # includes its prior execution + backoff
                        self.h_queue_wait.observe(w / 1e3, ctx=r.ctx)
        return results

    # -- bookkeeping hooks (pool-driven) -----------------------------------
    def note_flush(self, reason):
        with self._lock:
            self._flushes[reason] = self._flushes.get(reason, 0) + 1

    def note_split(self, n):
        with self._lock:
            self._splits += 1
            self._split_requests += n

    # -- observability -----------------------------------------------------
    def stats(self):
        """Snapshot. Conservation: for every executed batch,
        bucket = requests_in_it + padding_in_it, so
        ``sum(b * executed_by_bucket[b]) == requests + padded_examples``.
        ``occupancy`` is the mean real-request fraction per dispatch."""
        with self._lock:
            formed = self._formed
            snap = {
                "buckets": list(self.config.buckets),
                "formed": formed,
                "requests": self._requests,
                "padded_examples": self._padded,
                "executed_by_bucket": dict(self._by_bucket),
                "occupancy": (self._occupancy_sum / formed) if formed else 0.0,
                "flushes": dict(self._flushes),
                "splits": self._splits,
                "split_requests": self._split_requests,
                "queue_wait_ms_total": self._queue_wait_ms,
                "queue_wait_ms_max": self._queue_wait_max_ms,
                "queue_wait_ms_avg": (self._queue_wait_ms / self._requests)
                if self._requests else 0.0,
                "execute_ms_total": self._execute_ms,
                "execute_ms_avg": (self._execute_ms / formed)
                if formed else 0.0,
            }
        snap["compile"] = self.layer.aot_stats() \
            if hasattr(self.layer, "aot_stats") else {}
        return snap
