"""paddle_tpu.obs.metrics — metric primitives + process-wide registry.

The framework's production pieces each kept private counters
(`ServingPool.stats()`, `ServingRouter.stats()`, `DecodeEngine.stats()`,
`engine.stats` dispatch counts...). This module is the ONE surface an
operator — or the router's SLO autoscaler — watches:

* **`Counter` / `Gauge` / `Histogram`** — standalone metric objects. The
  histogram uses FIXED log-spaced buckets, so p50/p95/p99 come from ~30
  ints (interpolated within the crossing bucket) with no per-sample
  storage and no allocation on the observe path.

* **Hot-path discipline** — `Counter.inc()` / `Histogram.observe()` are
  a dict-free int add (plus one `bisect` for the histogram): NO lock is
  taken. Under CPython's GIL a preempted read-modify-write can in theory
  drop an increment under extreme contention; that is an accepted
  telemetry tolerance. Exact invariants — the serving conservation laws
  — are published through **collector callbacks** over the owning
  subsystem's own lock-guarded counters (`register_collector(name,
  pool.stats)`), so the registry never duplicates bookkeeping and never
  de-syncs from the numbers the fault harnesses already assert.

* **`MetricsRegistry`** — get-or-create metric families (name + labels)
  plus the collector table. Its named lock (``obs.registry``) is held
  only to copy references during `snapshot()` — collector callbacks and
  serialization run OUTSIDE it, so a scrape can never nest
  ``obs.registry`` inside ``serving.pool`` (or vice versa) and the
  lockcheck acquisition-order graph stays cycle-free.

* **`registry()`** — the process-wide default instance every
  instrumented subsystem registers into unless handed a private one
  (`ServingPool(metrics=...)`); exporters (obs.export / obs.http) read
  from whichever registry they are given.
"""
from __future__ import annotations

import bisect
import math
import os
import weakref

from ..analysis import locks as _locks
from . import trace as _trace

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "registry",
    "default_latency_buckets",
]


def default_latency_buckets(lo=1e-4, hi=100.0, per_decade=5):
    """Fixed log-spaced histogram bounds (seconds): `per_decade` buckets
    per factor of 10 spanning [lo, hi] — 31 bounds at the defaults.
    Adjacent bounds differ by ~1.58x, so an interpolated quantile is
    within that ratio of the truth at any traffic shape."""
    n = int(round(math.log10(float(hi) / float(lo)) * per_decade))
    return tuple(float(lo) * (10.0 ** (i / float(per_decade)))
                 for i in range(n + 1))


def _label_key(labels):
    if not labels:
        return ()
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class _Metric:
    __slots__ = ("name", "help", "labels")

    def __init__(self, name, help="", labels=None):
        self.name = str(name)
        self.help = str(help)
        self.labels = dict(labels) if labels else {}


class Counter(_Metric):
    """Monotonic event count. `inc()` is ONE unlocked int add (see the
    module docstring for the GIL tolerance contract)."""

    kind = "counter"
    __slots__ = ("_value",)

    def __init__(self, name, help="", labels=None):
        super().__init__(name, help=help, labels=labels)
        self._value = 0

    def inc(self, n=1):
        self._value += n

    @property
    def value(self):
        return self._value

    def snapshot(self):
        return {"value": self._value}


class Gauge(_Metric):
    """Point-in-time value: `set()` a number, or `set_function()` a
    callable resolved at snapshot time (a zero-bookkeeping bridge for
    values some other object already tracks)."""

    kind = "gauge"
    __slots__ = ("_value", "_fn")

    def __init__(self, name, help="", labels=None):
        super().__init__(name, help=help, labels=labels)
        self._value = 0.0
        self._fn = None

    def set(self, v):
        self._value = float(v)

    def inc(self, n=1):
        self._value += n

    def dec(self, n=1):
        self._value -= n

    def set_function(self, fn):
        self._fn = fn

    @property
    def value(self):
        if self._fn is not None:
            return float(self._fn())
        return self._value

    def snapshot(self):
        try:
            return {"value": self.value}
        except Exception as e:  # a broken gauge callback must not break
            return {"value": None,  # the whole scrape
                    "error": f"{type(e).__name__}: {e}"}


class Histogram(_Metric):
    """Distribution over fixed log-spaced buckets. `observe(v)` is one
    `bisect` over the precomputed bounds plus three unlocked adds —
    nothing is allocated and no sample is stored, so p50/p95/p99 cost
    O(buckets) at SNAPSHOT time and ~nothing at observe time.

    Quantiles interpolate linearly within the bucket where the
    cumulative count crosses q*total; observations beyond the last bound
    report that bound (the overflow bucket has no upper edge).

    **Exemplars** (OpenMetrics-style): when an observation happens under
    a sampled trace context (obs.trace — or one is passed as `ctx=`),
    the bucket it lands in remembers that trace id and value — one
    unlocked slot write, no history. A scrape can then walk from "the
    p99 bucket grew" to the LAST request that landed there
    (``/traces/<id>``). With tracing off the exemplar path is one
    module-flag check."""

    kind = "histogram"
    __slots__ = ("bounds", "_counts", "_sum", "_count", "_exemplars")

    def __init__(self, name, help="", labels=None, bounds=None):
        super().__init__(name, help=help, labels=labels)
        bs = tuple(sorted(float(b) for b in
                          (bounds if bounds is not None
                           else default_latency_buckets())))
        if not bs:
            raise ValueError("histogram needs at least one bucket bound")
        self.bounds = bs
        self._counts = [0] * (len(bs) + 1)  # [-1] = overflow (+Inf)
        self._sum = 0.0
        self._count = 0
        self._exemplars = [None] * (len(bs) + 1)  # (trace_hex, value)

    def observe(self, v, ctx=None):
        v = float(v)
        i = bisect.bisect_left(self.bounds, v)
        self._counts[i] += 1
        self._sum += v
        self._count += 1
        if _trace.enabled():
            if ctx is None:
                ctx = _trace.current()
            if ctx is not None and ctx.sampled:
                self._exemplars[i] = (ctx.trace_id_hex, v)

    @property
    def count(self):
        return self._count

    @property
    def sum(self):
        return self._sum

    def counts(self):
        """Copy of the per-bucket counts (last entry = overflow). With
        `quantile(q, counts=...)` this supports windowed quantiles: diff
        two counts() snapshots and quantile the delta (the router's
        SLO autoscaler windows its p99s this way)."""
        return list(self._counts)

    def quantile(self, q, counts=None):
        """Interpolated q-quantile (q in [0, 1]) from bucket counts."""
        counts = list(self._counts) if counts is None else counts
        total = sum(counts)
        if total == 0:
            return 0.0
        target = q * total
        cum = 0
        for i, c in enumerate(counts):
            cum += c
            if c and cum >= target:
                if i >= len(self.bounds):
                    return self.bounds[-1]   # overflow: no upper edge
                lo = self.bounds[i - 1] if i else 0.0
                frac = (target - (cum - c)) / c
                return lo + frac * (self.bounds[i] - lo)
        return self.bounds[-1]

    def exemplar_for(self, q, counts=None):
        """The `(trace_id_hex, value)` exemplar of the bucket the
        q-quantile falls in (walking down to the nearest bucket that
        holds one), or None — the "which request blew the p99" hook."""
        counts = list(self._counts) if counts is None else counts
        total = sum(counts)
        if total == 0:
            return None
        target = q * total
        cum = 0
        crossing = len(counts) - 1
        for i, c in enumerate(counts):
            cum += c
            if c and cum >= target:
                crossing = i
                break
        for i in range(crossing, -1, -1):
            if self._exemplars[i] is not None:
                return self._exemplars[i]
        return None

    def snapshot(self):
        # copy counts ONCE so count/sum/quantiles describe one instant
        # even while observers keep adding
        counts = list(self._counts)
        total = sum(counts)
        cum, buckets = 0, []
        for i, b in enumerate(self.bounds):
            cum += counts[i]
            buckets.append([b, cum])
        buckets.append(["+Inf", total])
        snap = {
            "count": total,
            "sum": self._sum,
            "avg": (self._sum / total) if total else 0.0,
            "p50": self.quantile(0.50, counts),
            "p95": self.quantile(0.95, counts),
            "p99": self.quantile(0.99, counts),
            "buckets": buckets,
        }
        exemplars = {}
        for i, ex in enumerate(self._exemplars):
            if ex is not None:
                exemplars[i] = {"trace_id": ex[0], "value": ex[1]}
        if exemplars:  # absent entirely when no trace ever landed, so
            snap["exemplars"] = exemplars  # untraced goldens stay stable
        return snap


_METRIC_KINDS = {Counter.kind: Counter, Gauge.kind: Gauge,
                 Histogram.kind: Histogram}


class MetricsRegistry:
    """Process-wide (or private) metric table: get-or-create families by
    (name, labels), plus collector callbacks bridging existing `stats()`
    dicts in — single source of truth, zero duplicated bookkeeping.

    Thread-safety: the ``obs.registry`` named lock guards only the
    tables. `snapshot()` copies references under it and then calls every
    collector and serializes WITHOUT it, so collector callbacks are free
    to take their owners' locks (serving.pool / router.core / ...)."""

    #: label key every over-cardinality observation collapses onto
    OVERFLOW_LABELS = {"_overflow": "true"}

    def __init__(self, max_label_sets=None):
        self._lock = _locks.new_lock("obs.registry")
        self._metrics = {}     # (name, label_key) -> metric
        self._kinds = {}       # name -> metric class (family-wide)
        self._collectors = {}  # name -> callable | weakref.WeakMethod
        # per-NAME label-cardinality cap: a runaway label source (e.g.
        # request ids leaking into labels) degrades to ONE shared
        # `_overflow` series per family instead of unbounded growth
        if max_label_sets is None:
            max_label_sets = int(os.environ.get(
                "PADDLE_TPU_OBS_MAX_LABEL_SETS", "64"))
        if max_label_sets < 1:
            raise ValueError("max_label_sets must be >= 1")
        self.max_label_sets = max_label_sets
        self._label_sets = {}  # name -> count of distinct label sets
        self.label_overflows = 0

    # -- metric families ---------------------------------------------------
    def _get(self, cls, name, help, labels, **kw):
        name = str(name)
        key = (name, _label_key(labels))
        with self._lock:
            # kind is a FAMILY property (checked across every label
            # set): one name holding mixed kinds would make the
            # Prometheus exposition unrenderable
            known = self._kinds.get(name)
            if known is not None and known is not cls:
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{known.kind}, requested {cls.kind}")
            m = self._metrics.get(key)
            if m is None:
                if labels and \
                        self._label_sets.get(name, 0) >= self.max_label_sets:
                    # cardinality cap: collapse onto the family's single
                    # _overflow series (created on first overflow; it
                    # does NOT count against the cap)
                    self.label_overflows += 1
                    labels = dict(self.OVERFLOW_LABELS)
                    key = (name, _label_key(labels))
                    m = self._metrics.get(key)
                    if m is not None:
                        return m
                else:
                    self._label_sets[name] = \
                        self._label_sets.get(name, 0) + 1
                m = cls(name, help=help, labels=labels, **kw)
                self._metrics[key] = m
                self._kinds[name] = cls
            return m

    def counter(self, name, help="", labels=None):
        return self._get(Counter, name, help, labels)

    def gauge(self, name, help="", labels=None):
        return self._get(Gauge, name, help, labels)

    def histogram(self, name, help="", labels=None, bounds=None):
        h = self._get(Histogram, name, help, labels, bounds=bounds)
        if bounds is not None:
            want = tuple(sorted(float(b) for b in bounds))
            if h.bounds != want:
                raise ValueError(
                    f"histogram {name!r} already exists with bounds "
                    f"{h.bounds} — conflicting bounds {want} requested "
                    f"(observations would land in buckets the caller "
                    f"never asked for)")
        return h

    # -- collectors --------------------------------------------------------
    def register_collector(self, name, fn):
        """Attach a stats-snapshot callable under `name`; its dict rides
        in `snapshot()["collectors"][name]` and is flattened into the
        Prometheus exposition. Bound methods are held WEAKLY (a pool
        that is garbage-collected without shutdown() un-registers
        itself); a collector returning None is pruned the same way.
        Re-registering a name replaces the previous collector."""
        if hasattr(fn, "__self__"):
            fn = weakref.WeakMethod(fn)
        with self._lock:
            self._collectors[name] = fn

    def unregister_collector(self, name, fn=None):
        """Remove the collector under `name`. Pass the SAME callable that
        was registered to make the removal conditional: if a later
        registration replaced this one (two same-named owners — last
        writer wins), the survivor's collector is left alone instead of
        being torn down by the loser's shutdown."""
        with self._lock:
            if fn is None:
                self._collectors.pop(name, None)
                return
            cur = self._collectors.get(name)
            live = cur() if isinstance(cur, weakref.WeakMethod) else cur
            if live is None or live == fn:
                self._collectors.pop(name, None)

    def collector_names(self):
        with self._lock:
            return sorted(self._collectors)

    # -- snapshot ----------------------------------------------------------
    def snapshot(self):
        """Nested-JSON view: ``{"metrics": {name: [{labels, kind, ...}]},
        "collectors": {name: stats-dict}}``. Deterministic ordering
        (sorted names / label sets); collectors run OUTSIDE the registry
        lock."""
        with self._lock:
            metrics = sorted(self._metrics.items())
            collectors = list(self._collectors.items())
        out_m = {}
        for (name, _), m in metrics:
            out_m.setdefault(name, []).append(
                {"kind": m.kind, "labels": dict(m.labels),
                 "help": m.help, **m.snapshot()})
        out_c = {}
        dead = []
        for name, fn in collectors:
            f = fn() if isinstance(fn, weakref.WeakMethod) else fn
            if f is None:
                dead.append((name, fn))
                continue
            try:
                stats = f()
            except Exception as e:  # tpu-lint: disable=TL007 — a broken
                # stats() must not break every OTHER subsystem's scrape
                out_c[name] = {"_collector_error":
                               f"{type(e).__name__}: {e}"}
                continue
            if stats is None:
                dead.append((name, fn))
                continue
            out_c[name] = stats
        if dead:
            with self._lock:
                for name, fn in dead:
                    if self._collectors.get(name) is fn:
                        del self._collectors[name]
        return {"metrics": out_m, "collectors": out_c}

    def prometheus_text(self):
        """Text exposition (format 0.0.4) of `snapshot()`."""
        from .export import render_prometheus

        return render_prometheus(self.snapshot())


_DEFAULT = MetricsRegistry()


def registry():
    """The process-wide default registry. Constructed at first import of
    paddle_tpu.obs — i.e. lazily, when the first instrumented subsystem
    comes up — so a PADDLE_TPU_LOCKCHECK=1 harness observes its named
    lock like any other framework lock."""
    return _DEFAULT
