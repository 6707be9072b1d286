"""What every driver shares: files found by name, compilation counting, host
spans on the profiler's clock, the profiler window, device facts and the
comparison of norms."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve_cell(name: str, rehearsal: bool) -> dict:
    """A cell by its name in BENCHMARK.json: its configuration's file, its
    traffic mix's file (`traffic/<traffic>.json`) and the limits of its
    `correct` (`limits/<cell>.json`, set from this cell's own readings: {}
    where the file is not there yet, and then no number can be compared).
    In a rehearsal the `rehearsal` entries of the three files replace the
    sizes, and the limits that were read at those sizes."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(has: {sorted(cells)})")
    cell = dict(cells[name])
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT, conf["file"])
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    model = dict(config["model"])
    if rehearsal:
        model.update(config.get("rehearsal", {}))
        mix = {**mix, **mix.get("rehearsal", {})}
    limits = os.path.join(HERE, "limits", name + ".json")
    doc = load_json(limits) if os.path.exists(limits) else {"numbers": {}}
    limits = {**doc["numbers"],
              **(doc.get("rehearsal", {}) if rehearsal else {})}
    cell.update(model=model, mix=mix, bench=bench, rehearsal=rehearsal,
                limits={k: v["limit"] for k, v in limits.items()})
    return cell


def metrics_for(cell: dict, group: str) -> list:
    """The metrics of `group` (`end_to_end` or `per_layer`) that this cell
    reports: those with no `workloads` key, or with the cell in it."""
    return [m for m in cell["bench"][group]
            if cell["name"] in m.get("workloads", [cell["name"]])]


class CompileCounter:
    """Executables XLA was asked to build in this process (`builds`), and
    how many of them jax's persistent cache served (`hits`)."""

    def __init__(self):
        import jax.monitoring as mon

        self.builds = 0
        self.hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == _BACKEND_COMPILE:
            self.builds += 1

    def _on_event(self, event, **kw):
        if event == _CACHE_HIT:
            self.hits += 1


class Spans:
    """The benchmark's own spans around its calls into the program: kept in
    memory by the host clock, and written into the profiler's trace too
    (`bench::<name>`), where they meet the device's events on one clock."""

    def __init__(self):
        self.records = []

    @contextlib.contextmanager
    def span(self, name):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench::" + name):
            yield
        self.records.append((name, t0, time.perf_counter()))

    def total(self, name, lo=-np.inf, hi=np.inf):
        return sum(b - a for n, a, b in list(self.records)
                   if n == name and a >= lo and b <= hi)


class TraceWindow:
    """A profiler trace of some seconds inside a run's window. The trace
    lands in a fixed directory inside the checkout and is removed once it
    has been reduced."""

    def __init__(self, enabled: bool, start_after_s: float, seconds: float):
        self.enabled = enabled
        self.start_after_s = start_after_s
        self.seconds = seconds
        self.dir = os.path.join(ROOT, ".bench_tmp", "trace")
        self.t_start = self.t_stop = None
        self.on_start = self.on_stop = None

    def poll(self, t_rel: float):
        """Start or stop the trace when its time has come (`t_rel` is the
        time since the window opened)."""
        import jax

        if not self.enabled:
            return
        if self.t_start is None and t_rel >= self.start_after_s:
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)
            self.t_start = time.perf_counter()
            if self.on_start:
                self.on_start()
        elif self.t_start is not None and self.t_stop is None \
                and time.perf_counter() - self.t_start >= self.seconds:
            self.stop()

    def stop(self):
        import jax

        if self.enabled and self.t_start is not None and self.t_stop is None:
            if self.on_stop:
                self.on_stop()
            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()

    def reduced(self):
        from benchmarks import trace_reader

        if not self.enabled or self.t_stop is None:
            return None
        try:
            return trace_reader.load(trace_reader.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def device_info(devices, chips: int) -> tuple:
    """(the line's `device` object, the fullest chip's reserved peak). The
    runtime counts live buffers (`peak_bytes_in_use`: `memory_peak_bytes`)
    and what it holds back for the executables' temporaries
    (`peak_bytes_reserved`) apart, and they are reported apart."""
    used = devices[:chips]
    stats = [d.memory_stats() or {} for d in used]
    return ({"platform": used[0].platform, "kind": used[0].device_kind,
             "count": len(devices), "memory_peak_bytes": int(max(
                 s.get("peak_bytes_in_use", 0) for s in stats))},
            int(max(s.get("peak_bytes_reserved", 0) for s in stats)))


def leaf_norms(tree: dict, parts: dict = None) -> dict:
    """name -> float norm, in one jitted call. A leaf that fuses several
    projections (`parts[name]` of them along its last axis, as q, k and v)
    is measured part by part, as `name[i]`: a key's bias has no gradient
    under softmax, and fused with q and v it would hide in their norm."""
    import jax
    import jax.numpy as jnp

    def norms(t):
        out = {}
        for k, v in t.items():
            n = (parts or {}).get(k, 1)
            for i, piece in enumerate(jnp.split(v, n, axis=-1)):
                out[k if n == 1 else f"{k}[{i}]"] = jnp.sqrt(jnp.sum(
                    jnp.square(piece.astype(jnp.float32))))
        return out

    return {k: float(v) for k, v in jax.jit(norms)(tree).items()}


def leaf_gaps(got: dict, want: dict, skip=()) -> tuple:
    """(worst, its leaf, mean): per leaf the gap between the program's norm
    and the reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger; the widest of them, where, and their
    mean over the leaves."""
    names = [n for n in want if n not in skip]
    median = float(np.median([want[n] for n in names]))
    gaps = [abs(got[n] - want[n]) / max(want[n], median, 1e-30)
            for n in names]
    worst = int(np.argmax(gaps))
    return gaps[worst], names[worst], float(np.mean(gaps))


class Checks:
    """Each number compared beside its limit; `correct` is all of them.
    A measured limit comes from the cell's own `limits/<cell>.json`
    (`limits`); an exact comparison gives its limit in the call. A number
    with neither stops the run: no cell borrows another's limits."""

    def __init__(self, limits: dict):
        self.limits = limits
        self.rows = []

    def add(self, name, value, limit=None, at_most=True):
        if limit is None:
            try:
                limit = self.limits[name]
            except KeyError:
                raise SystemExit(
                    f"no limit for {name!r} in this cell's limits file "
                    f"(benchmarks/limits/<cell>.json has {sorted(self.limits)}"
                    f"): set it from the cell's own readings, calibrate.py "
                    f"prints them") from None
        value = float(value)
        ok = value <= limit if at_most else value >= limit
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "ok": bool(ok and np.isfinite(value))})

    @property
    def correct(self):
        return bool(self.rows) and all(r["ok"] for r in self.rows)
