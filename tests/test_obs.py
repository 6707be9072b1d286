"""paddle_tpu.obs — metrics registry, exporters, HTTP endpoint, SLO gate.

Kept cheap on purpose (ROADMAP suite-budget caveat): stub predictors
(no XLA programs), a private registry per test (no cross-test state),
one tiny Engine build for the collector bridge.
"""
import gc
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu.obs import (
    Counter, Gauge, Histogram, MetricsRegistry, MetricsServer,
    default_latency_buckets, render_json, render_prometheus, slo,
)
from paddle_tpu.obs import registry as default_registry


class Stub:
    """Predictor stand-in: the pool machinery runs for real, XLA never."""

    def clone(self):
        return Stub()

    def reset_handles(self):
        pass


def make_pool(reg, **kw):
    from paddle_tpu.inference.serving import ServingPool

    kw.setdefault("size", 2)
    kw.setdefault("metrics", reg)
    return ServingPool(predictor=Stub(), **kw)


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def test_counter_and_gauge():
    c = Counter("reqs")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = Gauge("depth")
    g.set(3.5)
    assert g.value == 3.5
    g.inc()
    g.dec(0.5)
    assert g.value == 4.0
    g2 = Gauge("cb")
    g2.set_function(lambda: 7)
    assert g2.value == 7.0
    assert g2.snapshot() == {"value": 7.0}


def test_histogram_bucket_math_known_samples():
    h = Histogram("lat", bounds=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 3.0, 3.0, 3.0, 5.0, 9.0):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 7
    assert s["sum"] == pytest.approx(25.0)
    # cumulative by le: 1 <=1, 2 <=2, 5 <=4, 6 <=8, 7 total
    assert s["buckets"] == [[1.0, 1], [2.0, 2], [4.0, 5], [8.0, 6],
                            ["+Inf", 7]]
    # p50: target 3.5 crosses in (2, 4] holding 3 -> 2 + 1.5/3 * 2 = 3.0
    assert s["p50"] == pytest.approx(3.0)
    # p95: target 6.65 crosses in the overflow bucket -> clamps to 8.0
    assert s["p95"] == pytest.approx(8.0)
    assert s["p99"] == pytest.approx(8.0)
    # exact-edge quantile: target exactly at a cumulative boundary
    assert h.quantile(2 / 7) == pytest.approx(2.0)


def test_histogram_default_buckets_log_spaced():
    bs = default_latency_buckets()
    ratios = {round(b2 / b1, 6) for b1, b2 in zip(bs, bs[1:])}
    assert len(ratios) == 1          # constant multiplicative spacing
    assert bs[0] == pytest.approx(1e-4) and bs[-1] == pytest.approx(100.0)
    h = Histogram("lat")
    for v in (0.001, 0.01, 0.01, 0.1):
        h.observe(v)
    s = h.snapshot()
    assert 0.001 <= s["p50"] <= 0.02
    assert s["p50"] <= s["p95"] <= s["p99"] <= 0.2
    assert Histogram("e").snapshot()["p99"] == 0.0  # empty: no samples


def test_registry_get_or_create_and_conflicts():
    r = MetricsRegistry()
    assert r.counter("a") is r.counter("a")
    assert r.counter("a", labels={"k": "v"}) is not r.counter("a")
    with pytest.raises(TypeError):
        r.gauge("a")
    h = r.histogram("h", bounds=(1.0,))
    assert r.histogram("h") is h          # bounds omitted: same family
    assert r.histogram("h", bounds=(1.0,)) is h   # matching bounds ok
    with pytest.raises(ValueError, match="conflicting bounds"):
        r.histogram("h", bounds=(1.0, 2.0))
    # kind is a FAMILY property: a different label set cannot smuggle a
    # second kind under an existing name (it would break the exposition)
    with pytest.raises(TypeError):
        r.counter("h", labels={"x": "1"})
    render_prometheus(r.snapshot())  # family stays renderable


def test_histogram_windowed_quantile_via_counts():
    h = Histogram("lat", bounds=(1.0, 2.0, 4.0))
    h.observe(3.9)                    # cold-start outlier
    base = h.counts()
    for v in (0.5, 0.5, 1.5, 1.5):    # measured window
        h.observe(v)
    window = [a - b for a, b in zip(h.counts(), base)]
    assert sum(window) == 4
    assert h.quantile(0.99, window) <= 2.0   # outlier excluded
    assert h.snapshot()["p99"] > 2.0         # lifetime view keeps it


def test_unregister_collector_is_conditional():
    """Two same-named owners: last registration wins, and the LOSER's
    shutdown must not tear down the survivor's collector."""
    r = MetricsRegistry()

    class Owner:
        def __init__(self, v):
            self.v = v

        def stats(self):
            return {"v": self.v}

    first, second = Owner(1), Owner(2)
    r.register_collector("dup", first.stats)
    r.register_collector("dup", second.stats)   # replaces first
    r.unregister_collector("dup", first.stats)  # loser's shutdown: no-op
    assert r.snapshot()["collectors"]["dup"] == {"v": 2}
    r.unregister_collector("dup", second.stats)
    assert "dup" not in r.snapshot()["collectors"]


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def _golden_registry():
    r = MetricsRegistry()
    r.counter("reqs.total", help="total requests").inc(3)
    r.counter("reqs.total", labels={"pool": 'a"b\\c'}).inc(1)
    r.gauge("depth").set(2)
    h = r.histogram("lat", bounds=(1.0, 2.0))
    h.observe(0.5)
    h.observe(1.5)
    r.register_collector("pool", lambda: {
        "admitted": 5, "ok": True, "note": "json-only",
        "members": [{"index": 0, "alive": True},
                    {"index": 1, "alive": False}]})
    return r


def test_prometheus_text_golden():
    text = render_prometheus(_golden_registry().snapshot())
    assert text == """\
# TYPE depth gauge
depth 2
# TYPE lat histogram
lat_bucket{le="1"} 1
lat_bucket{le="2"} 2
lat_bucket{le="+Inf"} 2
lat_sum 2
lat_count 2
# HELP reqs_total total requests
# TYPE reqs_total counter
reqs_total 3
reqs_total{pool="a\\"b\\\\c"} 1
# collector pool
pool_admitted 5
pool_members_alive{idx="0"} 1
pool_members_alive{idx="1"} 0
pool_members_index{idx="0"} 0
pool_members_index{idx="1"} 1
pool_ok 1
"""


def test_snapshot_json_roundtrip():
    snap = _golden_registry().snapshot()
    loaded = json.loads(render_json(snap))
    assert loaded["collectors"]["pool"]["note"] == "json-only"
    assert loaded["collectors"]["pool"]["admitted"] == 5
    fam = loaded["metrics"]["lat"][0]
    assert fam["kind"] == "histogram" and fam["count"] == 2
    # numpy leaves inside collector dicts degrade to plain numbers —
    # in BOTH exporters (a bridged stats() dict computed with numpy
    # must not silently vanish from the scrape)
    np_snap = {"collectors": {"x": {"n": np.int64(3),
                                    "f": np.float32(0.5),
                                    "v": [np.int64(1), np.int64(2)]}},
               "metrics": {}}
    assert json.loads(render_json(np_snap))["collectors"]["x"]["n"] == 3
    text = render_prometheus(np_snap)
    assert "x_n 3" in text and "x_f 0.5" in text
    assert 'x_v{idx="1"} 2' in text


def test_prometheus_nonfinite_values_render():
    """One inf/NaN value must render as a Prometheus literal, not turn
    the whole scrape into an exception."""
    r = MetricsRegistry()
    r.gauge("g.inf").set(float("inf"))
    r.gauge("g.nan").set(float("nan"))
    r.register_collector("c", lambda: {"frac": float("-inf")})
    text = render_prometheus(r.snapshot())
    assert "g_inf +Inf" in text
    assert "g_nan NaN" in text
    assert "c_frac -Inf" in text


def test_collector_weak_and_broken():
    r = MetricsRegistry()

    class Owner:
        def stats(self):
            return {"v": 1}

    o = Owner()
    r.register_collector("own", o.stats)
    r.register_collector("boom", lambda: 1 / 0)
    snap = r.snapshot()
    assert snap["collectors"]["own"] == {"v": 1}
    assert "_collector_error" in snap["collectors"]["boom"]
    del o
    gc.collect()
    assert "own" not in r.snapshot()["collectors"]
    assert "own" not in r.collector_names()


# ---------------------------------------------------------------------------
# HTTP endpoint
# ---------------------------------------------------------------------------

def test_http_endpoint_smoke():
    r = MetricsRegistry()
    r.counter("hits").inc(2)
    health = {"ok": True}
    with MetricsServer(r, healthz=lambda: (health["ok"],
                                           {"detail": "x"})) as s:
        url = s.url
        text = urllib.request.urlopen(url + "/metrics",
                                      timeout=5).read().decode()
        assert "hits 2" in text
        body = json.loads(urllib.request.urlopen(
            url + "/metrics.json", timeout=5).read())
        assert body["metrics"]["hits"][0]["value"] == 2
        assert urllib.request.urlopen(url + "/healthz",
                                      timeout=5).status == 200
        health["ok"] = False
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url + "/healthz", timeout=5)
        assert ei.value.code == 503
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url + "/nope", timeout=5)
        assert ei.value.code == 404
        thread = s._thread
    # context exit == stop(): thread joined, port closed
    assert not s.running and not thread.is_alive()
    with pytest.raises(Exception):
        urllib.request.urlopen(url + "/metrics", timeout=1)
    s.stop()  # idempotent


# ---------------------------------------------------------------------------
# ServingPool integration
# ---------------------------------------------------------------------------

def test_pool_registers_histograms_and_collector():
    reg = MetricsRegistry()
    pool = make_pool(reg, name="t")
    try:
        for _ in range(8):
            assert pool.submit(lambda p: 42, timeout=5.0).result() == 42
        snap = reg.snapshot()
        st = snap["collectors"]["serving.pool.t"]
        assert st["admitted"] == 8 and st["completed"] == 8
        assert st["queue_depth_peak"] >= 1
        for fam in ("serving.request_seconds", "serving.queue_wait_seconds",
                    "serving.execute_seconds"):
            assert snap["metrics"][fam][0]["count"] == 8, fam
        # latency >= execute is not guaranteed per-sample by clocks, but
        # sums are monotone: total latency covers queue wait + execute
        lat = snap["metrics"]["serving.request_seconds"][0]
        exe = snap["metrics"]["serving.execute_seconds"][0]
        assert lat["sum"] >= exe["sum"] * 0.99
    finally:
        pool.shutdown(drain_timeout=5.0)
    assert "serving.pool.t" not in reg.snapshot()["collectors"]


def test_pool_serve_metrics_and_healthz_lifecycle():
    reg = MetricsRegistry()
    pool = make_pool(reg, name="web")
    try:
        server = pool.serve_metrics()
        assert pool.serve_metrics() is server  # idempotent
        pool.submit(lambda p: 1, timeout=5.0).result()
        text = urllib.request.urlopen(server.url + "/metrics",
                                      timeout=5).read().decode()
        assert "serving_pool_web_admitted 1" in text
        assert urllib.request.urlopen(server.url + "/healthz",
                                      timeout=5).status == 200
    finally:
        pool.shutdown(drain_timeout=5.0)
    assert not server.running  # shutdown stopped the exporter


def test_conservation_law_from_registry():
    reg = MetricsRegistry()
    pool = make_pool(reg, name="law", default_timeout=5.0,
                     hang_grace=0.02, supervise_interval=0.01)
    try:
        reqs = [pool.submit(lambda p: "ok") for _ in range(6)]
        reqs.append(pool.submit(
            lambda p: (_ for _ in ()).throw(ValueError("malformed"))))
        reqs.append(pool.submit(lambda p: time.sleep(0.4), timeout=0.05))
        for r in reqs:
            try:
                r.result(timeout=5.0)
            except Exception:
                pass
        # quiesce: the wedged slot's replacement may lag the callers
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            st = reg.snapshot()["collectors"]["serving.pool.law"]
            balance = (st["completed"] + st["failed"] + st["timed_out"]
                       + st["cancelled"])
            if st["admitted"] == balance and st["in_flight"] == 0:
                break
            time.sleep(0.02)
        assert st["admitted"] == 8
        assert st["admitted"] == balance, st
        assert st["completed"] == 6 and st["failed"] == 1 \
            and st["timed_out"] == 1, st
    finally:
        pool.shutdown(drain_timeout=5.0)


def test_metrics_false_strips_instrumentation():
    pool = make_pool(None, metrics=False, name="off")
    try:
        assert pool._h_latency is None and pool._metrics is None
        assert pool.submit(lambda p: 9, timeout=5.0).result() == 9
        with pytest.raises(RuntimeError, match="metrics=False"):
            pool.serve_metrics()
        assert "serving.pool.off" not in \
            default_registry().snapshot()["collectors"]
    finally:
        pool.shutdown(drain_timeout=5.0)


def test_overhead_guard_instrumented_vs_disabled(monkeypatch):
    """The always-on hot path must be in the noise of the machinery
    itself. Guarded by COUNTS, not by the wall clock (a timing ratio
    fails under a loaded suite and passes alone):

    1. metrics: an instrumented pool makes exactly one observation per
       histogram per request; a registry-disabled pool has no histogram
       to observe at all;
    2. tracing off: every probe is the shared no-op and the recorder's
       `record` is never called, through a whole decode engine run;
    3. tracing on: the same run makes a fixed number of spans a request,
       a prefill, a round and a step — nothing per token beyond the one
       `decode.step_join` a member a step."""
    import collections

    from paddle_tpu.inference.decode import demo
    from paddle_tpu.obs import flight, trace

    n = 40
    on = make_pool(MetricsRegistry(), name="ovh-on", max_queue_depth=n + 8)
    off = make_pool(None, metrics=False, name="ovh-off",
                    max_queue_depth=n + 8)
    try:
        for pool in (on, off):
            for r in [pool.submit(lambda p: 0, timeout=30.0)
                      for _ in range(n)]:
                r.result(timeout=30.0)
        assert [h.count for h in (on._h_queue_wait, on._h_execute,
                                  on._h_latency)] == [n, n, n]
        assert off._h_queue_wait is off._h_execute is off._h_latency is None
    finally:
        on.shutdown(drain_timeout=10.0)
        off.shutdown(drain_timeout=10.0)

    calls = []
    real = flight.FlightRecorder.record
    monkeypatch.setattr(flight.FlightRecorder, "record",
                        lambda self, span: (calls.append(span.name),
                                            real(self, span))[1])

    def run():
        eng = demo.tiny_engine(1)
        try:
            assert len(eng.generate(demo.demo_prompt(5, 8), 4)) == 4
            return eng.stats()
        finally:
            eng.shutdown()

    was = trace.enabled()
    try:
        trace.disable()
        assert trace.span("x") is trace.root_span("y") is trace.null_span()
        assert trace.detached() is trace.null_span()
        run()
        assert calls == []
        trace.enable()
        st = run()
    finally:
        (trace.enable if was else trace.disable)()
    got = collections.Counter(calls)
    rounds, steps, chunks = st["rounds"], st["steps"], st["prefill_chunks"]
    assert (rounds, steps, chunks) == (3, 3, 1)
    phase = ("", ".pack", ".handoff", ".enqueue", ".fetch", ".deliver")
    want = {"decode.sequence": 1, "decode.first_token": 1,
            "decode.round": rounds, "decode.round.admit": rounds,
            "decode.prefill": chunks, "decode.step": steps,
            "decode.step_join": steps, "decode.round.decode.grow": steps}
    want.update({"decode.round.prefill" + p: chunks for p in phase})
    want.update({"decode.round.decode" + p: steps for p in phase})
    idle = got.pop("decode.idle_wait", 0)
    got.pop("host.gc", None)             # the collector's own schedule
    assert dict(got) == want
    assert idle <= 2                     # before the request, after it


# ---------------------------------------------------------------------------
# profiler + engine bridges
# ---------------------------------------------------------------------------

def test_profiled_span_feeds_histogram_without_recording():
    from paddle_tpu import profiler

    h = Histogram("span.lat", bounds=(0.001, 0.1, 1.0))
    with profiler.profiled_span("unit::span", histogram=h):
        time.sleep(0.002)
    assert h.count == 1
    assert 0.001 <= h.snapshot()["sum"] <= 1.0
    # histogram=None keeps the zero-cost no-op contract when idle
    assert not profiler.host_recording()
    with profiler.profiled_span("unit::noop"):
        pass


def test_engine_stats_collector_registered():
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu import nn

    paddle.seed(0)
    model = nn.Linear(4, 2)
    opt = paddle.optimizer.Momentum(learning_rate=0.1,
                                    parameters=model.parameters())
    mesh = dist.build_mesh(dp=-1, devices=jax.devices()[:1])
    eng = dist.parallelize(
        model, opt, mesh=mesh,
        loss_fn=lambda m, x, y: paddle.nn.functional.mse_loss(m(x), y))
    key = eng._obs_key
    snap = default_registry().snapshot()
    assert snap["collectors"][key] == {"dispatches": 0, "device_puts": 0,
                                       "steps": 0}
    del eng, model, opt
    gc.collect()
    assert key not in default_registry().snapshot()["collectors"]


# ---------------------------------------------------------------------------
# SLO gate
# ---------------------------------------------------------------------------

def test_slo_evaluate_pass_fail_and_missing():
    objs = [slo.Objective("x.p99", "max", slack=2.0, unit="s"),
            slo.Objective("x.rps", "min", slack=2.0, unit="req/s")]
    baseline = {"x.p99": {"kind": "max", "bound": 1.0},
                "x.rps": {"kind": "min", "bound": 100.0}}
    ok = slo.evaluate({"x.p99": 0.5, "x.rps": 250.0}, baseline, objs)
    assert ok["ok"] and not ok["breaches"]
    bad = slo.evaluate({"x.p99": 2.0, "x.rps": 50.0}, baseline, objs)
    assert set(bad["breaches"]) == {"x.p99", "x.rps"}
    missing = slo.evaluate({"x.p99": 0.5}, baseline, objs)
    assert missing["breaches"] == ["x.rps"]  # unmeasured objective fails
    nobase = slo.evaluate({"x.p99": 0.5, "x.rps": 250.0},
                          {"x.p99": baseline["x.p99"]}, objs)
    assert nobase["breaches"] == ["x.rps"]   # unratcheted objective fails
    report = slo.format_report(bad)
    assert "FAIL" in report and "SLO gate: FAIL" in report


def test_slo_write_and_load_baseline(tmp_path):
    objs = [slo.Objective("a.lat", "max", slack=4.0),
            slo.Objective("a.rps", "min", slack=4.0)]
    path = str(tmp_path / "SLO_BASELINE.json")
    written = slo.write_baseline(path, {"a.lat": 0.1, "a.rps": 400.0},
                                 objs, note="test")
    assert written["a.lat"]["bound"] == pytest.approx(0.4)
    assert written["a.rps"]["bound"] == pytest.approx(100.0)
    loaded = slo.load_baseline(path)
    assert loaded == written
    rep = slo.evaluate({"a.lat": 0.39, "a.rps": 101.0}, loaded, objs)
    assert rep["ok"]
    with pytest.raises(FileNotFoundError, match=r"write_baseline\(\)"):
        slo.load_baseline(str(tmp_path / "missing.json"))
    with pytest.raises(ValueError):
        slo.Objective("bad", "between")
    with pytest.raises(ValueError):
        slo.Objective("bad", "max", slack=0.5)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_metrics_dump_cli_scrape_modes(capsys):
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "metrics_dump", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "metrics_dump.py"))
    md = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(md)
    r = MetricsRegistry()
    r.counter("cli.hits").inc(5)
    with MetricsServer(r) as s:
        assert md.main(["--url", s.url]) == 0
        assert "cli_hits 5" in capsys.readouterr().out
        assert md.main(["--url", f"127.0.0.1:{s.port}",
                        "--format", "json"]) == 0
        assert json.loads(capsys.readouterr().out)[
            "metrics"]["cli.hits"][0]["value"] == 5
        # --grep keeps only matching lines (shell-free series filter)
        r.counter("cli.misses").inc(1)
        assert md.main(["--url", s.url, "--grep", "cli_hits"]) == 0
        filtered = capsys.readouterr().out
        assert "cli_hits 5" in filtered and "cli_misses" not in filtered
        assert md.main(["--url", s.url, "--grep", "(unbalanced"]) == 2
        capsys.readouterr()
    assert md.main(["--url", "http://127.0.0.1:1/metrics"]) == 1


def test_label_cardinality_cap_degrades_to_overflow():
    """Beyond max_label_sets distinct label sets per family, new label
    sets collapse onto ONE shared `_overflow` series instead of growing
    the registry unboundedly (runaway label sources: request ids,
    per-sequence tags...)."""
    reg = MetricsRegistry(max_label_sets=3)
    for i in range(3):
        reg.counter("fam", labels={"k": str(i)}).inc()
    over = reg.counter("fam", labels={"k": "runaway-1"})
    assert over.labels == MetricsRegistry.OVERFLOW_LABELS
    # every further new label set lands on the SAME series
    again = reg.counter("fam", labels={"k": "runaway-2"})
    assert again is over
    over.inc(2)
    assert reg.label_overflows == 2
    # existing label sets still resolve to their own metrics
    assert reg.counter("fam", labels={"k": "1"}).labels == {"k": "1"}
    # the cap is per NAME: other families are unaffected
    assert reg.counter("other", labels={"k": "x"}).labels == {"k": "x"}
    # the exposition renders the overflow series like any other
    assert 'fam{_overflow="true"} 2' in reg.prometheus_text()
    with pytest.raises(ValueError):
        MetricsRegistry(max_label_sets=0)


def test_label_cap_env_default(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_OBS_MAX_LABEL_SETS", "2")
    reg = MetricsRegistry()
    assert reg.max_label_sets == 2
    reg.gauge("g", labels={"a": "1"})
    reg.gauge("g", labels={"a": "2"})
    assert reg.gauge("g", labels={"a": "3"}).labels == \
        MetricsRegistry.OVERFLOW_LABELS


def test_sharding_mesh_collector_snapshot():
    """The `sharding.<name>` collector exposes mesh shape and per-param
    shard fractions through a plain registry snapshot."""
    import paddle_tpu.sharding as shardlib

    reg = MetricsRegistry()
    mesh = shardlib.MeshConfig(tp=8).build()
    key = shardlib.register_mesh_collector(
        "unit", mesh, {"w": shardlib.spec(None, "tp")}, registry=reg)
    assert key == "sharding.unit"
    snap = reg.snapshot()["collectors"]["sharding.unit"]
    assert snap["mesh_axes"] == {"dp": 1, "fsdp": 1, "tp": 8}
    assert snap["param_shard_fractions"]["w"] == 0.125
    assert snap["params_sharded"] == 1
    reg.unregister_collector(key)
