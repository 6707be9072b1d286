"""Runtime sanitizer (paddle_tpu/analysis/runtime_san.py + tools/
tpu_san.py): per-detector bad/good pairs (forced retrace with the
signature delta, a deliberate host sync inside a hot region, use-after-
donate with donation-site blame, injected NaN with first-leaf blame),
the off-by-default zero-overhead guard, baseline-ratchet determinism,
and the CLI exit-code contract (0 clean / 1 new / 2 usage). The deep
end-to-end dogfood (every serving/decode/router fault phase with the
sanitizer live asserting zero findings) runs in
tools/serving_fault_injector.py via test_serving_fault_injection."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from paddle_tpu.analysis import runtime_san

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(REPO, "tools", "tpu_san.py")
BASELINE = os.path.join(REPO, ".tpu_san_baseline.json")


@pytest.fixture
def san():
    """Enable the sanitizer for one test, restore afterwards (interposers
    uninstalled, findings cleared) — never leak the numpy patch into the
    rest of the suite."""
    was = runtime_san.enabled()
    runtime_san.enable()
    runtime_san.reset()
    yield runtime_san
    runtime_san.reset()
    if not was:
        runtime_san.disable()


@pytest.fixture(scope="module")
def engine():
    """One tiny donating train engine shared by the detector tests (the
    XLA compile is the expensive part; probes read the enable flag per
    call, so per-test enabling composes with a shared engine)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed.engine import parallelize

    paddle.seed(0)
    model = nn.Linear(8, 4)
    opt = optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
    eng = parallelize(model, opt,
                      loss_fn=lambda m, x, y: ((m(x) - y) ** 2).mean())
    rng = np.random.RandomState(0)
    # batch dim divisible by the conftest's 8-virtual-device mesh
    x = paddle.to_tensor(rng.rand(8, 8).astype(np.float32))
    y = paddle.to_tensor(rng.rand(8, 4).astype(np.float32))
    eng.train_batch(x, y)     # cold compile outside any test's budget
    return eng, x, y


def _tensors(*arrays):
    import paddle_tpu as paddle

    return [paddle.to_tensor(a) for a in arrays]


# ---------------------------------------------------------------------------
# off by default: zero overhead, no patches, null probes
# ---------------------------------------------------------------------------

def test_off_by_default_zero_overhead():
    assert not runtime_san.enabled()
    # null singleton, not a fresh context manager per call
    assert runtime_san.hot_region("a") is runtime_san.hot_region("b")
    assert runtime_san.allow_host_sync() is runtime_san.hot_region("c")
    # numpy is NOT patched while off
    assert runtime_san._np_orig == {}
    before = dict(runtime_san.registry().counters)
    runtime_san.note_trace("s", "k", ("sig",))
    runtime_san.check_use(np.ones(2))
    runtime_san.check_finite("s", [("x", np.ones(2))])
    runtime_san.note_donation("s", [np.ones(2)])
    assert runtime_san.registry().counters == before
    assert runtime_san.counts_by_key() == {}


def test_enable_installs_and_disable_restores(san):
    orig = san._np_orig["asarray"]
    assert np.asarray is not orig          # patched wrapper in place
    san.disable()
    assert np.asarray is orig              # restored bit-identical
    assert san._np_orig == {}
    san.enable()                           # fixture teardown expects on


# ---------------------------------------------------------------------------
# retrace sentinel
# ---------------------------------------------------------------------------

def test_retrace_duplicate_signature_always_flags(san):
    san.note_trace("aot.scratch", "fp", ("(2, 8)/float32",))
    assert san.counts_by_key() == {}
    san.note_trace("aot.scratch", "fp", ("(2, 8)/float32",))
    assert san.counts_by_key() == {"aot.scratch::retrace": 1}
    [f] = san.findings()
    assert "compile cache" in f.message


def test_retrace_new_signature_only_after_warm(san):
    san.note_trace("engine.scratch", "e1", ("(2, 8)/float32",))
    san.note_trace("engine.scratch", "e1", ("(4, 8)/float32",))
    assert san.counts_by_key() == {}       # warmup: new shapes are free
    san.mark_warm()
    san.note_trace("engine.scratch", "e1", ("(6, 8)/float32",))
    assert san.counts_by_key() == {"engine.scratch::retrace": 1}
    [f] = san.findings()
    assert "'(4, 8)/float32' -> '(6, 8)/float32'" in f.message  # the delta


def test_retrace_per_call_probe_treats_repeats_as_cache_hits(san):
    for _ in range(3):
        san.note_trace("aot.layer_call", "L", ("(2, 8)/float32",),
                       per_call=True)
    assert san.counts_by_key() == {}
    san.mark_warm()
    for _ in range(3):                     # warm cache hits stay free
        san.note_trace("aot.layer_call", "L", ("(2, 8)/float32",),
                       per_call=True)
    assert san.counts_by_key() == {}
    san.note_trace("aot.layer_call", "L", ("(3, 8)/float32",),
                   per_call=True)
    assert san.counts_by_key() == {"aot.layer_call::retrace": 1}


def test_retrace_sharding_delta_blamed_as_placement_change(san):
    """PR-12 satellite: a recompile forced by a mesh/spec change is
    named a sharding-signature change, not reported as a shape delta."""
    from paddle_tpu.sharding import cpu_mesh, spec

    sig = ("(2, 8)/float32",)
    san.note_trace("aot.layer_call", "L",
                   (sig, san.sharding_signature(None)), per_call=True)
    san.mark_warm()
    mesh = cpu_mesh(tp=8)
    san.note_trace(
        "aot.layer_call", "L",
        (sig, san.sharding_signature(mesh, {"w": spec("tp")})),
        per_call=True)
    [f] = san.findings()
    assert "sharding signature changed (mesh/spec)" in f.message
    assert "tp=8" in f.message and "'w'" not in f.message  # readable form
    assert "leaf" not in f.message       # NOT an anonymous leaf diff
    # mixed delta: shape AND sharding changed -> both named
    san.reset()
    san.note_trace("engine.step", "e",
                   (("(2, 8)/float32",), san.sharding_signature(None)))
    san.mark_warm()
    san.note_trace(
        "engine.step", "e",
        (("(4, 8)/float32",), san.sharding_signature(mesh)))
    [f] = san.findings()
    assert "sharding signature changed" in f.message
    assert "'(2, 8)/float32' -> '(4, 8)/float32'" in f.message


def test_sharding_signature_stable_and_bounded(san):
    from paddle_tpu.sharding import cpu_mesh, spec

    mesh = cpu_mesh(tp=8)
    a = san.sharding_signature(mesh, {"w": spec("tp"), "b": spec()})
    b = san.sharding_signature(mesh, {"b": spec(), "w": spec("tp")})
    assert a == b and a.startswith("sharding:")      # order-insensitive
    assert san.sharding_signature(None) == "sharding:none"
    # giant spec tables stay hashable and bounded (digest tail)
    many = {f"p{i}": spec("tp") for i in range(200)}
    assert len(san.sharding_signature(mesh, many)) < 120


def test_mark_warm_does_not_cover_future_entrypoints(san):
    san.note_trace("aot.batched", "old-model", (1,))
    san.mark_warm()
    # a model loaded AFTER warmup (hot-swap, replica restart) compiles
    # cold without findings
    san.note_trace("aot.batched", "new-model", (1,))
    assert san.counts_by_key() == {}


def test_engine_forced_bucket_retrace_has_correct_site_key(san, engine):
    """The acceptance-criterion probe: steady state, mark warm, then a
    new batch shape — caught at the engine.step site with the delta."""
    eng, x, y = engine
    eng.train_batch(x, y)
    assert san.counts_by_key() == {}       # steady state is clean
    san.mark_warm()
    rng = np.random.RandomState(1)
    x2, y2 = _tensors(rng.rand(16, 8).astype(np.float32),
                      rng.rand(16, 4).astype(np.float32))
    eng.train_batch(x2, y2)
    assert "engine.step::retrace" in san.counts_by_key()
    f = [f for f in san.findings() if f.detector == "retrace"][0]
    assert "(8, 8)" in f.message and "(16, 8)" in f.message


# ---------------------------------------------------------------------------
# host-sync detector
# ---------------------------------------------------------------------------

def test_hot_region_catches_item_and_asarray(san):
    import jax.numpy as jnp
    import paddle_tpu as paddle

    arr = jnp.ones((2, 2))
    np.asarray(arr)                        # outside any region: free
    assert san.counts_by_key() == {}
    with san.hot_region("scratch.dispatch"):
        paddle.Tensor(arr).item(0)         # deliberate .item() mid-region
    assert san.counts_by_key() == {"scratch.dispatch::host-sync": 1}
    [f] = san.findings()
    assert "scratch.dispatch" in f.message
    # plain numpy input never flags (no device array involved)
    with san.hot_region("scratch.dispatch"):
        np.asarray([1.0, 2.0])
    assert sum(san.counts_by_key().values()) == 1


def test_allow_host_sync_escape_and_nesting(san):
    import jax.numpy as jnp

    arr = jnp.ones(3)
    with san.hot_region("scratch.dispatch"):
        with san.allow_host_sync("result fetch"):
            np.asarray(arr)                # sanctioned
        with san.hot_region("scratch.inner"):
            np.asarray(arr)                # inner region blames itself
    assert san.counts_by_key() == {"scratch.inner::host-sync": 1}


def test_device_get_probe(san):
    import jax
    import jax.numpy as jnp

    arr = jnp.ones(3)
    with san.hot_region("scratch.dispatch"):
        jax.device_get(arr)
    assert san.counts_by_key() == {"scratch.dispatch::host-sync": 1}


def test_serving_execute_region_catches_planted_sync(san):
    """A request fn that syncs a device array mid-execution is blamed on
    the pool's serving.execute hot region (stub predictor: no XLA)."""
    import jax.numpy as jnp
    from paddle_tpu.inference import Predictor, ServingPool

    class _Out:
        def __init__(self, a):
            self._a = a

        def numpy(self):
            return self._a

    class _StubLayer:
        input_spec = [{"shape": [2], "dtype": "float32"}]
        num_outputs = 1

        def __call__(self, x):
            return _Out(np.asarray(x) * 2.0)

    dev = jnp.ones(())
    pool = ServingPool(predictor=Predictor(None, _shared_layer=_StubLayer()),
                       size=1, max_queue_depth=8, default_timeout=10.0)
    try:
        pool.infer([np.ones(2, np.float32)])          # good twin: clean
        assert san.counts_by_key() == {}

        def bad(pred):
            float(np.asarray(dev))                    # planted sync
            return pred.run([np.ones(2, np.float32)])

        pool.submit(bad, timeout=10.0).result()
    finally:
        pool.shutdown(drain_timeout=5.0)
    assert san.counts_by_key() == {"serving.execute::host-sync": 1}


# ---------------------------------------------------------------------------
# donation guard
# ---------------------------------------------------------------------------

def test_use_after_donate_names_the_donation_site(san, engine):
    eng, x, y = engine
    eng.train_batch(x, y)
    stale = dict(eng.param_vals)
    eng.train_batch(x, y)                  # donates the `stale` buffers
    w = stale["weight"]
    with pytest.raises(san.DonatedBufferError, match="engine.dispatch"):
        san.check_use(w, "unit")
    with pytest.raises(san.DonatedBufferError, match="engine.dispatch"):
        np.asarray(w)                      # the numpy patch catches it too
    with pytest.raises(san.DonatedBufferError):
        eng.train_batch(x, w)              # and the batch-placement choke
    assert set(san.counts_by_key()) == {"engine.dispatch::donation"}
    # good twin: the LIVE engine state is always safe to read
    san.reset()
    np.asarray(eng.param_vals["weight"])
    assert san.counts_by_key() == {}


def test_donation_guard_off_when_disabled(engine):
    eng, x, y = engine
    assert not runtime_san.enabled()
    eng.train_batch(x, y)
    stale = dict(eng.param_vals)
    eng.train_batch(x, y)
    # sanitizer off: reading the stale buffer either succeeds silently
    # (backends that skip real donation) or raises jax's ANONYMOUS
    # deletion error — never the typed, site-blaming DonatedBufferError,
    # and never a recorded finding
    try:
        np.asarray(stale["weight"])
    except RuntimeError as e:
        assert not isinstance(e, runtime_san.DonatedBufferError)
        assert "deleted" in str(e)
    assert runtime_san.counts_by_key() == {}


# ---------------------------------------------------------------------------
# non-finite guard
# ---------------------------------------------------------------------------

def test_nonfinite_blames_first_offending_leaf(san):
    import jax.numpy as jnp

    good = jnp.ones((2, 2))
    bad = jnp.asarray([[1.0, float("nan")]])
    with pytest.raises(san.NonFiniteError) as ei:
        san.check_finite("scratch.step", [
            ("loss", good[0, 0]), ("param/linear.weight", bad),
            ("param/linear.bias", bad)])   # first offender wins blame
    assert ei.value.path == "param/linear.weight"
    assert ei.value.site == "scratch.step"
    assert san.counts_by_key() == {"scratch.step::non-finite": 1}
    # good twin: all-finite sweep is silent; int leaves are skipped
    san.reset()
    san.check_finite("scratch.step",
                     [("a", good), ("ids", jnp.zeros(3, jnp.int32))])
    assert san.counts_by_key() == {}


def test_nonfinite_catches_bfloat16(san):
    """bf16 is NOT under np.floating (ml_dtypes) — the sweep must still
    see it: bf16 params and the decode engine's bf16 KV pool are the
    prime NaN carriers."""
    import jax.numpy as jnp

    bad = jnp.full((2, 2), float("nan"), dtype=jnp.bfloat16)
    with pytest.raises(san.NonFiniteError) as ei:
        san.check_finite("scratch.step", [("kv_pool/layer0/t0", bad)])
    assert ei.value.path == "kv_pool/layer0/t0"
    san.reset()
    san.check_finite("scratch.step",
                     [("ok", jnp.ones((2, 2), jnp.bfloat16))])
    assert san.counts_by_key() == {}


def test_engine_injected_nan_blamed_as_loss(san, engine):
    eng, x, y = engine
    bad_y = _tensors(np.full((8, 4), np.nan, np.float32))[0]
    with pytest.raises(san.NonFiniteError) as ei:
        eng.train_batch(x, bad_y)
    assert ei.value.path == "loss"
    assert "engine.step::non-finite" in san.counts_by_key()


def test_nonfinite_detector_knob(san, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_SAN_NONFINITE", "0")
    assert not san.nonfinite_enabled()
    san.check_finite("scratch.step", [("x", np.asarray([np.nan]))])
    assert san.counts_by_key() == {}       # detector off: silent
    monkeypatch.setenv("PADDLE_TPU_SAN_NONFINITE", "1")
    assert san.nonfinite_enabled()


# ---------------------------------------------------------------------------
# obs export
# ---------------------------------------------------------------------------

def test_san_counters_ride_the_obs_registry(san):
    from paddle_tpu.obs.metrics import registry

    with san.hot_region("scratch.obs"):
        pass
    snap = registry().snapshot()
    col = snap["collectors"][san.OBS_COLLECTOR]
    assert col["enabled"] == 1
    assert col["hot_regions"] >= 1
    assert {"retrace", "host_sync", "donation", "non_finite"} <= set(col)
    san.disable()
    assert san.OBS_COLLECTOR not in registry().snapshot()["collectors"]
    san.enable()                           # fixture teardown expects on


# ---------------------------------------------------------------------------
# baseline ratchet
# ---------------------------------------------------------------------------

def test_baseline_roundtrip_and_determinism(tmp_path):
    counts = {"engine.step::retrace": 2, "serving.execute::host-sync": 1}
    p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
    runtime_san.write_baseline(str(p1), counts)
    runtime_san.write_baseline(str(p2), dict(reversed(list(counts.items()))))
    assert p1.read_bytes() == p2.read_bytes()      # sorted keys
    assert runtime_san.load_baseline(str(p1)) == counts
    with pytest.raises(ValueError):
        (tmp_path / "bad.json").write_text('{"no": "counts"}')
        runtime_san.load_baseline(str(tmp_path / "bad.json"))


def test_new_counts_ratchet_semantics():
    base = {"a::retrace": 2, "b::host-sync": 1}
    cur = {"a::retrace": 2, "b::host-sync": 3, "c::donation": 1}
    fresh = runtime_san.new_counts(cur, base)
    assert fresh == {"b::host-sync": (3, 1), "c::donation": (1, 0)}
    assert runtime_san.new_counts(base, base) == {}


def test_checked_in_baseline_is_zero_findings():
    """The framework's runtime baseline is EMPTY — tpu-san holds the
    whole stack at zero findings (the injector proves it end-to-end)."""
    with open(BASELINE) as f:
        data = json.load(f)
    assert data["tool"] == "tpu_san"
    assert data["counts"] == {}


# ---------------------------------------------------------------------------
# CLI exit-code contract
# ---------------------------------------------------------------------------

def _load_cli():
    spec = importlib.util.spec_from_file_location("_tpu_san_cli", CLI)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def cli(san, monkeypatch):
    """The CLI module with its smoke workloads stubbed out — exit-code
    semantics are testable without paying an engine compile per case."""
    mod = _load_cli()
    monkeypatch.setattr(mod, "_smoke_engine", lambda: None)
    monkeypatch.setattr(mod, "_smoke_serving", lambda: None)
    return mod


def test_cli_clean_run_exits_0(cli, tmp_path):
    b = tmp_path / "base.json"
    runtime_san.write_baseline(str(b), {})
    assert cli.main(["--smoke", "engine", "--baseline", str(b)]) == 0


def test_cli_new_finding_exits_1(cli, tmp_path, monkeypatch, capsys):
    def planted():
        runtime_san.registry().record("host-sync", "scratch.site",
                                      "planted finding")
    monkeypatch.setattr(cli, "_smoke_engine", planted)
    b = tmp_path / "base.json"
    runtime_san.write_baseline(str(b), {})
    assert cli.main(["--smoke", "engine", "--baseline", str(b)]) == 1
    assert "scratch.site::host-sync" in capsys.readouterr().out
    # the same finding baselined -> clean
    runtime_san.write_baseline(str(b), {"scratch.site::host-sync": 1})
    assert cli.main(["--smoke", "engine", "--baseline", str(b)]) == 0


def test_cli_usage_errors_exit_2(cli, tmp_path):
    assert cli.main(["--smoke", "nonsense"]) == 2
    missing = tmp_path / "missing.json"
    assert cli.main(["--smoke", "engine",
                     "--baseline", str(missing)]) == 2
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert cli.main(["--smoke", "engine", "--baseline", str(bad)]) == 2


def test_cli_write_baseline(cli, tmp_path, monkeypatch):
    def planted():
        runtime_san.registry().record("retrace", "scratch.site", "x")
    monkeypatch.setattr(cli, "_smoke_engine", planted)
    b = tmp_path / "base.json"
    assert cli.main(["--smoke", "engine", "--write-baseline",
                     "--baseline", str(b)]) == 0
    assert runtime_san.load_baseline(str(b)) == {
        "scratch.site::retrace": 1}


# ---------------------------------------------------------------------------
# dogfood: the framework runs clean via the real CLI
# ---------------------------------------------------------------------------

def test_framework_serving_smoke_clean_in_process(san):
    """The in-process half of the exit-0 contract: the real serving
    smoke (no XLA compile) against the checked-in baseline, with the
    vacuity guard that the probes actually ran."""
    mod = _load_cli()
    counts, report = mod.run_smokes(["serving"])
    base = runtime_san.load_baseline(BASELINE)
    assert runtime_san.new_counts(counts, base) == {}
    assert report["counters"]["hot_regions"] > 0


def test_framework_runs_clean_via_cli(tmp_path):
    """The CI-shaped invocation: the REAL smoke workloads (engine hot
    path + serving pool, every detector live) against the checked-in
    zero-findings baseline, in a subprocess, exit 0. This single run
    proves the exit-code contract on the real path and that the
    framework's hot paths are retrace-free, sync-free, donation-clean
    and finite."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    env.pop("PADDLE_TPU_SAN", None)        # the CLI enables it itself
    r = subprocess.run([sys.executable, CLI], capture_output=True,
                       text=True, env=env, timeout=180)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 new finding(s)" in r.stdout
