"""Dynamic request batching (paddle_tpu/inference/batching.py +
jit/aot.py + the ServingPool integration): bucketed batch formation,
bit-equality with unbatched execution across buckets and ragged tails,
deadline-pressure flush, deterministic dispatch counting, split-on-failure
isolation, stats conservation, and the persistent compile cache
(including a warm-process subprocess smoke proving zero compiles).

Cost control: ONE tiny exported model per module (module-scoped fixture),
bucket executables shared across tests via the layer + an on-module
compile-cache dir, and the deterministic gate-blocker trick instead of
sleeps wherever batch composition must be exact.
"""
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.inference import (
    BatchConfig, Config, DeadlineExceeded, DynamicBatcher, RequestFailed,
    ServingPool, create_predictor,
)
from paddle_tpu.inference.serving import RetryPolicy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUCKETS = (1, 2, 4, 8)


@pytest.fixture(scope="module")
def exported(tmp_path_factory):
    """One tiny exported model + a module-scoped persistent compile cache
    (so bucket executables compile at most once for the whole module and
    $HOME is never touched)."""
    root = tmp_path_factory.mktemp("batching")
    old = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / "compile-cache")
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(6, 8), nn.ReLU(), nn.Linear(8, 3))
    model.eval()
    path = str(root / "infer")
    paddle.jit.save(model, path, input_spec=[
        paddle.to_tensor(np.zeros((1, 6), np.float32))])
    rng = np.random.RandomState(3)
    feeds = [rng.rand(1, 6).astype(np.float32) for _ in range(16)]
    ref = create_predictor(Config(path))
    want = [ref.run([f])[0] for f in feeds]
    yield {"path": path, "feeds": feeds, "want": want}
    if old is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = old


def _pool(exported, **kw):
    kw.setdefault("default_timeout", 30.0)
    kw.setdefault("batching", BatchConfig(buckets=BUCKETS, max_wait_ms=50.0))
    return ServingPool(predictor=create_predictor(Config(exported["path"])),
                       size=kw.pop("size", 1), **kw)


def _submit_wave(pool, exported, indices, timeout=30.0):
    """Admit batchable (feeds-style) requests for the given input
    indices, returning their future-like handles."""
    futs = []
    for i in indices:
        feeds = pool._batcher.validate([exported["feeds"][i]])
        futs.append(pool._admit(
            lambda p, f=feeds: p.run(f), timeout, feeds=feeds))
    return futs


def _gated_wave(pool, exported, indices, timeout=30.0):
    """Deterministic batch composition: occupy the single worker with a
    gate-blocked request, queue the wave, release the gate — the worker
    then forms batches from exactly that wave."""
    gate = threading.Event()
    blocker = pool.submit(lambda p: (gate.wait(10), "gate")[1])
    time.sleep(0.05)  # the (sole) worker is now parked on the gate
    futs = _submit_wave(pool, exported, indices, timeout=timeout)
    gate.set()
    assert blocker.result() == "gate"
    return futs


# ---------------------------------------------------------------------------
# bit-equality across buckets and ragged tails
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 3, 5, 8, 11])
def test_batched_outputs_bit_identical_across_buckets(exported, n):
    """Every wave size (exact bucket fits AND ragged tails that need
    padding or a second dispatch) must produce outputs bit-identical to
    sequential unbatched execution."""
    with _pool(exported) as pool:
        futs = _gated_wave(pool, exported, range(n))
        for i, f in enumerate(futs):
            out, = f.result()
            assert out.shape == exported["want"][i].shape
            assert (out == exported["want"][i]).all(), \
                f"wave n={n}, request {i}: batched output differs bitwise"
        b = pool.stats()["batch"]
        # bucket accounting: every dispatch is fully explained by real
        # requests + padding
        assert sum(k * v for k, v in b["executed_by_bucket"].items()) \
            == b["requests"] + b["padded_examples"]


def test_single_request_via_bucket1_matches_plain_run(exported):
    """A lone request (no batchmates arrive) rides the bucket-1 AOT
    executable and still matches the plain Predictor.run bitwise."""
    cfg = BatchConfig(buckets=BUCKETS, max_wait_ms=0.0)
    with _pool(exported, batching=cfg) as pool:
        out, = pool.infer([exported["feeds"][0]])
        assert (out == exported["want"][0]).all()
        assert pool.stats()["batch"]["executed_by_bucket"] == {1: 1}


# ---------------------------------------------------------------------------
# dispatch counting + stats
# ---------------------------------------------------------------------------

def test_dispatch_count_at_most_ceil_n_over_bucket(exported):
    """The serving analog of engine.stats dispatch assertions: 8
    concurrent same-shape requests released at once take <= ceil(8/8) = 1
    bucketed dispatch (deterministic — counts, not wall-clock)."""
    with _pool(exported) as pool:
        futs = _gated_wave(pool, exported, range(8))
        for f in futs:
            f.result()
        b = pool.stats()["batch"]
        assert b["executed_by_bucket"] == {8: 1}, b
        assert b["formed"] == 1 and b["requests"] == 8
        assert b["padded_examples"] == 0
        assert b["occupancy"] == 1.0
        assert b["flushes"]["full"] == 1


def test_occupancy_queue_wait_and_conservation(exported):
    """Ragged wave: occupancy/padding/queue-wait counters are coherent
    and the pool-level conservation law still balances."""
    with _pool(exported) as pool:
        futs = _gated_wave(pool, exported, range(5))
        for f in futs:
            f.result()
        s = pool.stats()
        b = s["batch"]
        # 5 requests over buckets (1,2,4,8): one 4-batch + one 1-batch
        # (or a padded 8 if the worker got them all at once) — whatever
        # the timing, the books must balance exactly:
        assert b["requests"] == 5
        assert sum(k * v for k, v in b["executed_by_bucket"].items()) \
            == 5 + b["padded_examples"]
        assert 0.0 < b["occupancy"] <= 1.0
        assert b["queue_wait_ms_total"] >= b["queue_wait_ms_max"] >= 0.0
        assert b["execute_ms_total"] > 0.0
        # global conservation (blocker + 5 batchables, all terminal)
        assert s["admitted"] == 6
        assert s["admitted"] == s["completed"] + s["failed"] \
            + s["timed_out"] + s["cancelled"]


# ---------------------------------------------------------------------------
# deadline pressure
# ---------------------------------------------------------------------------

def test_deadline_pressure_flushes_partial_batch_early(exported):
    """A partial batch under deadline pressure must dispatch well before
    max_wait_ms: requests with ~300ms budget against a 5s batching window
    complete instead of expiring."""
    cfg = BatchConfig(buckets=(8,), max_wait_ms=5000.0,
                      deadline_margin_ms=150.0)
    with _pool(exported, batching=cfg) as pool:
        t0 = time.monotonic()
        futs = _submit_wave(pool, exported, range(2), timeout=0.3)
        outs = [f.result() for f in futs]
        wall = time.monotonic() - t0
        for i, (out,) in enumerate(outs):
            assert (out == exported["want"][i]).all()
        assert wall < 2.0, f"partial batch waited {wall:.2f}s — the " \
            f"deadline-margin flush did not fire"
        b = pool.stats()["batch"]
        assert b["flushes"]["deadline"] >= 1, b["flushes"]
        # bucket (8,) forces padding for the 2-request batch
        assert b["padded_examples"] >= 6


# ---------------------------------------------------------------------------
# failure isolation: split retry
# ---------------------------------------------------------------------------

def test_poison_request_is_the_only_failure_in_its_batch(exported):
    """One deterministically-failing request inside a 4-batch: the batch
    splits, the poison request alone surfaces RequestFailed (ValueError
    cause), batchmates complete bit-correct, member health untouched."""
    poison = {"id": None}

    def hook(slot, req, pred):
        if req.id == poison["id"]:
            raise ValueError("poison request")

    with _pool(exported, fault_hook=hook) as pool:
        gate = threading.Event()
        blocker = pool.submit(lambda p: (gate.wait(10), "g")[1])
        time.sleep(0.05)
        futs = _submit_wave(pool, exported, range(4))
        poison["id"] = futs[2].id
        gate.set()
        blocker.result()
        for i, f in enumerate(futs):
            if i == 2:
                with pytest.raises(RequestFailed) as ei:
                    f.result()
                assert isinstance(ei.value.cause, ValueError)
            else:
                out, = f.result()
                assert (out == exported["want"][i]).all()
        s = pool.stats()
        assert s["batch"]["splits"] == 1
        assert s["batch"]["split_requests"] == 4
        # deterministic request error: no member penalty, no re-clone
        assert s["reclones"] == 0
        assert s["members"][0]["breaker"] == "closed"


def test_transient_batch_failure_splits_and_all_recover(exported):
    """A transient member fault failing a whole batch quarantines the
    member (re-clone + breaker charge) and re-runs every request as a
    single — nobody is lost."""
    calls = {"n": 0}

    def hook(slot, req, pred):
        if req.feeds is not None and not req.no_batch and req.attempts == 1:
            calls["n"] += 1
            raise RuntimeError("transient member fault under a batch")

    with _pool(exported, fault_hook=hook,
               retry=RetryPolicy(max_retries=2, base_delay=0.005,
                                 max_delay=0.02)) as pool:
        futs = _gated_wave(pool, exported, range(4))
        for i, f in enumerate(futs):
            out, = f.result()
            assert (out == exported["want"][i]).all()
        s = pool.stats()
        assert s["batch"]["splits"] >= 1
        assert s["reclones"] >= 1          # quarantined + re-cloned
        assert s["completed"] == 5         # gate + 4 requests
        assert s["admitted"] == s["completed"] + s["failed"] \
            + s["timed_out"] + s["cancelled"]


# ---------------------------------------------------------------------------
# warmup + compile accounting
# ---------------------------------------------------------------------------

def test_warmup_precompiles_then_traffic_compiles_nothing(exported):
    """pool.warmup() builds every bucket executable up front; traffic
    afterwards never compiles (mem hits only)."""
    with _pool(exported, size=2) as pool:
        assert pool.warmup() == sorted(BUCKETS)
        comp = pool.stats()["batch"]["compile"]
        base = comp["compiles"] + comp["disk_hits"]
        assert sorted(comp["buckets"]) == sorted(BUCKETS)
        futs = _gated_wave(pool, exported, range(8))
        for f in futs:
            f.result()
        comp = pool.stats()["batch"]["compile"]
        assert comp["compiles"] + comp["disk_hits"] == base, \
            "traffic caused executable (re)builds after warmup"


def test_concurrent_cold_calls_build_each_bucket_once(exported):
    """Racing workers hitting an unwarmed bucket must coordinate on one
    build (losers wait on the builder) — never pay a duplicate compile
    or corrupt the aot counters."""
    layer = paddle.jit.load(exported["path"])
    fns, errs = [], []

    def cold():
        try:
            fns.append(layer.batched_call(4))
        except Exception as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    threads = [threading.Thread(target=cold) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    assert all(f is fns[0] for f in fns)
    st = layer.aot_stats()
    assert st["compiles"] + st["disk_hits"] == 1, st
    assert st["mem_hits"] == 5, st


def test_scatter_results_are_standalone_copies(exported):
    """Per-request results must not be views pinning the bucket-sized
    stacked output buffer."""
    with _pool(exported) as pool:
        futs = _gated_wave(pool, exported, range(3))
        for f in futs:
            out, = f.result()
            assert out.base is None, "result is a view into the batch"


def test_warmup_requires_batching(exported):
    pool = ServingPool(predictor=create_predictor(Config(exported["path"])),
                       size=1)
    try:
        with pytest.raises(RuntimeError, match="batching"):
            pool.warmup()
    finally:
        pool.shutdown(1)


def test_reclone_shares_bucket_executables(exported):
    """A quarantine re-clone must not rebuild executables: the bucket
    cache lives on the shared exported layer."""
    flaky = {"armed": True}

    def hook(slot, req, pred):
        if flaky["armed"]:
            flaky["armed"] = False
            raise RuntimeError("one transient fault")

    with _pool(exported, fault_hook=hook,
               retry=RetryPolicy(max_retries=2, base_delay=0.005,
                                 max_delay=0.02)) as pool:
        pool.warmup()
        comp0 = pool.stats()["batch"]["compile"]
        out, = pool.infer([exported["feeds"][0]])
        assert (out == exported["want"][0]).all()
        s = pool.stats()
        assert s["reclones"] >= 1
        comp1 = s["batch"]["compile"]
        assert comp1["compiles"] == comp0["compiles"]
        assert comp1["disk_hits"] == comp0["disk_hits"]


# ---------------------------------------------------------------------------
# persistent compile cache
# ---------------------------------------------------------------------------

def test_compile_cache_bounds_and_atomics(tmp_path):
    """CompileCache unit: keep-last-K eviction (LRU — a get refreshes),
    atomic write leaves no temp droppings. (Where the default cache lives
    is tests/test_chip_smoke.py's resolver test.)"""
    from paddle_tpu.jit.aot import CompileCache

    cache = CompileCache(root=str(tmp_path / "bounded"), keep=3)
    keys = [CompileCache.key("entry", i) for i in range(5)]
    for i, k in enumerate(keys):
        cache.put(k, b"blob-%d" % i)
        if i == 2:
            time.sleep(0.01)
            assert cache.get(keys[0]) is not None  # refresh entry 0's LRU
        time.sleep(0.01)
    live = cache.entries()
    assert len(live) == 3
    assert keys[0] in live          # refreshed entry survived
    assert keys[1] not in live      # oldest unrefreshed entries evicted
    assert cache.get(keys[4]) == b"blob-4"
    assert cache.stats()["evictions"] == 2
    # atomic-write protocol leaves only committed entries behind
    assert all(n.endswith(".aotexec")
               for n in os.listdir(str(tmp_path / "bounded")))
    with pytest.raises(ValueError):
        CompileCache(root=str(tmp_path), keep=0)


_WARM_SCRIPT = r"""
import json, os, sys
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_COMPILATION_CACHE_DIR"] = {cache!r}
import paddle_tpu as paddle
layer = paddle.jit.load({path!r})
layer.warmup_buckets((1, 2))
print("AOT_STATS=" + json.dumps(layer.aot_stats()))
"""


@pytest.mark.slow
def test_persistent_cache_warm_process_compiles_zero(exported):
    """Cross-process proof of the acceptance criterion: a fresh process
    warming the same buckets compiles ZERO executables — every bucket is
    a persistent-cache hit (subprocess smoke; slow: two interpreter +
    jax startups)."""
    cache = os.environ["JAX_COMPILATION_CACHE_DIR"]

    def run():
        script = _WARM_SCRIPT.format(repo=REPO, cache=cache,
                                     path=exported["path"])
        r = subprocess.run([sys.executable, "-c", script], cwd=REPO,
                           capture_output=True, text=True, timeout=240)
        assert r.returncode == 0, f"\n{r.stdout}\n{r.stderr}"
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("AOT_STATS=")][0]
        import json
        return json.loads(line[len("AOT_STATS="):])

    first = run()                      # cold-or-warm: populates the cache
    second = run()                     # MUST be fully warm
    assert first["compiles"] + first["disk_hits"] == 2
    assert second["compiles"] == 0, \
        f"warm process recompiled bucket executables: {second}"
    assert second["disk_hits"] == 2


# ---------------------------------------------------------------------------
# admission validation + DynamicBatcher construction
# ---------------------------------------------------------------------------

def test_wrong_shape_feed_rejected_at_admission(exported):
    with _pool(exported) as pool:
        with pytest.raises(ValueError, match="input_spec"):
            pool.infer([np.zeros((2, 6), np.float32)])
        with pytest.raises(ValueError, match="1 input"):
            pool.infer([np.zeros((1, 6), np.float32)] * 2)
        s = pool.stats()
        assert s["admitted"] == 0  # rejected before the queue


def test_batcher_requires_exported_layer():
    class NotExported:
        pass

    with pytest.raises(TypeError, match="batched_call"):
        DynamicBatcher(NotExported())


def test_batch_config_validation():
    with pytest.raises(ValueError):
        BatchConfig(buckets=())
    with pytest.raises(ValueError):
        BatchConfig(buckets=(0, 2))
    with pytest.raises(ValueError):
        BatchConfig(max_wait_ms=-1)
    cfg = BatchConfig(buckets=(8, 2, 4, 2))
    assert cfg.buckets == (2, 4, 8)   # sorted, deduped


# ---------------------------------------------------------------------------
# lock discipline under the race checker (paddle_tpu.analysis.lockcheck)
# ---------------------------------------------------------------------------

def test_batched_pool_lock_discipline_clean(exported, checker):
    """The batching hot path (gather under the pool cv -> one bucketed
    dispatch -> scatter) run with the lock-order checker ENABLED (the
    shared `checker` fixture from conftest): no acquisition-order cycles
    and no lock held across the serving.batch_dispatch / aot.* blocking
    regions. Constructing the pool after enable() is what instruments
    its named locks."""
    pool = _pool(exported, size=1)
    try:
        futs = _gated_wave(pool, exported, range(8))
        for i, f in enumerate(futs):
            out, = f.result()
            assert (out == exported["want"][i]).all()
    finally:
        pool.shutdown(5)
    rep = checker.assert_clean()
    observed = set(rep["locks"])
    assert {"serving.pool", "serving.batcher",
            "serving.request"} <= observed
