"""Continuous-batching decode engine (paddle_tpu/inference/decode):
block-pool allocator invariants, iteration-level scheduling (short
sequences stream out while long ones decode; late arrivals join the
running batch), per-token BIT-IDENTITY between batched and
single-sequence decode, typed admission/deadline/cancel semantics shared
with the serving runtime, compile-once-per-bucket via the persistent
compile cache (warm-start subprocess proof is `slow`-marked like PR 4's),
and the `cache_quant` precedence/typed-error satellite on the GPT model.

The model under test is a tiny LLaMA-style config (rope + GQA + swiglu +
rms_norm) chosen because its random init emits VARIED greedy tokens —
a degenerate repeated-token model would vacuously pass sequencing bugs.
"""
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (
    DeadlineExceeded, DecodeEngine, Overloaded, PoolClosed, ServingPool)
from paddle_tpu.inference.decode.block_pool import (
    BlockKVCache, OutOfBlocks, RESERVED_BLOCKS)
from paddle_tpu.models import (CacheQuantError, GenerationConfig, generate,
                               gpt)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(vocab_size=97, hidden_size=48, num_heads=4, num_kv_heads=2,
            num_layers=2, rope=True, swiglu=True, rms_norm=True,
            max_position_embeddings=64, tie_word_embeddings=False)


@pytest.fixture(scope="module", autouse=True)
def _shared_compile_cache(tmp_path_factory):
    """One on-disk compile cache for the whole module: the first engine
    compiles each bucket once, every later engine disk-loads it — the
    suite stays cheap AND the persistence path gets exercised."""
    d = str(tmp_path_factory.mktemp("decode-compile-cache"))
    old = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    yield d
    if old is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = old


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    m = gpt("gpt_tiny", **TINY)
    m.eval()
    return m


def _engine(model, **kw):
    kw.setdefault("max_length", 48)
    kw.setdefault("block_size", 8)
    kw.setdefault("decode_buckets", (1, 2, 4))
    kw.setdefault("prefill_buckets", (8,))
    kw.setdefault("default_timeout", 60.0)
    return DecodeEngine(model, **kw)


@pytest.fixture(scope="module")
def eng(model):
    """ONE shared engine for every test that only drives traffic
    through it (suite-budget trim: each DecodeEngine build pays
    functionalize + step-pool threads + per-bucket executable disk
    loads — consolidating the duplicate warmup cut this file's wall
    clock by ~a third). Tests that reconfigure, quantize, or shut the
    engine down still build their own; stats assertions on the shared
    engine are DELTAS."""
    e = _engine(model)
    yield e
    e.shutdown(drain_timeout=10.0)



def _leaked(st):
    """Blocks held beyond the prefix cache's deliberate pins (the cache
    RETAINS prompt blocks across sequences — that is the feature); a
    quiesced engine must hold nothing else."""
    return (st["blocks"]["allocated"]
            - st["prefix_cache"]["physical_blocks"])

def _prompt(seed, n=6):
    return np.random.RandomState(seed).randint(
        0, TINY["vocab_size"], (n,)).astype(np.int32)


def _ref_tokens(model, prompt, max_new):
    out = generate(model, prompt[None],
                   GenerationConfig(max_new_tokens=max_new,
                                    use_cache=True)).numpy()
    return list(out[0, len(prompt):])


# ---------------------------------------------------------------------------
# block pool allocator
# ---------------------------------------------------------------------------

def _tiny_pool(num_blocks=6, block_size=4):
    import jax.numpy as jnp

    spec = (((8,), jnp.float32, 2), ((8,), jnp.float32, 2))
    return BlockKVCache(num_blocks, block_size, [spec])


def test_block_pool_alloc_free_conservation():
    pool = _tiny_pool()
    a = pool.alloc(2, owner="a")
    b = pool.alloc(3, owner="b")
    assert len(set(a) | set(b)) == 5 and 0 not in a + b  # reserved block
    s = pool.stats()
    assert s["allocated"] + s["free"] + s["reserved"] == s["total"]
    pool.free(a)
    assert pool.free_owned("b") == 3
    s = pool.stats()
    assert s["allocated"] == 0 and s["allocs"] == 5 and s["frees"] == 5
    assert pool.free_owned("b") == 0  # idempotent


def test_block_pool_all_or_nothing_exhaustion():
    pool = _tiny_pool(num_blocks=4)   # 3 allocatable
    pool.alloc(2, owner="x")
    with pytest.raises(OutOfBlocks):
        pool.alloc(2, owner="y")      # only 1 free: must not partially grab
    s = pool.stats()
    assert s["free"] == 1 and s["failed_allocs"] == 1


def test_block_pool_double_free_raises():
    pool = _tiny_pool()
    blocks = pool.alloc(1, owner="x")
    pool.free(blocks)
    with pytest.raises(ValueError):
        pool.free(blocks)
    with pytest.raises(ValueError):
        pool.free([0])                # reserved id was never allocated


def test_block_pool_geometry():
    pool = _tiny_pool(num_blocks=6, block_size=4)
    assert pool.blocks_for(1) == 1 and pool.blocks_for(4) == 1
    assert pool.blocks_for(5) == 2
    assert pool.capacity_tokens == (6 - RESERVED_BLOCKS) * 4
    assert len(pool.tensors) == 1 and len(pool.tensors[0]) == 2
    assert pool.tensors[0][0].shape == (6, 4, 8)   # [N, bs, Hkv*D]


# ---------------------------------------------------------------------------
# engine: correctness + iteration-level scheduling
# ---------------------------------------------------------------------------

def test_single_sequence_matches_dense_generate(eng, model):
    """The paged, bucketed engine path must reproduce the dense
    `generate()` greedy tokens on a varied-output model (rope + GQA)."""
    p = _prompt(3)
    got = eng.generate(p, 10)
    assert got == _ref_tokens(model, p, 10)
    assert len(set(got)) > 3   # varied output: the test has teeth


def test_iteration_level_scheduling_and_bit_identity(eng):
    """The core continuous-batching claims, on one mixed workload:
    short sequences complete and stream out while a long one is still
    decoding; a late arrival joins the RUNNING batch (no drain wait) and
    also finishes first; and every sequence's tokens are bit-identical
    to running it alone through the same engine."""
    base = eng.stats()
    solo = {}
    for seed, n in ((1, 24), (2, 4), (4, 4)):
        solo[seed] = eng.generate(_prompt(seed), n)
    assert eng.stats()["active"] == 0

    long_s = eng.submit(_prompt(1), 24)
    short_s = eng.submit(_prompt(2), 4)
    assert short_s.result() == solo[2]
    assert not long_s.done(), \
        "short sequence should finish while the long one decodes"
    late_s = eng.submit(_prompt(4), 4)       # joins the running batch
    assert late_s.result() == solo[4]
    assert not long_s.done(), \
        "late arrival must not wait for the batch to drain"
    assert long_s.result() == solo[1]

    st = eng.stats()
    assert st["occupancy"] > 0.0
    assert _leaked(st) == 0                  # everything returned
    assert st["admitted"] - base["admitted"] == 6
    assert st["completed"] - base["completed"] == 6


def test_streaming_tokens_arrive_incrementally(eng):
    s = eng.submit(_prompt(5), 16)
    first = next(iter(s))
    assert s.status == "running"      # token before completion
    rest = s.result()
    assert rest[0] == first and len(rest) == 16
    assert s.tokens == rest


def test_deadline_typed_and_blocks_freed(eng):
    base = eng.stats()["timed_out"]
    # tight deadline: the shared engine is WARM (no compile/disk-load
    # stall to hide behind); 5ms < one prefill + a handful of decode
    # dispatches on ANY machine, so the 40-token ask must expire
    s = eng.submit(_prompt(6), 40, timeout=0.005)
    with pytest.raises(DeadlineExceeded):
        for _ in s:
            pass
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        st = eng.stats()
        if st["timed_out"] - base == 1 and _leaked(st) == 0:
            break
        time.sleep(0.01)
    st = eng.stats()
    assert st["timed_out"] - base == 1 and _leaked(st) == 0


def test_cancel_mid_generation_spares_batchmate(eng):
    base = eng.stats()["cancelled"]
    mate_ref = eng.generate(_prompt(8), 12)
    victim = eng.submit(_prompt(7), 30)
    mate = eng.submit(_prompt(8), 12)
    next(iter(victim))                 # it is definitely running
    victim.cancel()
    with pytest.raises(PoolClosed):
        victim.result()
    assert victim.status == "cancelled"
    assert mate.result() == mate_ref   # batchmate bit-unaffected
    st = eng.stats()
    assert st["cancelled"] - base == 1 and _leaked(st) == 0


def test_admission_overload_and_closed(model):
    with _engine(model, max_waiting=1, decode_buckets=(1,),
                 default_timeout=None) as eng:
        running = eng.submit(_prompt(9), 30)       # occupies the one slot
        next(iter(running))
        eng.submit(_prompt(10), 30)                # fills waiting queue
        with pytest.raises(Overloaded):
            eng.submit(_prompt(11), 4)
        with pytest.raises(DeadlineExceeded):      # dead on arrival
            eng.submit(_prompt(11), 4, timeout=-1.0)
    with pytest.raises(PoolClosed):                # after shutdown
        eng.submit(_prompt(11), 4)


def test_submit_validation_typed_errors(eng):
    with pytest.raises(ValueError):
        eng.submit(np.zeros((3, 3), np.int32), 4)      # rank
    with pytest.raises(ValueError):
        eng.submit(np.array([0.5, 1.5]), 4)            # dtype
    with pytest.raises(ValueError):
        eng.submit(np.array([], np.int32), 4)          # empty
    with pytest.raises(ValueError):
        eng.submit(np.arange(40, dtype=np.int32), 4)   # over bucket
    with pytest.raises(ValueError):
        eng.submit(np.array([5, 96, 97], np.int32), 4)  # out of vocab
    with pytest.raises(ValueError):
        eng.submit(_prompt(1), 0)                      # no tokens
    with pytest.raises(ValueError):
        eng.submit(_prompt(1), 47)                     # > max_length


def test_int8_paged_cache_solo_vs_batched_identity(model):
    """int8 paged KV: batched decode stays bit-identical to solo decode
    (the quantize/dequantize path rides inside the per-sequence scan
    body), and the engine honors the model-level cache_quant default."""
    model.cache_quant = "int8"
    try:
        with _engine(model) as eng:
            assert eng.pool.quant == "int8"
            solo_a = eng.generate(_prompt(12), 10)
            solo_b = eng.generate(_prompt(13), 6)
            a = eng.submit(_prompt(12), 10)
            b = eng.submit(_prompt(13), 6)
            assert a.result() == solo_a and b.result() == solo_b
    finally:
        del model.cache_quant


def test_compile_once_per_bucket(eng):
    for seed in (14, 15, 16, 17, 18):
        eng.generate(_prompt(seed), 5)
    st = eng.stats()
    built = st["compiles"]["built"] + st["compiles"]["disk"]
    # at most one executable per decode bucket + per prefill bucket, no
    # matter how many sequences ran (shared engine: every prior test's
    # traffic counts toward the same bound)
    assert built <= len(eng.decode_buckets) + len(eng.prefill_buckets)
    before = st["compiles"]
    eng.generate(_prompt(19), 5)
    assert eng.stats()["compiles"] == before


def test_serving_pool_generation_integration(model):
    """ServingPool(decode_engine=...): submit_generate streams through
    the pool surface, stats embed the engine + block pool, shutdown
    drains the engine too."""
    eng = _engine(model)
    pool = ServingPool(decode_engine=eng, default_timeout=60.0)
    try:
        ref = eng.generate(_prompt(20), 6)
        s = pool.submit_generate(_prompt(20), 6)
        assert s.result() == ref
        assert pool.generate(_prompt(20), 6) == ref
        st = pool.stats()
        assert st["decode"]["completed"] >= 2
        assert _leaked(st["decode"]) == 0
    finally:
        assert pool.shutdown(drain_timeout=10.0)
    with pytest.raises(PoolClosed):
        eng.submit(_prompt(20), 4)
    with pytest.raises(ValueError):
        ServingPool()   # still needs config/predictor without an engine


def test_unexpected_prefill_error_fails_sequence_typed(eng):
    """An unexpected error in the prefill path (e.g. an XLA compile
    failure) must fail THAT sequence with a typed RequestFailed — not
    orphan it with a forever-blocked stream and leaked blocks."""
    from paddle_tpu.inference import RequestFailed

    base = eng.stats()["failed"]
    orig = eng._prefill_fn
    def boom(pbucket):
        raise RuntimeError("injected compile failure")
    eng._prefill_fn = boom
    try:
        s = eng.submit(_prompt(21), 4, timeout=10.0)
        with pytest.raises(RequestFailed):
            s.result()
    finally:
        eng._prefill_fn = orig
    st = eng.stats()
    assert st["failed"] - base == 1 and _leaked(st) == 0
    assert eng.generate(_prompt(21), 4)   # engine still serves


# ---------------------------------------------------------------------------
# the pool donated to every step and chunk (ISSUE 35): one copy of the
# cache, the fault contract, the warm set brought up on threads
# ---------------------------------------------------------------------------

def _leaves(pool):
    return [t for layer in pool.tensors for t in layer]


def _sound(st):
    """A run without speculation in which no dispatch was lost: every
    step and chunk consumed a pool and brought its successor back."""
    return (st["pool_rebuilds"] == 0 and st["donated_dispatches"]
            == st["steps"] + st["prefill_chunks"])


def test_a_dispatch_consumes_the_pool_it_was_given(model):
    """After traffic the tensors the engine started from are gone (each
    dispatch's output pool IS its input's buffers), the pool it holds is
    whole, and the tokens are the un-donated reference's."""
    with _engine(model) as eng:
        first = _leaves(eng.pool)
        got = eng.generate(_prompt(3), 10)
        mid = _leaves(eng.pool)
        assert all(t.is_deleted() for t in first)
        assert not any(t.is_deleted() for t in mid)
        a, b = eng.submit(_prompt(1), 12), eng.submit(_prompt(2), 4)
        assert a.result() and b.result()
        assert all(t.is_deleted() for t in mid)
        assert not any(t.is_deleted() for t in _leaves(eng.pool))
        st = eng.stats()
        # both counters reach /metrics through the engine's collector
        from paddle_tpu.obs.metrics import registry
        text = registry().prometheus_text()
        want = f"decode_{eng.name}_donated_dispatches {st['donated_dispatches']}"
        assert want in text.replace(".0\n", "\n"), text[-600:]
        assert f"decode_{eng.name}_pool_rebuilds 0" in text
    assert got == _ref_tokens(model, _prompt(3), 10)
    assert st["steps"] > 0 and st["prefill_chunks"] > 0 and _sound(st)


def test_compile_cache_key_tells_a_donated_program_from_a_copying_one(
        tmp_path):
    """One tag, one fingerprint, the same avals: `donate_argnums` alone
    makes two cache entries, and each caller is served its own binary."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.jit import aot

    cache = aot.CompileCache(root=str(tmp_path))
    avals = (jax.ShapeDtypeStruct((8, 4), jnp.float32),
             jax.ShapeDtypeStruct((), jnp.int32))

    def fn(x, i):
        return x.at[i].set(1.0), x[i].sum()

    def get(donate):
        return aot.compile_jit(fn, avals, fingerprint="fp", cache=cache,
                               tag="one-tag", donate_argnums=donate)

    assert [get(None)[1], get((0,))[1]] == ["compiled", "compiled"]
    assert len(cache.entries()) == 2
    for donate, consumed in ((None, False), ((0,), True)):
        compiled, source = get(donate)
        x = jnp.zeros((8, 4), jnp.float32)
        compiled(x, jnp.int32(3))
        assert source == "disk" and x.is_deleted() is consumed


class _FailsAfterTheCall:
    """Stands in the engine's non-finite sweep, which runs on the step-pool
    worker right after the compiled call has come back: the pool is
    consumed by then, and the dispatch fails."""

    def __init__(self, eng, when):
        self.eng, self.when, self.fired = eng, when, []
        self.sound = eng._san_sweep
        eng._san_sweep = self

    def __call__(self, new_pool):
        members = (self.eng._last_step or (None, []))[1]
        if self.when(members, len(self.fired)):
            self.fired.append(list(members))
            raise RuntimeError("injected: failed after consuming the pool")
        return self.sound(new_pool)


def _conserved(eng):
    b = eng.stats()["blocks"]
    return (b["allocated"] == 0 and b["allocs"] == b["frees"]
            and b["free"] + b["reserved"] == b["total"]
            and b["state_slots"] == 0
            and b["state_slot_allocs"] == b["state_slot_frees"])


JOBS = ((31, 7, 14), (32, 13, 9), (33, 5, 12))    # seed, prompt, max_new
CHUNKED = dict(prefill_buckets=(8, 16), prefill_chunk=8)


def test_a_step_that_fails_after_consuming_the_pool_costs_the_cache_only(
        model):
    """Fault contract (c): one shared step fails after its compiled call.
    The cache is rebuilt once, every resident sequence prefills again from
    its committed tokens and ends with the tokens of an unfaulted run, no
    block leaks, and the members decode alone until a step of theirs has
    come back."""
    with _engine(model, **CHUNKED) as eng:
        refs = [eng.generate(_prompt(s, n), new) for s, n, new in JOBS]
        base = eng.stats()
        fault = _FailsAfterTheCall(
            eng, lambda members, fired: len(members) == 3 and not fired)
        streams = [eng.submit(_prompt(s, n), new) for s, n, new in JOBS]
        outs = [s.result() for s in streams]
        st = eng.stats()
        assert len(fault.fired) == 1 and outs == refs
        assert st["pool_rebuilds"] - base["pool_rebuilds"] == 1
        assert st["failed"] == 0 and st["completed"] == 6
        assert not any(t.is_deleted() for t in _leaves(eng.pool))
        # the lost dispatch's compiled call did come back: one consumed
        # pool that no step or chunk counts
        assert st["donated_dispatches"] \
            == st["steps"] + st["prefill_chunks"] + 1
        assert eng.generate(_prompt(34), 6)          # and it serves on
    assert _conserved(eng)


def test_a_sequence_that_fails_alone_twice_is_the_one_that_fails(model):
    """A dispatch that fails after consuming the pool whenever one
    sequence is in it: the shared step costs a rebuild, then the suspect
    fails alone and is failed; its batchmates end with their own
    tokens."""
    from paddle_tpu.inference import RequestFailed

    with _engine(model, **CHUNKED) as eng:
        refs = [eng.generate(_prompt(s, n), new) for s, n, new in JOBS]
        victim = []
        fault = _FailsAfterTheCall(
            eng, lambda members, fired: bool(victim)
            and victim[0] in members and eng._round_no > 0
            and any(s.state == "active" and s.id == victim[0]
                    for s in eng._active))
        streams = [eng.submit(_prompt(s, n), new) for s, n, new in JOBS]
        victim.append(streams[1].id)
        outs = []
        for s in streams:
            try:
                outs.append(s.result())
            except RequestFailed:
                outs.append(None)
        st = eng.stats()
        assert outs[1] is None and outs[0] == refs[0] and outs[2] == refs[2]
        assert st["failed"] == 1 and 1 <= st["pool_rebuilds"] <= 2
        assert eng.generate(_prompt(35), 5)
    assert _conserved(eng)


def test_a_wedged_steps_retired_worker_does_not_dispatch(model):
    """Fault contract (b): the step that hangs in its hook past the
    deadline is submitted again from the pool as it stands; the retired
    worker wakes afterwards, finds its attempt cancelled and takes no
    pool, so every dispatch that consumed one is a committed one."""
    from paddle_tpu.inference.decode import engine as engine_mod

    hung, woke, retired = [], threading.Event(), []

    def hook(tag, ids, info):
        if tag == "decode" and len(ids) > 1 and not hung:
            hung.append(ids)
            time.sleep(1.0)
            woke.set()

    with _engine(model, fault_hook=hook, step_timeout=0.3,
                 step_retries=2, **CHUNKED) as eng:
        consume = eng._consume

        def watched(attempt, pool, call):
            try:
                return consume(attempt, pool, call)
            except engine_mod._AttemptRetired:
                retired.append(attempt)
                raise

        eng._consume = watched
        refs = [eng.generate(_prompt(s, n), new) for s, n, new in JOBS]
        streams = [eng.submit(_prompt(s, n), new) for s, n, new in JOBS]
        assert [s.result() for s in streams] == refs
        assert woke.wait(10.0)
        for _ in range(200):
            if retired:
                break
            time.sleep(0.01)
        st = eng.stats()
    assert hung and st["wedged_steps"] >= 1 and len(retired) == 1
    assert retired[0].cancelled and _sound(st)
    assert _conserved(eng)


def test_a_step_that_outlasts_its_deadline_inside_the_call_is_waited_for(
        model):
    """An attempt that times out INSIDE its compiled call holds the only
    copy of the cache: it is not given up and nothing is dispatched beside
    it; when it comes back, late, what it brings is the step. No rebuild,
    no token differs, every consumed pool is a committed one."""
    slow = []

    with _engine(model, step_timeout=0.3, step_retries=2, **CHUNKED) as eng:
        refs = [eng.generate(_prompt(s, n), new) for s, n, new in JOBS]
        sweep = eng._san_sweep

        def slow_after_the_call(new_pool):
            members = (eng._last_step or (None, []))[1]
            if len(members) > 1 and not slow:
                slow.append(members)
                time.sleep(0.5)         # past the deadline, pool consumed
            return sweep(new_pool)

        eng._san_sweep = slow_after_the_call
        streams = [eng.submit(_prompt(s, n), new) for s, n, new in JOBS]
        assert [s.result() for s in streams] == refs
        st = eng.stats()
    assert slow and st["wedged_steps"] >= 1 and _sound(st)
    assert _conserved(eng)


def test_a_dispatch_that_raises_before_the_call_leaves_the_pool_whole(
        model):
    """Fault contract (a): a hook that raises in a shared step (before the
    compiled call) costs an isolation round and no cache."""
    armed = [3]     # the step pool runs a failing closure three times

    def hook(tag, ids, info):
        if tag == "decode" and len(ids) > 1 and armed[0]:
            armed[0] -= 1
            raise RuntimeError("injected before the call")

    with _engine(model, fault_hook=hook, **CHUNKED) as eng:
        refs = [eng.generate(_prompt(s, n), new) for s, n, new in JOBS]
        streams = [eng.submit(_prompt(s, n), new) for s, n, new in JOBS]
        assert [s.result() for s in streams] == refs
        st = eng.stats()
    assert not armed[0] and st["isolation_rounds"] >= 1 and _sound(st)


def _warm_engine(model, width, monkeypatch, **kw):
    """An engine, its `warmup()`'s return, and the (tag, key, source) of
    every program it brought up, sorted; `width` 1 walks them one by
    one."""
    from paddle_tpu.jit import aot

    rows, real_load, real_jit = [], aot._load_executable, aot.compile_jit
    here = threading.local()

    def load(cache, key, in_shardings):
        here.key = key
        return real_load(cache, key, in_shardings)

    def jit(fn, avals, **k):
        out = real_jit(fn, avals, **k)
        if out[0] is not None:          # not: a look into the cache alone
            rows.append((k["tag"], here.key, out[1]))
        return out

    monkeypatch.setattr(aot, "_load_executable", load)
    monkeypatch.setattr(aot, "compile_jit", jit)
    if width == 1:
        monkeypatch.setattr(os, "cpu_count", lambda: 1)
    eng = _engine(model, **kw)
    warmed = eng.warmup()
    monkeypatch.undo()
    return eng, warmed, sorted(rows)


def test_warmup_on_threads_brings_up_what_a_serial_walk_does(
        model, tmp_path, monkeypatch):
    """Same return, same cache keys, same built / disk counts: cold (every
    program built) and warm (a second engine in the process loads them
    all), and tokens from the programs either walk brought up."""
    kw = dict(decode_buckets=(1, 2, 4), prefill_buckets=(8, 16, 24),
              prefill_chunk=16)
    seen = {}
    for width in (1, 0):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / f"w{width}"))
        cold, cold_ret, cold_rows = _warm_engine(model, width, monkeypatch,
                                                 **kw)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / f"w{width}"))
        warm, warm_ret, warm_rows = _warm_engine(model, width, monkeypatch,
                                                 **kw)
        with cold, warm:
            n = len(cold_rows)
            assert n == 3 + 2 + 1                   # steps, chunks, COW
            assert "decode-prefill-p24" not in [t for t, _, _ in cold_rows]
            assert cold.stats()["compiles"] == {"built": n, "disk": 0}
            assert warm.stats()["compiles"] == {"built": 0, "disk": n}
            assert [(t, k) for t, k, _ in cold_rows] \
                == [(t, k) for t, k, _ in warm_rows]
            # the bucket above the chunk is no part of the warm set: no
            # dispatch can reach it, and a prompt longer than it compiles
            # nothing
            assert cold_ret == warm_ret == {"decode": [1, 2, 4],
                                            "prefill": [8, 16]}
            tokens = [e.generate(_prompt(41, 29), 7) for e in (cold, warm)]
            assert warm.stats()["compiles"] == {"built": 0, "disk": n}
        seen[width] = ([(t, k) for t, k, _ in cold_rows], cold_ret, tokens)
    assert seen[0] == seen[1]


def test_warmup_on_threads_under_the_lock_checker(model, tmp_path,
                                                  monkeypatch, checker):
    """The engine built after `enable()`: its locks, the step pool's and
    the compile cache's are instrumented. A cold `warmup()` on threads,
    traffic with a wedge in it, and shutdown leave no ordering cycle and
    no lock held across a blocking region."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    hung = []

    def hook(tag, ids, info):
        if tag == "decode" and not hung:
            hung.append(ids)
            time.sleep(0.6)

    with _engine(model, fault_hook=hook, step_timeout=0.2,
                 step_retries=2, **CHUNKED) as eng:
        eng.warmup()
        assert eng.stats()["compiles"]["built"] == 5
        streams = [eng.submit(_prompt(s, n), new) for s, n, new in JOBS]
        assert all(s.result() for s in streams)
        assert eng.stats()["wedged_steps"] >= 1
    rep = checker.assert_clean()
    assert {"decode.engine", "aot.compile_cache"} <= set(rep["locks"])


# ---------------------------------------------------------------------------
# cache_quant precedence + typed error (satellite)
# ---------------------------------------------------------------------------

def test_cache_quant_argument_beats_attribute():
    paddle.seed(0)
    m = gpt("gpt_tiny", **TINY)
    m.cache_quant = "int8"
    assert len(m.init_cache(1, 8)[0]) == 4          # attribute default
    assert len(m.init_cache(1, 8, quant="bf16")[0]) == 2   # arg overrides
    assert m.init_block_pool(4, 4, quant="bf16").quant is None
    assert m.init_block_pool(4, 4).quant == "int8"  # attr fallback
    del m.cache_quant
    assert len(m.init_cache(1, 8)[0]) == 2
    assert len(m.init_cache(1, 8, quant="int8")[0]) == 4


def test_cache_quant_unknown_raises_typed():
    paddle.seed(0)
    m = gpt("gpt_tiny", **TINY)
    for bad in ("int3", "fp8", "INT4", 8):
        with pytest.raises(CacheQuantError):
            m.init_cache(1, 8, quant=bad)
        with pytest.raises(CacheQuantError):
            m.init_block_pool(4, 4, quant=bad)
    m.cache_quant = "int5"                # poisoned attribute is typed too
    with pytest.raises(CacheQuantError):
        m.init_cache(1, 8)
    assert issubclass(CacheQuantError, ValueError)  # compat contract


# ---------------------------------------------------------------------------
# persistent compile cache (warm start) — subprocess-proven, slow like PR 4
# ---------------------------------------------------------------------------

_WARM_SNIPPET = """
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.inference import DecodeEngine
from paddle_tpu.models import gpt

paddle.seed(7)
m = gpt("gpt_tiny", vocab_size=97, hidden_size=48, num_heads=4,
        num_kv_heads=2, num_layers=2, rope=True, swiglu=True,
        rms_norm=True, max_position_embeddings=64,
        tie_word_embeddings=False)
m.eval()
eng = DecodeEngine(m, max_length=48, block_size=8, decode_buckets=(1, 2),
                   prefill_buckets=(8,), default_timeout=60.0)
eng.warmup()
tokens = eng.generate(np.arange(6, dtype=np.int32), 4)
st = eng.stats()
eng.shutdown()
print("COMPILES", st["compiles"]["built"], st["compiles"]["disk"],
      "TOKENS", ",".join(map(str, tokens)))
"""


@pytest.mark.slow
def test_warm_start_compiles_zero_decode_executables(
        tmp_path, _shared_compile_cache):
    """A fresh process with a warm on-disk cache must compile ZERO
    decode-step/prefill executables (all disk loads) and produce the
    same tokens."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    outs = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", _WARM_SNIPPET], env=env,
                           cwd=REPO, capture_output=True, text=True,
                           timeout=600)
        assert r.returncode == 0, f"\n{r.stdout}\n{r.stderr}"
        outs.append([ln for ln in r.stdout.splitlines()
                     if ln.startswith("COMPILES")][0].split())
    cold, warm = outs
    assert int(cold[1]) > 0                    # cold: really compiled
    assert int(warm[1]) == 0 and int(warm[2]) > 0   # warm: zero compiles
    assert cold[4] == warm[4]                  # identical tokens
