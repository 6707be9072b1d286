"""Batched LoRA adapter multiplexing (PR 18): mixed-adapter decode is
bit-identical per sequence to solo decode (including the int8 KV layout
and prefix sharing), the slot pool is LOUD on refcount misuse, LRU
eviction / generation-stamped swap / `OutOfAdapterSlots` backpressure
behave, the Pallas BGMV kernel agrees with the XLA fallback in
interpret mode, and `AdapterNotLoaded` is the typed (ValueError)
deterministic request error.

One module-scoped engine + pool carry the forward-pass tests; the pool
bookkeeping tests use a throwaway 1-layer model (hooks detached after)
so they never perturb the shared engine.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (
    AdapterNotLoaded, AdapterPool, DecodeEngine, OutOfAdapterSlots,
    SamplingParams)
from paddle_tpu.models import gpt
from paddle_tpu.ops.pallas.bgmv import lora_delta

TINY = dict(vocab_size=97, hidden_size=48, num_heads=4, num_kv_heads=2,
            num_layers=2, rope=True, swiglu=True, rms_norm=True,
            max_position_embeddings=64, tie_word_embeddings=False)

GEO = dict(max_length=32, block_size=8, decode_buckets=(1, 4),
           prefill_buckets=(8,), num_blocks=18, prefix_cache=False,
           default_timeout=60.0)

RANK = 4


@pytest.fixture(scope="module", autouse=True)
def _shared_compile_cache(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("decode-adapters-compile-cache"))
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


def _weights(pool, seed):
    """Random A/B per matched layer at the pool's geometry, small scale
    so adapted logits stay near (but not equal to) the base model's."""
    rng = np.random.RandomState(seed)
    out = {}
    for lname, ab in pool.stacks().items():
        _, in_f, r = ab[0].shape
        out_f = ab[1].shape[-1]
        out[lname] = (rng.randn(in_f, r).astype(np.float32) * 0.05,
                      rng.randn(r, out_f).astype(np.float32) * 0.05)
    return out


@pytest.fixture(scope="module")
def pool(model):
    p = AdapterPool(model, rank=RANK, slots=4)
    p.load("t0", _weights(p, 100))
    p.load("t1", _weights(p, 101))
    yield p
    p.detach()


@pytest.fixture(scope="module")
def eng(model, pool):
    e = DecodeEngine(model, **GEO, adapters=pool)
    yield e
    e.shutdown(drain_timeout=10.0)


def _prompt(seed, n=8):
    return np.random.RandomState(seed).randint(
        0, TINY["vocab_size"], (n,)).astype(np.int32)


# ---------------------------------------------------------------------------
# the acceptance bar: mixed == solo, bitwise
# ---------------------------------------------------------------------------

def test_mixed_batch_bit_identical_to_solo(eng):
    """Three tenants (base, t0, t1) decoded in ONE batched dispatch
    each emit exactly the tokens they emit decoded alone — the BGMV
    gather gives every row its own slot, and slot-0 rows select the
    base output bitwise."""
    prompts = [_prompt(i) for i in range(3)]
    tenants = [None, "t0", "t1"]
    solo = [eng.generate(p, 8, adapter=a)
            for p, a in zip(prompts, tenants)]
    assert len({tuple(s) for s in solo}) == 3  # adapters actually bite
    streams = [eng.submit(p, 8, adapter=a)
               for p, a in zip(prompts, tenants)]
    assert [s.result() for s in streams] == solo
    st = eng.stats()["adapters"]
    assert st["refs"] == 0 and st["loaded"] == 2


def test_sampled_adapter_decode_deterministic(eng):
    """Adapter + sampling compose: a seeded sampled stream under t0 is
    reproducible, and a mixed sampled/greedy/adapter batch still
    reproduces each solo stream."""
    p = _prompt(5)
    sp = SamplingParams(temperature=0.8, top_k=10, seed=77)
    solo = eng.generate(p, 8, adapter="t0", sampling=sp)
    assert eng.generate(p, 8, adapter="t0", sampling=sp) == solo
    base = eng.generate(_prompt(6), 8)
    a = eng.submit(p, 8, adapter="t0", sampling=sp)
    b = eng.submit(_prompt(6), 8)
    assert a.result() == solo and b.result() == base


def test_int8_base_and_prefix_sharing_compose(model, pool):
    """The adapter delta rides the int8-KV engine with prefix sharing
    on: shared-prefix mixed-tenant decode is bit-identical to solo, and
    the cache keys carry the adapter signature (a t0 hit never feeds a
    base-model sequence)."""
    model.cache_quant = "int8"
    try:
        with DecodeEngine(model, **{**GEO, "decode_buckets": (1, 2),
                                    "prefix_cache": True},
                          adapters=pool) as e:
            # a prompt under which t0 flips a greedy token within 6 steps
            # (under _prompt(9) it flips none, with or without int8 KV or
            # the prefix cache)
            p = _prompt(2)
            solo_base = e.generate(p, 6)
            solo_t0 = e.generate(p, 6, adapter="t0")
            assert solo_base != solo_t0
            s0 = e.submit(p, 6)
            s1 = e.submit(p, 6, adapter="t0")
            assert s0.result() == solo_base
            assert s1.result() == solo_t0
    finally:
        del model.cache_quant


# ---------------------------------------------------------------------------
# pool bookkeeping: LOUD misuse, LRU, swap, backpressure
# ---------------------------------------------------------------------------

def _mini_pool(slots=3):
    paddle.seed(3)
    m = gpt("gpt_tiny", vocab_size=31, hidden_size=16, num_heads=2,
            num_kv_heads=2, num_layers=1, max_position_embeddings=16)
    return AdapterPool(m, rank=2, slots=slots)


def test_refcount_misuse_is_loud():
    pool = _mini_pool()
    try:
        pool.load("a", _weights(pool, 1))
        slot, gen = pool.acquire("a", "owner-1")
        with pytest.raises(ValueError, match="referenced"):
            pool.unload("a")
        with pytest.raises(ValueError, match="no reference"):
            pool.release(slot, "owner-2")
        pool.release(slot, "owner-1")
        with pytest.raises(ValueError, match="no reference"):
            pool.release(slot, "owner-1")
        assert pool.release_owned("owner-1") == 0  # idempotent teardown
        pool.unload("a")
        with pytest.raises(AdapterNotLoaded):
            pool.unload("a")
    finally:
        pool.detach()


def test_lru_eviction_and_slot_backpressure():
    pool = _mini_pool(slots=3)  # 2 usable, slot 0 reserved
    try:
        pool.load("a", _weights(pool, 1))
        pool.load("b", _weights(pool, 2))
        pool.acquire("a", "s1")
        pool.acquire("b", "s2")
        with pytest.raises(OutOfAdapterSlots):
            pool.load("c", _weights(pool, 3))
        pool.release_owned("s1")  # "a" idle -> the LRU victim
        pool.load("c", _weights(pool, 3))
        st = pool.stats()
        assert st["evictions"] == 1 and st["loaded"] == 2
        with pytest.raises(AdapterNotLoaded):
            pool.acquire("a", "s3")
        pool.release_owned("s2")
    finally:
        pool.detach()


def test_generation_stamped_swap_pins_old_slot():
    """Hot-reloading a REFERENCED adapter lands in a fresh slot; the
    old slot stays pinned (anonymous) until its holders release, so
    in-flight sequences finish under the weights they started with."""
    pool = _mini_pool(slots=4)
    try:
        pool.load("a", _weights(pool, 1))
        old_slot, old_gen = pool.acquire("a", "s1")
        pool.load("a", _weights(pool, 9))  # swap under load
        new_slot, new_gen = pool.acquire("a", "s2")
        assert new_slot != old_slot and new_gen > old_gen
        st = pool.stats()
        assert st["swaps"] == 1 and st["pinned_anonymous"] == 1
        pool.release(old_slot, "s1")  # last holder frees the old slot
        st = pool.stats()
        assert st["pinned_anonymous"] == 0 and st["used"] == 1
        pool.release_owned("s2")
        # idle reload stays in place: no swap, fresh generation
        assert pool.load("a", _weights(pool, 10)) == new_slot
        assert pool.stats()["swaps"] == 1
    finally:
        pool.detach()


def test_adapter_not_loaded_is_typed_request_error(eng):
    """`AdapterNotLoaded` subclasses ValueError — the deterministic
    request-error contract (fail fast, no failover) — and surfaces
    synchronously from submit, on a pool-less engine too."""
    assert issubclass(AdapterNotLoaded, ValueError)
    with pytest.raises(AdapterNotLoaded):
        eng.submit(_prompt(0), 4, adapter="nope")
    assert eng.stats()["adapters"]["refs"] == 0


def test_load_shape_mismatch_is_loud():
    pool = _mini_pool()
    try:
        w = _weights(pool, 1)
        bad = {k: (v[0][:, :-1], v[1]) for k, v in w.items()}
        with pytest.raises(ValueError, match="expected A"):
            pool.load("a", bad)
        first = next(iter(w))
        with pytest.raises(ValueError, match="missing weights"):
            pool.load("a", {k: v for k, v in w.items() if k != first})
    finally:
        pool.detach()


# ---------------------------------------------------------------------------
# BGMV kernel parity (interpret mode) — the math under the hook
# ---------------------------------------------------------------------------

def test_bgmv_kernel_matches_fallback():
    rng = np.random.RandomState(0)
    x = rng.randn(3, 2, 16).astype(np.float32)
    A = rng.randn(4, 16, RANK).astype(np.float32)
    B = rng.randn(4, RANK, 8).astype(np.float32)
    A[0] = 0.0
    B[0] = 0.0
    ids = np.asarray([0, 2, 3], np.int32)
    ref = np.asarray(lora_delta(x, A, B, ids, use_kernel=False))
    ker = np.asarray(lora_delta(x, A, B, ids, use_kernel=True,
                                interpret=True))
    np.testing.assert_allclose(ker, ref, rtol=1e-5, atol=1e-5)
    assert not ref[0].any()  # slot 0 is the all-zero no-adapter lane
    # scalar-id path (per-sequence prefill) agrees with the batched row
    solo = np.asarray(lora_delta(x[1:2], A, B, np.int32(2)))
    np.testing.assert_allclose(solo[0], ref[1], rtol=1e-5, atol=1e-5)
