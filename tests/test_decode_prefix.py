"""Decode speed 2.0 (paddle_tpu/inference/decode): copy-on-write prefix
sharing and chunked prefill.

Proves the PR-13 acceptance bar: N sequences over one prompt prefix hold
ONE physical copy of the shared KV blocks (pool refcounts + `stats()`
prove it) while their per-token outputs stay BIT-IDENTICAL to
private-copy decode (`prefix_cache=False`) — including the int8 KV
layout — plus refcount conservation on the allocator, longest-prefix
(chunk-boundary) matching, chunked-prefill parity against monolithic
prefill, LRU eviction under the block cap and admission pressure, and
the admission-headroom win sharing buys at a fixed pool size.

Named to sort before test_op_schema (the tier-1 timeout lands there);
engines are module-scoped and share one on-disk compile cache like
test_decode_engine's, so the file stays cheap.
"""
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import DecodeEngine
from paddle_tpu.inference.decode.block_pool import (
    BlockKVCache, OutOfBlocks, RESERVED_BLOCKS)
from paddle_tpu.models import gpt

TINY = dict(vocab_size=97, hidden_size=48, num_heads=4, num_kv_heads=2,
            num_layers=2, rope=True, swiglu=True, rms_norm=True,
            max_position_embeddings=64, tie_word_embeddings=False)

#: shared geometry: identical across the sharing and private engines so
#: they compile the SAME executables (the second engine disk-hits)
GEO = dict(max_length=48, block_size=8, decode_buckets=(1, 2, 4),
           prefill_buckets=(8, 16, 24), prefill_chunk=8,
           num_blocks=29, default_timeout=60.0)


@pytest.fixture(scope="module", autouse=True)
def _shared_compile_cache(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("decode-prefix-compile-cache"))
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


@pytest.fixture(scope="module")
def eng(model):
    """The sharing engine (prefix cache + chunked prefill on)."""
    e = DecodeEngine(model, **GEO)
    yield e
    e.shutdown(drain_timeout=10.0)


@pytest.fixture(scope="module")
def peng(model):
    """The PRIVATE-COPY reference engine: identical geometry and chunk
    decomposition, prefix cache off — the bit-identity yardstick."""
    e = DecodeEngine(model, **{**GEO, "prefix_cache": False})
    yield e
    e.shutdown(drain_timeout=10.0)


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(
        0, TINY["vocab_size"], (n,)).astype(np.int32)


def _quiesced_leak(st):
    """Blocks held beyond the prefix cache's deliberate pins."""
    return (st["blocks"]["allocated"]
            - st["prefix_cache"]["physical_blocks"])


# ---------------------------------------------------------------------------
# allocator: refcounts, conservation, copy-on-write primitive
# ---------------------------------------------------------------------------

def _tiny_pool(num_blocks=8, block_size=4):
    import jax.numpy as jnp

    spec = (((8,), jnp.float32, 2), ((8,), jnp.float32, 2))
    return BlockKVCache(num_blocks, block_size, [spec])


def test_pool_refcount_conservation_and_sharing():
    pool = _tiny_pool()
    a = pool.alloc(3, owner="seq1")
    pool.incref(a[:2], owner="seq2")          # share two blocks
    pool.incref(a[:1], owner="cache")
    s = pool.stats()
    assert s["allocated"] == 3                 # ONE physical copy each
    assert s["shared_blocks"] == 2 and s["shared_refs"] == 3
    assert s["allocated"] + s["free"] + s["reserved"] == s["total"]
    assert pool.refcount(a[0]) == 3 and pool.refcount(a[2]) == 1
    # dropping seq1 keeps the shared blocks alive for seq2/cache
    assert pool.free_owned("seq1") == 3
    s = pool.stats()
    assert s["allocated"] == 2 and pool.refcount(a[0]) == 2
    assert pool.decref(a[:2], owner="seq2") == 1   # a[1] freed, a[0] kept
    assert pool.free_owned("cache") == 1
    s = pool.stats()
    assert s["allocated"] == 0 and s["allocs"] == s["frees"] == 3


def test_pool_refcount_misuse_is_loud():
    pool = _tiny_pool()
    a = pool.alloc(2, owner="x")
    pool.incref([a[0]], owner="y")
    with pytest.raises(ValueError):
        pool.free([a[0]])                      # shared: free() refuses
    with pytest.raises(ValueError):
        pool.decref([a[0]], owner="z")         # z holds no reference
    with pytest.raises(ValueError):
        pool.incref([0], owner="y")            # reserved id
    pool.free([a[1]])                          # exclusive: still fine
    with pytest.raises(ValueError):
        pool.free([a[1]])                      # double-free
    assert pool.free_owned("nobody") == 0      # idempotent


def test_pool_copy_block_copies_every_layer_tensor():
    import jax.numpy as jnp

    pool = _tiny_pool()
    src, dst = pool.alloc(2, owner="s")
    pool.tensors = [tuple(t.at[src].set(float(i + 1))
                          for i, t in enumerate(layer))
                    for layer in pool.tensors]
    pool.copy_block(src, dst)
    for layer in pool.tensors:
        for i, t in enumerate(layer):
            assert jnp.array_equal(t[dst], t[src])
            assert float(t[dst].ravel()[0]) == float(i + 1)


# ---------------------------------------------------------------------------
# engine: sharing, COW, bit-identity
# ---------------------------------------------------------------------------

def test_full_prompt_sharing_one_physical_copy_bit_identical(eng, peng):
    """The acceptance criterion end-to-end: N identical prompts share ONE
    physical copy of the prompt blocks, outputs bit-match private-copy
    decode, and the mid-block prompt tail is COW-copied by each writer
    (publisher included) without corrupting anyone."""
    p = _prompt(30, 12)                # 12 tokens: partial third... 2nd block
    ref = peng.generate(p, 8)
    base = eng.stats()
    assert eng.generate(p, 8) == ref   # publisher populates the cache
    st = eng.stats()
    assert st["prefix_cache"]["entries"] - \
        base["prefix_cache"]["entries"] >= 2   # chunk@8 + full@12
    # chunk and full entries overlap on block 0 — shared even at rest
    assert st["blocks"]["shared_refs"] >= 1

    streams = [eng.submit(p, 24) for _ in range(3)]
    first = [next(iter(s)) for s in streams]   # delivered AT admission:
    assert first == [ref[0]] * 3               # the cached next token
    st = eng.stats()
    assert st["prefix_cache"]["full_hits"] - \
        base["prefix_cache"]["full_hits"] == 3
    # while all three decode: block 0 carries cache + 3 sequence refs —
    # one physical block however many holders (poll: COW progressively
    # privatizes the TAIL block, block 0 is never written)
    deadline = time.monotonic() + 5.0
    seen_shared = 0
    while time.monotonic() < deadline:
        bs = eng.stats()["blocks"]
        seen_shared = max(seen_shared, bs["shared_refs"])
        if seen_shared >= 4:
            break
        time.sleep(0.002)
    assert seen_shared >= 4
    out = [s.result() for s in streams]
    solo = peng.generate(p, 24)
    assert out == [solo] * 3                   # bit-identical to private
    st = eng.stats()
    # publisher + each of the 3 full hitters COWed the mid-block tail
    assert st["cow_copies"] - base["cow_copies"] == 4
    assert _quiesced_leak(st) == 0


def test_longest_prefix_chunk_boundary_match(eng, peng):
    """Two prompts sharing a 16-token prefix (two chunks) but different
    tails: the second bumps refcounts for the shared chunks and only
    prefills its private remainder — tokens stay bit-identical to
    private-copy decode."""
    common = _prompt(40, 16)
    pa = np.concatenate([common, _prompt(41, 4)]).astype(np.int32)
    pb = np.concatenate([common, _prompt(42, 4)]).astype(np.int32)
    ref_a, ref_b = peng.generate(pa, 6), peng.generate(pb, 6)
    base = eng.stats()
    assert eng.generate(pa, 6) == ref_a        # seeds chunk@8, chunk@16
    assert eng.generate(pb, 6) == ref_b        # longest match: 16 tokens
    st = eng.stats()
    assert st["prefix_cache"]["hits"] - base["prefix_cache"]["hits"] >= 1
    assert st["prefix_cache"]["tokens_reused"] - \
        base["prefix_cache"]["tokens_reused"] >= 16
    assert st["prefix_hit_rate"] > 0.0
    assert _quiesced_leak(st) == 0


def test_chunked_prefill_parity_vs_monolithic(eng, model):
    """A 22-token prompt runs as 8+8+6 chunk dispatches interleaved with
    decode rounds; tokens must match a monolithic single-dispatch
    prefill of the same prompt."""
    p = _prompt(50, 22)
    base = eng.stats()
    got = eng.generate(p, 6)
    st = eng.stats()
    assert st["prefill_chunks"] - base["prefill_chunks"] == 3
    with DecodeEngine(model, **{**GEO, "prefix_cache": False,
                                "prefill_chunk": False}) as mono:
        assert mono.stats()["buckets"]["prefill_chunk"] == 0
        want = mono.generate(p, 6)
        assert mono.stats()["prefill_chunks"] == 1   # one dispatch
    assert got == want


def test_int8_kv_cow_identity(model):
    """COW bit-identity holds for the int8 (kq, ks, vq, vs) pool layout:
    quantized value blocks and f32 scale blocks copy together."""
    model.cache_quant = "int8"
    try:
        with DecodeEngine(model, **{**GEO, "decode_buckets": (2,),
                                    "prefill_buckets": (8, 16)}) as se, \
                DecodeEngine(model, **{**GEO, "decode_buckets": (2,),
                                       "prefill_buckets": (8, 16),
                                       "prefix_cache": False}) as pe:
            assert se.pool.quant == "int8"
            p = _prompt(60, 12)
            ref = pe.generate(p, 8)
            assert se.generate(p, 8) == ref
            a, b = se.submit(p, 8), se.submit(p, 8)
            assert a.result() == ref and b.result() == ref
            st = se.stats()
            assert st["prefix_cache"]["full_hits"] == 2
            assert st["cow_copies"] >= 3
            assert _quiesced_leak(st) == 0
    finally:
        del model.cache_quant


# ---------------------------------------------------------------------------
# admission headroom + eviction
# ---------------------------------------------------------------------------

def test_admission_headroom_under_sharing(eng, peng):
    """At a FIXED pool size, sharing shrinks each sequence's fresh-block
    footprint: the same 4-deep identical-prompt workload peaks far fewer
    FRESH physical blocks than private-copy decode — the capacity that
    gates admission at scale. Runs on the warmed module engines (no
    throwaway construction): `reset_peak()` re-arms each pool's
    high-water mark, so `peak - baseline-allocated` is the workload's
    own footprint delta even though earlier tests already pushed the
    monotone peak higher."""
    p = _prompt(70, 24)                        # 3 full blocks of prompt
    peaks = {}
    for mode, e in (("shared", eng), ("private", peng)):
        base_alloc = e.pool.reset_peak()       # pins held by the prefix
        e.generate(p, 8)                       # cache stay in the base
        streams = [e.submit(p, 8) for _ in range(4)]
        for s in streams:
            assert s.result() == streams[0].tokens
        peaks[mode] = e.stats()["blocks"]["peak_allocated"] - base_alloc
    # private: 4 concurrent sequences own 4 blocks each (+canary churn);
    # shared: 3 prompt blocks exist ONCE + per-seq COW/growth blocks
    assert peaks["shared"] < peaks["private"]


def test_prefix_cache_eviction_cap_and_pressure(model):
    """The cache is bounded: a small block cap LRU-evicts older entries,
    and admission pressure evicts rather than shedding a sequence."""
    with DecodeEngine(model, **{**GEO, "decode_buckets": (1,),
                                "num_blocks": 9,
                                "prefix_cache_blocks": 4}) as e:
        for seed in (80, 81, 82, 83):
            e.generate(_prompt(seed, 12), 4)
        st = e.stats()
        assert st["prefix_cache"]["evictions"] >= 1
        # the cap bounds PHYSICAL pinned blocks (overlapping entries
        # share prefix blocks — the per-entry sum may legally exceed it)
        assert st["prefix_cache"]["physical_blocks"] <= 4
        # pressure path: a request whose worst case needs nearly the
        # whole pool forces the remaining entries out instead of waiting
        before = st["prefix_cache"]["evictions"]
        assert e.generate(_prompt(84, 12), 36)   # worst case: 7 of 8
        st = e.stats()
        assert st["prefix_cache"]["evictions"] > before
        assert _quiesced_leak(st) == 0
        bs = st["blocks"]
        assert bs["allocated"] + bs["free"] + bs["reserved"] == bs["total"]
