"""Speculative decoding on the paged decode engine
(paddle_tpu/inference/decode): a draft model proposes K tokens per
scheduler round, the target verifies all K+1 positions in ONE bucketed
dispatch, greedy acceptance commits the longest matching prefix plus the
target's correction/bonus token.

The acceptance bar is BIT-IDENTITY: speculative output must equal plain
greedy decode (`speculate_k=0`) at every bucket size — proven here for a
self-draft (always accepts), a perturbed draft (real rejections +
corrections), the int8 KV layout, prefix sharing (COW composes), EOS
stopping mid-round, and the near-max-length plain fallback. Plus: draft
AND target block-pool conservation, admission reservation on the draft
pool, and compile-once-per-bucket for the propose/verify executables.

Named to sort before test_op_schema (the tier-1 timeout lands there);
engines are module-scoped and share one on-disk compile cache like
test_decode_engine's, so the file stays cheap.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import DecodeEngine, RequestFailed
from paddle_tpu.models import gpt

TINY = dict(vocab_size=97, hidden_size=48, num_heads=4, num_kv_heads=2,
            num_layers=2, rope=True, swiglu=True, rms_norm=True,
            max_position_embeddings=64, tie_word_embeddings=False)

#: shared geometry across every engine in this file, so the target-side
#: decode/prefill executables compile once and every later engine
#: disk-hits them (the draft/propose/verify programs have their own
#: fingerprints and compile once each too). Buckets (1, 2) keep the
#: compile bill small; the injector's decode-spec phase runs the same
#: bit-exactness bar at buckets (4, 8).
GEO = dict(max_length=48, block_size=8, decode_buckets=(1, 2),
           prefill_buckets=(8,), default_timeout=60.0)
K = 3


@pytest.fixture(scope="module", autouse=True)
def _shared_compile_cache(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("decode-spec-compile-cache"))
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
def draft(model):
    """A perturbed copy of the target: same init then small noise on the
    last block's MLP — it agrees with the target often (speculation
    pays) but not always (rejections + corrections actually run)."""
    paddle.seed(7)
    d = gpt("gpt_tiny", **TINY)
    d.eval()
    rng = np.random.RandomState(11)
    perturbed = 0
    for name, p in d.named_parameters():
        if "layers.1.mlp" in name:
            p._value = p._value + np.asarray(
                rng.normal(0, 2e-2, p.shape), p._value.dtype)
            perturbed += 1
    assert perturbed, "perturbation filter matched no parameter"
    return d


@pytest.fixture(scope="module")
def plain(model):
    """The speculate_k=0 reference engine — the bit-identity yardstick."""
    e = DecodeEngine(model, **GEO)
    yield e
    e.shutdown(drain_timeout=10.0)


@pytest.fixture(scope="module")
def spec(model, draft):
    """The speculative engine under test (perturbed draft)."""
    e = DecodeEngine(model, **GEO, draft_model=draft, speculate_k=K)
    e.warmup()
    yield e
    e.shutdown(drain_timeout=10.0)


def _prompt(seed, n=6):
    return np.random.RandomState(seed).randint(
        0, TINY["vocab_size"], (n,)).astype(np.int32)


def _quiesced(st):
    """Nothing held beyond the prefix cache's deliberate pins, on BOTH
    pools (the draft pool never pins anything)."""
    leak = st["blocks"]["allocated"] - st["prefix_cache"]["physical_blocks"]
    if st["speculative"]["enabled"]:
        leak += st["draft_blocks"]["allocated"]
    return leak == 0


# ---------------------------------------------------------------------------
# bit-identity
# ---------------------------------------------------------------------------

def test_self_draft_full_acceptance_bit_identity(model, plain):
    """Draft == target: every proposal is accepted (the argmaxes are
    computed by bit-identical programs over identical state), every
    round commits K+1 tokens, and output matches plain greedy decode."""
    with DecodeEngine(model, **GEO, draft_model=model,
                      speculate_k=K) as eng:
        eng.warmup()
        # 38 tokens = many consecutive BONUS rounds: acceptance must
        # hold at exactly 1.0 the whole way — it would erode if any
        # bonus round left a draft KV row unwritten behind the
        # committed position (the propose scan's K+1th write)
        for seed, n in ((1, 12), (2, 6), (15, 38)):
            assert eng.generate(_prompt(seed), n) \
                == plain.generate(_prompt(seed), n)
        sp = eng.stats()["speculative"]
        assert sp["enabled"] and sp["k"] == K
        assert sp["proposed"] > 0 and sp["rejected"] == 0
        assert sp["acceptance_rate"] == 1.0
        assert sp["bonus"] >= 1
        # the throughput claim in miniature: > 1 committed token per
        # target dispatch (plain greedy is exactly 1)
        assert sp["accepted_per_dispatch"] > 1.0
        assert _quiesced(eng.stats())


def test_perturbed_draft_rejections_still_bit_identical(spec, plain):
    """The perturbed draft diverges from the target on some positions:
    rejected proposals roll back and the target's correction token is
    committed — output must STILL be exactly plain greedy decode."""
    for seed, n in ((3, 14), (4, 8), (5, 11)):
        assert spec.generate(_prompt(seed), n) \
            == plain.generate(_prompt(seed), n)
    sp = spec.stats()["speculative"]
    assert sp["accepted"] > 0, "draft never agreed — perturbation too big"
    assert sp["rejected"] > 0, "draft always agreed — test has no teeth"
    assert 0.0 < sp["acceptance_rate"] < 1.0
    assert sp["committed"] > 0 and sp["rounds"] > 0


def test_batched_speculation_bit_identity(spec, plain):
    """Concurrent sequences share propose/verify dispatches (bucketed);
    each still gets its solo-identical tokens."""
    seeds = ((6, 10), (7, 7), (8, 12))
    refs = [plain.generate(_prompt(s), n) for s, n in seeds]
    streams = [spec.submit(_prompt(s), n) for s, n in seeds]
    assert [s.result() for s in streams] == refs
    assert _quiesced(spec.stats())


def test_eos_mid_round_stops_exactly_like_plain(model, draft, plain):
    """An EOS landing mid-commit stops delivery exactly where plain
    greedy stops (nothing after EOS leaks out of a speculation round)."""
    p = _prompt(9)
    ref_free = plain.generate(p, 16)
    eos = ref_free[4]              # a token known to appear mid-stream
    with DecodeEngine(model, **GEO, eos_token_id=eos) as pe:
        ref = pe.generate(p, 16)
    with DecodeEngine(model, **GEO, eos_token_id=eos,
                      draft_model=draft, speculate_k=K) as eng:
        eng.warmup()
        got = eng.generate(p, 16)
        assert got == ref and got[-1] == eos and len(got) < 16
        assert _quiesced(eng.stats())


def test_int8_kv_speculative_identity(model, draft):
    """Bit-identity holds over the int8 (kq, ks, vq, vs) pool layout on
    both pools (the draft pool shares the engine's quant mode). The
    reference is the dense `generate()` path — proven bit-identical to
    the plain paged engine in test_decode_engine — so the int8 aval set
    (its own executables) is compiled ONCE, for the spec engine only."""
    from paddle_tpu.models import GenerationConfig, generate

    model.cache_quant = "int8"
    draft.cache_quant = "int8"
    geo8 = {**GEO, "decode_buckets": (2,), "prefix_cache": False}
    try:
        with DecodeEngine(model, **geo8, draft_model=draft,
                          speculate_k=K) as se:
            se.warmup()
            assert se.pool.quant == "int8"
            assert se.draft_pool.quant == "int8"
            for seed, n in ((10, 9), (11, 12)):
                p = _prompt(seed)
                ref = generate(model, p[None], GenerationConfig(
                    max_new_tokens=n, use_cache=True)).numpy()
                assert se.generate(p, n) == list(ref[0, len(p):])
            assert _quiesced(se.stats())
    finally:
        del model.cache_quant
        del draft.cache_quant


def test_speculation_composes_with_prefix_sharing(spec, plain):
    """Prefix sharing + speculation (the module engines run with the
    prefix cache on): full-hit joiners skip prefill — the DRAFT catches
    up over the committed tokens instead — the shared mid-block tail
    still COWs before the first speculative write, and everything stays
    bit-identical to plain decode."""
    p = _prompt(12, 6)             # mid-block tail (6 % 8): COW trigger
    ref = plain.generate(p, 10)
    base = spec.stats()
    assert spec.generate(p, 10) == ref            # publisher
    a, b = spec.submit(p, 10), spec.submit(p, 10)  # full hits
    assert a.result() == ref and b.result() == ref
    st = spec.stats()
    assert st["prefix_cache"]["full_hits"] \
        - base["prefix_cache"]["full_hits"] == 2
    assert st["cow_copies"] - base["cow_copies"] >= 3   # tail COWs
    assert st["speculative"]["committed"] \
        > base["speculative"]["committed"]
    # full hitters never target-prefilled: the draft caught up alone
    assert st["speculative"]["catchup_chunks"] \
        - base["speculative"]["catchup_chunks"] >= 3
    assert _quiesced(st)


def test_max_length_and_short_tail_fall_back_to_plain(model, draft,
                                                      plain):
    """The two plain-fallback branches: a generation driven to the very
    end of max_length (verify rows may no longer fit the block table —
    whether a plain tail step actually runs depends on where the last
    speculation round lands, so the assertion is bit-identity), and a
    1-token remainder (remaining == 1 is deterministically one plain
    step, never a speculation round)."""
    p = _prompt(13, 8)
    n = GEO["max_length"] - len(p)         # decode to the very end: 40
    with DecodeEngine(model, **GEO, draft_model=draft,
                      speculate_k=K) as eng:
        eng.warmup()
        assert eng.generate(p, n) == plain.generate(p, n)
        st = eng.stats()
        assert st["speculative"]["committed"] > 0
        # remaining == 1 after prefill: guaranteed plain step, zero
        # speculation rounds for this sequence
        before = st["speculative"]["rounds"]
        assert eng.generate(_prompt(14), 2) == plain.generate(_prompt(14), 2)
        st = eng.stats()
        assert st["steps"] >= 1
        assert st["speculative"]["rounds"] == before
        assert _quiesced(st)


# ---------------------------------------------------------------------------
# executables, reservation, stats
# ---------------------------------------------------------------------------

def test_compile_once_per_bucket_including_spec_programs(spec):
    """After warmup, traffic at every bucket size never builds (or
    disk-loads) another executable: propose/verify/draft-prefill are
    part of the warm set — the zero-retrace invariant the injector's
    tpu-san phase enforces end-to-end."""
    before = dict(spec.stats()["compiles"])
    streams = [spec.submit(_prompt(20 + i), 5) for i in range(3)]
    for s in streams:
        s.result()
    spec.generate(_prompt(24), 5)
    assert spec.stats()["compiles"] == before


def test_draft_worst_case_infeasible_refused(model, draft):
    """A request whose draft worst case can never fit the draft pool is
    refused synchronously with ValueError (no warmup, no dispatch —
    the admission math alone)."""
    with DecodeEngine(model, **{**GEO, "prefix_cache": False},
                      draft_model=draft, speculate_k=K,
                      draft_num_blocks=1 + 4) as eng:
        with pytest.raises(ValueError):
            eng.submit(_prompt(30, 8), 40)


@pytest.mark.slow
def test_draft_pool_reservation_gates_admission(model, draft):
    """A tight draft pool delays (never breaks) admission — OutOfBlocks
    must never surface from a speculation round. Slow-marked: a
    non-default draft pool is a fresh aval set (its own executables);
    the reservation arithmetic itself runs in every tier-1 test above
    and the typed-refusal path is tier-1 just before this."""
    # a non-default draft pool size changes the pool avals (own
    # executables): one bucket each keeps the compile bill small
    with DecodeEngine(model, **{**GEO, "decode_buckets": (2,),
                                "prefill_buckets": (8,),
                                "prefix_cache": False},
                      draft_model=draft, speculate_k=K,
                      draft_num_blocks=1 + 4) as eng:
        eng.warmup()
        # two sequences of draft worst case 3 blocks each (plen=8,
        # max_new=9, K=3 -> ceil(19/8)) must SERIALIZE on the 4-block
        # draft pool rather than fail mid-flight
        a = eng.submit(_prompt(31, 8), 9)
        b = eng.submit(_prompt(32, 8), 9)
        ra, rb = a.result(), b.result()
        assert len(ra) == 9 and len(rb) == 9
        st = eng.stats()
        assert st["failed"] == 0
        assert st["draft_blocks"]["failed_allocs"] == 0
        assert _quiesced(st)


def test_speculative_stats_and_conservation(spec):
    """The obs-collector payload: acceptance counters are consistent
    (proposed == accepted + rejected, committed == accepted + emitted
    target tokens) and both pools obey their conservation laws."""
    spec.generate(_prompt(40), 8)
    st = spec.stats()
    sp = st["speculative"]
    assert sp["proposed"] == sp["accepted"] + sp["rejected"]
    # each committed token is an accepted proposal or a per-sequence
    # correction/bonus token; truncation can discard accepted proposals
    # (they are NOT rejections), so committed is bounded both ways but
    # equals accepted nowhere in general
    assert 0 < sp["committed"] <= sp["proposed"] + sp["rounds"] * \
        len(GEO["decode_buckets"])
    for pool_key in ("blocks", "draft_blocks"):
        bs = st[pool_key]
        assert bs["allocated"] + bs["free"] + bs["reserved"] == bs["total"]
    assert st["draft_blocks"]["name"] == "draft"
    assert st["blocks"]["name"] == "target"
    lhs = st["admitted"]
    rhs = st["completed"] + st["failed"] + st["timed_out"] + st["cancelled"]
    assert lhs == rhs


def test_speculate_k_zero_or_no_draft_is_plain_greedy(model, draft):
    """speculate_k=0 (or no draft model) is EXACTLY the plain engine:
    no draft pool, no speculative executables, empty counters."""
    with DecodeEngine(model, **GEO, draft_model=draft,
                      speculate_k=0) as eng:
        assert eng.draft_pool is None and eng.draft_model is None
        assert eng.generate(_prompt(41), 6)
        sp = eng.stats()["speculative"]
        assert not sp["enabled"] and sp["rounds"] == 0
        assert "draft_blocks" not in eng.stats()
    with pytest.raises(ValueError):
        DecodeEngine(model, **GEO, draft_model=draft, speculate_k=-1)


def test_draft_catchup_realigns_after_fallback(model, plain):
    """A failed shared speculative dispatch advances the sequence by
    plain isolated decode while the draft's position freezes at the
    last commit — generally NOT block-aligned. The next catch-up must
    round its chunk start DOWN to a block boundary (re-feeding the
    partial block's committed tokens); an unaligned start would shift
    the block-wise scatter and silently corrupt the draft's KV. With
    the draft == target, post-recovery acceptance stays near-perfect —
    corrupted draft KV would collapse it to ~1/vocab."""
    state = {"failed": 0}

    def hook(stage, ids, meta):
        if stage == "verify" and state["failed"] == 0:
            state["failed"] += 1
            raise ValueError("injected verify fault")

    with DecodeEngine(model, **GEO, draft_model=model, speculate_k=K,
                      fault_hook=hook) as eng:
        eng.warmup()
        p = _prompt(50)          # 6 tokens: the draft freezes mid-block
        got = eng.generate(p, 14)
        sp = eng.stats()["speculative"]
        assert state["failed"] == 1 and sp["fallbacks"] == 1
        assert sp["proposed"] > 0
        assert sp["acceptance_rate"] > 0.5
    assert got == plain.generate(p, 14)


def test_draft_vocab_mismatch_refused(model):
    other = gpt("gpt_tiny", **{**TINY, "vocab_size": 101})
    with pytest.raises(ValueError):
        DecodeEngine(model, **GEO, draft_model=other, speculate_k=K)


def test_self_draft_on_mesh_refused(model):
    """A self-draft shares the target's live parameter holders, so
    replicating the draft on a TP mesh would clobber the target's
    just-sharded placement — the constructor must refuse the combination
    before any weight is moved or program compiled."""
    import jax

    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:1]).reshape(1), ("tp",))
    with pytest.raises(ValueError, match="self-draft"):
        DecodeEngine(model, **GEO, draft_model=model, speculate_k=K,
                     mesh=mesh)


def test_unchunkable_catchup_config_refused(model, draft):
    """No block-aligned prefill bucket AND the largest bucket cannot
    span max_length - 1: draft catch-up could need to chunk and
    couldn't — refused at construction, not one request at a time."""
    with pytest.raises(ValueError):
        DecodeEngine(model, **{**GEO, "prefill_buckets": (12,)},
                     draft_model=draft, speculate_k=K)
    # a largest bucket spanning max_length - 1 never chunks: accepted
    DecodeEngine(model, **{**GEO, "prefill_buckets": (12, 47)},
                 draft_model=draft, speculate_k=K).shutdown()
