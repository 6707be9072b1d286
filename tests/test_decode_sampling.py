"""In-graph per-request sampling (PR 18): counter-based RNG determinism
across engine restart and mid-stream resume, greedy equivalence at
temperature -> 0, scheduler-side stop-sequence truncation with the
hold-back invariant, and a chi-square property check of the top-p
nucleus mass against solo `jax.random.categorical`.

Engines are module-scoped on one on-disk compile cache (the
test_decode_prefix idiom) so the file stays cheap; the pure-math
property tests never build an engine at all.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import DecodeEngine, SamplingParams
from paddle_tpu.inference import sampling as samp
from paddle_tpu.models import gpt

TINY = dict(vocab_size=97, hidden_size=48, num_heads=4, num_kv_heads=2,
            num_layers=2, rope=True, swiglu=True, rms_norm=True,
            max_position_embeddings=64, tie_word_embeddings=False)

#: lean geometry — two decode buckets (solo + the mixed pair), one
#: prefill bucket, prefix cache off (sampling never publishes anyway)
GEO = dict(max_length=32, block_size=8, decode_buckets=(1, 2),
           prefill_buckets=(8,), num_blocks=13, prefix_cache=False,
           default_timeout=60.0)


@pytest.fixture(scope="module", autouse=True)
def _shared_compile_cache(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("decode-sampling-compile-cache"))
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
    e = DecodeEngine(model, **GEO)
    yield e
    e.shutdown(drain_timeout=10.0)


def _prompt(seed, n=8):
    return np.random.RandomState(seed).randint(
        0, TINY["vocab_size"], (n,)).astype(np.int32)


# ---------------------------------------------------------------------------
# SamplingParams: the request-side contract
# ---------------------------------------------------------------------------

def test_params_validation_is_loud():
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=1.5)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(repetition_penalty=0.0)
    with pytest.raises(ValueError):
        SamplingParams(seed=2 ** 32)
    with pytest.raises(ValueError):
        SamplingParams(stop_sequences=[()])
    assert SamplingParams(temperature=0.0).is_greedy()
    assert not SamplingParams(temperature=0.5).is_greedy()


def test_params_wire_roundtrip():
    sp = SamplingParams(temperature=0.7, top_k=11, top_p=0.9,
                        repetition_penalty=1.3, seed=42,
                        stop_sequences=[(5, 6), [7]])
    rt = SamplingParams.from_dict(sp.to_dict())
    assert rt.to_dict() == sp.to_dict()
    assert rt.stop_sequences == ((5, 6), (7,))


# ---------------------------------------------------------------------------
# greedy equivalence: sampling=None, temperature<=0, and the mixed batch
# ---------------------------------------------------------------------------

def test_temperature_zero_is_bitwise_greedy(eng):
    """`temperature <= 0` rides the raw-argmax lane — every other knob
    is inert, so the stream is bit-identical to `sampling=None`."""
    p = _prompt(0)
    ref = eng.generate(p, 10)
    got = eng.generate(p, 10, sampling=SamplingParams(
        temperature=0.0, top_k=3, top_p=0.4, repetition_penalty=2.0,
        seed=99))
    assert got == ref


def test_mixed_batch_leaves_greedy_untouched(eng):
    """A greedy sequence batched WITH a sampled one emits the same
    tokens as solo greedy — knobs are per-sequence values, and the
    greedy row takes the raw-logits argmax behind `jnp.where`."""
    p = _prompt(0)
    ref = eng.generate(p, 10)
    sp = SamplingParams(temperature=0.9, top_k=8, seed=5)
    g = eng.submit(p, 10)
    s = eng.submit(_prompt(1), 10, sampling=sp)
    assert g.result() == ref
    s.result()
    assert eng.stats()["sampled"] >= 1


# ---------------------------------------------------------------------------
# counter-based RNG: restart + resume determinism
# ---------------------------------------------------------------------------

def test_seeded_decode_reproducible_across_restart(model, eng):
    """The per-token key is fold_in(PRNGKey(seed), absolute position) —
    no RNG state lives in the engine, so a second run, a fresh engine
    (restart), and a mid-stream resume all reproduce the stream."""
    p = _prompt(2, 4)  # short: the resume prefill (prompt+committed)
    #                    must still fit the 8-wide prefill bucket
    sp = SamplingParams(temperature=0.9, top_k=12, top_p=0.95,
                        repetition_penalty=1.2, seed=123)
    first = eng.generate(p, 10, sampling=sp)
    assert eng.generate(p, 10, sampling=sp) == first
    # engine restart: identical geometry, fresh process state
    with DecodeEngine(model, **GEO) as e2:
        assert e2.generate(p, 10, sampling=sp) == first
    # failover-style resume: committed prefix in, tail out — the counter
    # base is len(committed), so the tail continues the SAME stream
    # (max_new counts NEW tokens; the router passes max_new - committed)
    resumed = eng.submit(p, 6, resume_committed=first[:4],
                         sampling=sp).result()
    assert resumed == first[4:]


def test_different_seeds_diverge(eng):
    """Sanity that the sampled lane is actually live: across a seed
    sweep at high temperature the streams are not all identical."""
    p = _prompt(3)
    outs = {tuple(eng.generate(p, 10, sampling=SamplingParams(
        temperature=1.5, seed=s))) for s in (1, 2, 3, 4)}
    assert len(outs) > 1


# ---------------------------------------------------------------------------
# stop sequences: scheduler-side truncation + hold-back
# ---------------------------------------------------------------------------

def test_stop_sequence_truncates_before_match(eng):
    """The stream ends 'completed' at the first stop-sequence match and
    never emits the stop tokens themselves."""
    p = _prompt(0)
    ref = eng.generate(p, 12)
    # first bigram whose FIRST occurrence is past position 0, so the
    # truncated stream is non-empty and uniquely determined
    idx, stop = next(
        (i, tuple(ref[i:i + 2])) for i in range(1, len(ref) - 1)
        if tuple(ref[i:i + 2]) not in
        {tuple(ref[j:j + 2]) for j in range(i)})
    s = eng.submit(p, 12, sampling=SamplingParams(
        temperature=0.0, stop_sequences=[stop]))
    assert s.result() == ref[:idx]
    assert s.status == "completed"


def test_holdback_tail_flushes_on_completion(eng):
    """Tokens held back as a possible stop-prefix are flushed when the
    sequence completes without matching: the full stream equals the
    stop-free run bit for bit."""
    p = _prompt(0)
    ref = eng.generate(p, 10)
    # a stop whose first token appears in the stream but which never
    # fully matches, so the hold-back path is exercised then flushed
    never = (int(ref[-1]), TINY["vocab_size"] + 7)
    got = eng.generate(p, 10, sampling=SamplingParams(
        temperature=0.0, stop_sequences=[never]))
    assert got == ref
    assert eng.stats()["stop_hits"] >= 1  # from the truncation test


# ---------------------------------------------------------------------------
# property tests on the pure in-graph math (no engine)
# ---------------------------------------------------------------------------

def test_sample_token_matches_solo_categorical():
    """With greedy=0, rep=1, temp=1: `sample_token` IS
    categorical(fold_in(key, ctr), top_p-filtered logits) — pinned
    token-for-token against the solo construction."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(11)
    logits = jnp.asarray(rng.randn(33) * 2.0, jnp.float32)
    hist = jnp.full((16,), -1, jnp.int32)
    n, seed, p = 64, 7, 0.6

    def one(ctr):
        sp = {"ctr": jnp.int32(ctr), "greedy": jnp.int32(0),
              "rep": jnp.float32(1.0), "seed": jnp.uint32(seed),
              "temp": jnp.float32(1.0), "top_k": jnp.int32(0),
              "top_p": jnp.float32(p)}
        return samp.sample_token(logits, sp, hist)

    toks = jax.vmap(one)(jnp.arange(n, dtype=jnp.int32))
    filt = samp.apply_top_p(logits, jnp.float32(p))
    ref = jax.vmap(lambda c: jax.random.categorical(
        jax.random.fold_in(jax.random.PRNGKey(jnp.uint32(seed)), c),
        filt))(jnp.arange(n, dtype=jnp.int32))
    assert np.array_equal(np.asarray(toks), np.asarray(ref))


def test_top_p_mass_chi_square():
    """Cheap chi-square: empirical draw frequencies over the top-p
    nucleus match softmax of the filtered logits, and NO mass falls
    outside the nucleus."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(3)
    logits = jnp.asarray(rng.randn(17) * 1.5, jnp.float32)
    filt = samp.apply_top_p(logits, jnp.float32(0.7))
    probs = np.asarray(jax.nn.softmax(filt))
    nucleus = probs > 0
    n = 4000
    keys = jax.vmap(lambda c: jax.random.fold_in(
        jax.random.PRNGKey(0), c))(jnp.arange(n, dtype=jnp.int32))
    toks = np.asarray(jax.vmap(
        lambda k: jax.random.categorical(k, filt))(keys))
    counts = np.bincount(toks, minlength=17)
    assert counts[~nucleus].sum() == 0
    exp = probs[nucleus] * n
    chi2 = float((((counts[nucleus] - exp) ** 2) / exp).sum())
    # dof = |nucleus| - 1 <= 16; 99.9th percentile of chi2(16) ~ 39
    assert chi2 < 39.0, f"chi2={chi2} over {int(nucleus.sum())} bins"


def test_filter_helpers_identity_and_mask():
    """k<=0 / p>=1 / penalty==1 are exact identities (the inert pack
    defaults); active knobs mask exactly the expected support."""
    import jax.numpy as jnp

    logits = jnp.asarray([0.1, 2.0, -1.0, 3.0, 0.5], jnp.float32)
    assert np.array_equal(np.asarray(samp.apply_top_k(logits, 0)),
                          np.asarray(logits))
    assert np.array_equal(np.asarray(samp.apply_top_p(logits, 1.0)),
                          np.asarray(logits))
    hist = jnp.asarray([3, -1, -1], jnp.int32)
    assert np.array_equal(
        np.asarray(samp.apply_repetition_penalty(logits, hist, 1.0)),
        np.asarray(logits))
    k2 = np.asarray(samp.apply_top_k(logits, 2))
    assert np.isfinite(k2).sum() == 2 and np.isfinite(k2[[1, 3]]).all()
    pen = np.asarray(samp.apply_repetition_penalty(logits, hist, 2.0))
    assert pen[3] == pytest.approx(1.5) and pen[1] == pytest.approx(2.0)


@pytest.mark.slow
def test_top_p_chi_square_sweep_slow():
    """Heavier sweep across (p, seed) pairs — slow-marked, tier-2."""
    import jax
    import jax.numpy as jnp

    for p, seed in ((0.3, 1), (0.6, 2), (0.9, 3)):
        rng = np.random.RandomState(seed)
        logits = jnp.asarray(rng.randn(29) * 2.0, jnp.float32)
        filt = samp.apply_top_p(logits, jnp.float32(p))
        probs = np.asarray(jax.nn.softmax(filt))
        nucleus = probs > 0
        n = 20000
        toks = np.asarray(jax.vmap(lambda c: jax.random.categorical(
            jax.random.fold_in(jax.random.PRNGKey(jnp.uint32(seed)), c),
            filt))(jnp.arange(n, dtype=jnp.int32)))
        counts = np.bincount(toks, minlength=29)
        assert counts[~nucleus].sum() == 0
        exp = probs[nucleus] * n
        chi2 = float((((counts[nucleus] - exp) ** 2) / exp).sum())
        assert chi2 < 2.5 * max(int(nucleus.sum()) - 1, 1) + 25
