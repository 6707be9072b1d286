"""The plain decode step as one batched forward (ISSUE 29).

`DecodeEngine._decode_fn` runs a bucket's sequences through ONE forward
(`_forward_bucket`: the per-sequence step batched by `vmap`, the pool read
un-batched) and writes the B new rows of every pool tensor by one scatter.
What the program no longer gives by its shape, these cases observe: which
bytes of the pool a dispatch may change, that a dispatch repeats itself, that
a sampled row draws what it draws alone, how far a row's logits lie from the
row run alone, and that an executable cached under the scanned step's key is
never served in the batched step's place.
The step executables are called directly on a pool of random rows, so every
block holds bytes that a stray write would change.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import DecodeEngine
from paddle_tpu.inference.decode.block_pool import RESERVED_BLOCKS
from paddle_tpu.models import gpt

TINY = dict(vocab_size=97, hidden_size=48, num_heads=4, num_kv_heads=2,
            num_layers=2, rope=True, swiglu=True, rms_norm=True,
            max_position_embeddings=64, tie_word_embeddings=False)
MAX_LENGTH, BLOCK, BUCKET, LIVE = 48, 8, 8, 5

# Widest gap between a row's logits in a bucket of 8 and the same row run
# alone, as a share of the largest logit of the row. On the CPU backend
# both read 0 (the batched matmuls are row-stable there); the limits are
# what the arithmetic allows a backend that is not: a float32 sum of 48 to
# 192 terms reordered, and one bfloat16 rounding (2**-8) of an activation
# carried through two layers.
LOGIT_TOLERANCE = {"float32": 1e-5, "bfloat16": 3e-2}


@pytest.fixture(scope="module", autouse=True)
def _compile_cache_dir(tmp_path_factory):
    """The module's own on-disk compile cache (the engines' default)."""
    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_COMPILATION_CACHE_DIR",
              str(tmp_path_factory.mktemp("batched-step-cache")))
    yield
    mp.undo()


def _engine(dtype="float32", **kw):
    import jax.numpy as jnp

    paddle.seed(7)
    m = gpt("gpt_tiny", **TINY)
    m.eval()
    if dtype != "float32":
        for _, p in m.named_parameters():
            p._value = p._value.astype(jnp.dtype(dtype))
    kw.setdefault("decode_buckets", (1, BUCKET))
    return DecodeEngine(m, max_length=MAX_LENGTH, block_size=BLOCK,
                        prefill_buckets=(8,), default_timeout=60.0, **kw)


@pytest.fixture(scope="module")
def eng():
    e = _engine()
    yield e
    e.shutdown(drain_timeout=10.0)


def _random_pool(eng, seed):
    import jax
    import jax.numpy as jnp

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    return [tuple(jax.random.normal(next(keys), t.shape, jnp.float32)
                  .astype(t.dtype) for t in layer)
            for layer in eng.pool.tensors]


def _bucket_inputs(eng, live, bucket, seed):
    """Step inputs for `live` sequences in a bucket of `bucket` slots: each
    live slot owns its own run of blocks and stands at its own position
    inside them; a padded slot carries table 0, position 0, token 0."""
    rng = np.random.RandomState(seed)
    nb = eng._nb
    tokens = np.zeros(bucket, np.int32)
    positions = np.zeros(bucket, np.int32)
    tables = np.zeros((bucket, nb), np.int32)
    for i in range(live):
        tables[i] = RESERVED_BLOCKS + i * nb + np.arange(nb)
        positions[i] = rng.randint(1, MAX_LENGTH - 1)
        tokens[i] = rng.randint(1, TINY["vocab_size"])
    assert tables.max() < eng.pool.num_blocks
    return tokens, positions, tables, np.zeros(bucket, np.int32)


def _dispatch(eng, pool_ts, tokens, positions, tables, aids, samp=None):
    """The step consumes the pool it is given (donated): it gets a copy, so
    the caller can dispatch from `pool_ts` again and hold it beside the
    result."""
    import jax
    import jax.numpy as jnp

    bucket = len(tokens)
    pv, bv = eng._weights()
    new_pool, nxt = eng._decode_fn(bucket)(
        pv, bv, eng._adapter_stacks(),
        jax.tree_util.tree_map(jnp.copy, pool_ts), tokens, positions,
        tables, aids, eng._hist_pack([], bucket),
        eng._samp_pack([], bucket) if samp is None else samp)
    return new_pool, np.asarray(nxt)


def _bytes(pool_ts):
    return [np.asarray(t).view(np.uint8) for layer in pool_ts for t in layer]


def test_a_bucket_writes_its_live_rows_and_pads_into_block_zero(eng):
    """5 live + 3 padded slots: every live block keeps its bytes but for
    the 5 rows at `(table[pos // bs], pos % bs)`, which all change, and
    nothing outside them changes but in reserved block 0."""
    pool = _random_pool(eng, 1)
    tokens, positions, tables, aids = _bucket_inputs(eng, LIVE, BUCKET, 2)
    new_pool, _ = _dispatch(eng, pool, tokens, positions, tables, aids)
    written = {(int(tables[i, positions[i] // BLOCK]),
                int(positions[i] % BLOCK)) for i in range(LIVE)}
    assert len(written) == LIVE and all(b >= RESERVED_BLOCKS
                                        for b, _ in written)
    for before, after in zip(_bytes(pool), _bytes(new_pool)):
        changed = {(int(b), int(o)) for b, o in
                   zip(*np.nonzero((before != after).any(axis=-1)))}
        assert written <= changed, written - changed
        assert all(b < RESERVED_BLOCKS for b, _ in changed - written), \
            sorted(changed - written)


def test_a_dispatch_repeats_itself(eng):
    """The same batch composition from the same pool, dispatched twice:
    identical tokens and identical pool bytes, padding sink included."""
    pool = _random_pool(eng, 3)
    args = _bucket_inputs(eng, LIVE, BUCKET, 4)
    pool_1, nxt_1 = _dispatch(eng, pool, *args)
    pool_2, nxt_2 = _dispatch(eng, pool, *args)
    assert nxt_1.tolist() == nxt_2.tolist()
    for a, b in zip(_bytes(pool_1), _bytes(pool_2)):
        assert np.array_equal(a, b)


def test_a_sampled_rows_draw_is_its_own_whatever_the_slot(eng):
    """Sampled rows (seed and counter a row) in a bucket of 8 against each
    row alone: the same token. The session's `rbg` keys draw other bits
    under `vmap`, so the step samples row by row."""
    pool = _random_pool(eng, 7)
    tokens, positions, tables, aids = _bucket_inputs(eng, BUCKET, BUCKET, 8)
    samp = eng._samp_pack([], BUCKET)
    samp.update(greedy=np.zeros_like(samp["greedy"]),
                seed=np.full_like(samp["seed"], 77),
                ctr=np.arange(BUCKET, dtype=samp["ctr"].dtype),
                temp=np.full_like(samp["temp"], 0.8),
                top_k=np.full_like(samp["top_k"], 12))
    _, together = _dispatch(eng, pool, tokens, positions, tables, aids, samp)
    alone = [int(_dispatch(
        eng, pool, tokens[i:i + 1], positions[i:i + 1], tables[i:i + 1],
        aids[i:i + 1], {k: v[i:i + 1] for k, v in samp.items()})[1][0])
        for i in range(BUCKET)]
    assert together.tolist() == alone
    assert len(set(alone)) > 2          # draws, not one arg-max


@pytest.mark.parametrize("dtype", sorted(LOGIT_TOLERANCE))
def test_a_rows_logits_in_a_bucket_against_the_row_alone(dtype):
    """`_forward_bucket` over 8 rows against the same program over each
    row alone: logits within `LOGIT_TOLERANCE` of the row's largest, and
    the new cache rows within the same share of theirs."""
    import jax

    e = _engine(dtype)
    try:
        pool = _random_pool(e, 5)
        tokens, positions, tables, aids = _bucket_inputs(e, BUCKET, BUCKET, 6)
        pv, bv = e._weights()
        forward = jax.jit(e._forward_bucket)

        def run(rows):
            logits, new = forward(pv, bv, {}, pool, tokens[rows],
                                  positions[rows], tables[rows], aids[rows])
            return np.asarray(logits), [np.asarray(r, np.float32)
                                        for layer in new for r in layer]

        together, rows_together = run(slice(None))
        assert together.dtype == np.float32
        assert together.shape == (BUCKET, TINY["vocab_size"])
        tol = LOGIT_TOLERANCE[dtype]
        for i in range(BUCKET):
            alone, rows_alone = run(slice(i, i + 1))
            gap = np.abs(together[i] - alone[0]).max()
            assert gap <= tol * np.abs(alone[0]).max(), (i, gap)
            for got, want in zip(rows_together, rows_alone):
                assert np.abs(got[i] - want[0]).max() \
                    <= tol * max(1.0, np.abs(want[0]).max()), i
    finally:
        e.shutdown(drain_timeout=10.0)


def test_the_scanned_steps_cache_key_does_not_serve_the_batched_step(
        tmp_path, monkeypatch):
    """A persistent cache filled under the parent's keys (a decode step
    keyed on tag, fingerprint and avals alone): the batched step is built
    anew beside it, the prefill executable is served from it, and what
    the batched step stored serves the next engine."""
    from paddle_tpu.jit import aot

    cache = aot.CompileCache(str(tmp_path))
    real, keyed_as_parent, sources = aot.compile_jit, [True], {}

    def compile_jit(fn, avals, *, tag, extra_key=None, **kw):
        if tag.startswith("decode-step-b"):
            assert extra_key is not None
            if keyed_as_parent[0]:
                extra_key = None
        out = real(fn, avals, tag=tag, extra_key=extra_key, **kw)
        sources[tag] = out[1]
        return out

    monkeypatch.setattr(aot, "compile_jit", compile_jit)

    def build():
        sources.clear()
        e = _engine(compile_cache=cache, decode_buckets=(1, 2))
        try:
            e._decode_fn(2)
            e._prefill_fn(8)
        finally:
            e.shutdown(drain_timeout=10.0)
        return dict(sources)

    assert build() == {"decode-step-b2": "compiled",
                       "decode-prefill-p8": "compiled"}
    keyed_as_parent[0] = False
    assert build() == {"decode-step-b2": "compiled",
                       "decode-prefill-p8": "disk"}
    assert build() == {"decode-step-b2": "disk",
                       "decode-prefill-p8": "disk"}
