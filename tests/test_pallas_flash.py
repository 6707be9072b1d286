"""Pallas flash attention vs dense reference (interpret mode on CPU).

Reference test model: OpTest check_output/check_grad numeric comparisons
(test/legacy_test/op_test.py:2755/2963) for flash_attn kernels.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import flash_attention


def _qkv(b=1, s=256, h=2, d=32, seed=0, dtype=np.float32):
    r = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(r.randn(b, s, h, d).astype(dtype))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    ref = jax.nn.dot_product_attention(q, k, v, is_causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grads(causal):
    q, k, v = _qkv(s=128, d=16, seed=1)

    def f(q, k, v):
        return (flash_attention(q, k, v, causal=causal,
                                block_q=64, block_k=64) ** 2).sum()

    def f_ref(q, k, v):
        return (jax.nn.dot_product_attention(q, k, v, is_causal=causal)
                ** 2).sum()

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_flash_uneven_blocks():
    """Rectangular block split (block_q != block_k) and multi-head batch."""
    q, k, v = _qkv(b=2, s=256, h=3, d=16, seed=2)
    ref = jax.nn.dot_product_attention(q, k, v, is_causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s,d", [(300, 64), (200, 32), (130, 128), (97, 16)])
def test_flash_ragged_tail_matches_dense(s, d, causal):
    """Sequence lengths that are NOT multiples of the 128 block width
    (and head dims below it): the public wrapper pads to the block
    grid, the kernels mask the padded tail via `kv_valid`, and fwd
    output matches the unpadded dense reference exactly on the valid
    rows."""
    q, k, v = _qkv(b=1, s=s, h=2, d=d, seed=3)
    ref = jax.nn.dot_product_attention(q, k, v, is_causal=causal)
    out = flash_attention(q, k, v, causal=causal)
    assert out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_ragged_tail_grads(causal):
    """Backward through the padded grid: zero cotangents route through
    the pad/slice pair, the dq/dkv kernels mask padded rows AND padded
    cols (a fully-masked padded row must not leak NaN into valid
    dk/dv), and gradients match dense."""
    q, k, v = _qkv(b=1, s=200, h=2, d=32, seed=4)

    def f(q, k, v):
        return (flash_attention(q, k, v, causal=causal) ** 2).sum()

    def f_ref(q, k, v):
        return (jax.nn.dot_product_attention(q, k, v, is_causal=causal)
                ** 2).sum()

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_flash_supported_accepts_ragged():
    from paddle_tpu.ops.pallas.flash_attention import (
        flash_attention_supported)

    assert flash_attention_supported((1, 300, 2, 64))
    assert flash_attention_supported((1, 130, 2, 128))
    assert not flash_attention_supported((1, 64, 2, 64))    # < one block
    assert not flash_attention_supported((1, 256, 2, 512))  # head too wide


def test_flash_attention_bf16_path():
    """The production dtype: bf16 operands, fp32 accumulation (fwd+bwd)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(0)
    b, s, h, d = 2, 256, 4, 64
    q = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32)).astype(jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32)).astype(jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32)).astype(jnp.bfloat16)

    def naive(q, k, v):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) / np.sqrt(d)
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None], scores, -1e30)
        p = jax.nn.softmax(scores, -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))

    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = naive(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=0.05, atol=0.05)

    # gradients flow through the bf16 kernels
    def loss(q):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    g = jax.grad(loss)(q)
    def ref_loss(q):
        return naive(q, k, v).sum()
    gr = jax.grad(ref_loss)(q)
    np.testing.assert_allclose(np.asarray(g, np.float32),
                               np.asarray(gr, np.float32), rtol=0.1, atol=0.3)

    # mixed-dtype inputs normalize instead of failing
    out2 = flash_attention(q, k, v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(out2, np.float32),
                               np.asarray(out, np.float32), rtol=0.05,
                               atol=0.05)


def test_flash_per_shard_on_a_mesh_matches_unsharded():
    """GSPMD refuses to partition a Mosaic kernel, so on a multi-device
    mesh the engine runs flash per shard (batch over the data axes, heads
    over tp). Attention is independent per row and head: the sharded
    value and grads equal the unsharded ones; a shape the mesh does not
    divide gets no wrapper (the caller falls back to XLA attention)."""
    import functools

    from paddle_tpu.distributed.context_parallel import batch_head_shard_map
    from paddle_tpu.sharding import MeshConfig

    mesh = MeshConfig(fsdp=4, tp=2).build()
    q, k, v = _qkv(b=4, s=128, h=2, d=16, seed=3)
    flash = functools.partial(flash_attention, causal=True, interpret=True)
    sharded = batch_head_shard_map(flash, mesh, q.shape)

    def loss(f, q, k, v):
        return (f(q, k, v) ** 2).sum()

    got = jax.jit(jax.value_and_grad(functools.partial(loss, sharded),
                                     argnums=(0, 1, 2)))(q, k, v)
    want = jax.value_and_grad(functools.partial(loss, flash),
                              argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    assert batch_head_shard_map(flash, mesh, (3, 128, 2, 16)) is None
    assert batch_head_shard_map(flash, mesh, (4, 128, 3, 16)) is None
