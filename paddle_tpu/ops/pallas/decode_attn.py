"""Fused single-position decode attention with in-kernel KV dequant.

Reference analog: the fused masked_multihead_attention decode kernel
(paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu) — one
kernel per decode step covering QK^T, causal mask, softmax and PV over the
whole KV cache.

TPU-native motivation (docs/decode_perf.md): with an int8 KV cache the XLA
path must materialize a bf16 copy of the cache every step (TPU XLA does
not fuse the int8→bf16 convert into dot operands), so int8 reads MORE
bytes than bf16. Here the cache is read as int8 into VMEM and dequantized
in-register, so the HBM bill is genuinely half of bf16's. The workload is
bandwidth-bound at decode shapes (q_len=1), so everything runs on the VPU
as 2-D broadcast/reduce ops — the MXU has nothing to chew on at [1,D], and
per-(batch, head) grid cells keep every block a clean (T, D) tile.

Layout: Mosaic requires the blocked batch/head axes OUT of the last two
dims, so the kernel consumes caches in [B, Hkv, T, D] ("kernel layout",
scales [B, Hkv, T, 1]). Scope: q_len == 1.

STATUS — measured record, NOT wired into the model path: at the decode
bench shapes (bs=8, T=144) the whole attention stack runs an order of
magnitude below HBM spec (latency-bound), the XLA int8-convert path ties
bf16, and this kernel measures 1.9–2.3× slower than XLA's lowering
(docs/decode_perf.md round-5 section). models/gpt.py keeps the XLA
cached-attention impls; this kernel remains the template for genuinely
bytes-bound regimes (T in the thousands).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_VMEM_LIMIT = 64 * 1024 * 1024


def _default_interpret():
    if os.environ.get("PADDLE_TPU_PALLAS_INTERPRET") == "1":
        return True
    return jax.devices()[0].platform != "tpu"


def _kernel(pos_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref, o_ref, *,
            scale):
    # blocks: q [1,1,1,D]; kq/vq [1,1,T,D] (int8 or float); ks/vs
    # [1,1,T,1] f32; o [1,1,1,D]. All math f32 on the VPU.
    q = q_ref[0, 0].astype(jnp.float32)                    # [1, D]
    kf = kq_ref[0, 0].astype(jnp.float32)                  # [T, D]
    ks = ks_ref[0, 0]                                      # [T, 1]
    T = kf.shape[0]
    scores = jnp.sum(kf * q, axis=1, keepdims=True)        # [T, 1]
    scores = scores * ks * scale
    pos = pos_ref[0]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)
    scores = jnp.where(t_idx <= pos, scores, -jnp.inf)
    m = jnp.max(scores, axis=0, keepdims=True)             # [1, 1]
    e = jnp.exp(scores - m)
    p = e / jnp.sum(e, axis=0, keepdims=True)              # [T, 1]
    vf = vq_ref[0, 0].astype(jnp.float32)                  # [T, D]
    vs = vs_ref[0, 0]                                      # [T, 1]
    o = jnp.sum((p * vs) * vf, axis=0, keepdims=True)      # [1, D]
    o_ref[0, 0, 0] = o[0].astype(o_ref.dtype)


def decode_attention(q, kq, ks, vq, vs, pos, interpret=None):
    """q [B,1,H,D]; kq/vq [B,Hkv,T,D] (int8 or float, kernel layout);
    ks/vs [B,Hkv,T,1] f32 dequant scales (ones for float caches); pos
    int32 scalar (global position of the query). Returns [B,1,H,D]."""
    if interpret is None:
        interpret = _default_interpret()
    B, s, H, D = q.shape
    if s != 1:
        raise ValueError("decode_attention handles q_len == 1 only")
    Hkv, T = kq.shape[1], kq.shape[2]
    if H % Hkv:
        raise ValueError(
            f"num_heads {H} must be a multiple of kv heads {Hkv} (an "
            "uneven ratio would silently clamp block indices past the "
            "cache's head axis)")
    rep = H // Hkv
    scale = 1.0 / (D ** 0.5)

    qh = jnp.transpose(q, (0, 2, 1, 3))                    # [B, H, 1, D]
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)
    grid = (B, H)
    q_spec = pl.BlockSpec((1, 1, 1, D), lambda b, h: (b, h, 0, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, 1, T, D), lambda b, h: (b, h // rep, 0, 0),
                           memory_space=pltpu.VMEM)
    sc_spec = pl.BlockSpec((1, 1, T, 1), lambda b, h: (b, h // rep, 0, 0),
                           memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  q_spec, kv_spec, sc_spec, kv_spec, sc_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(pos_arr, qh, kq, ks, vq, vs)
    return jnp.transpose(out, (0, 2, 1, 3))


# ---------------------------------------------------------------------------
# paged (block-table) decode attention — the continuous-batching layout
# ---------------------------------------------------------------------------
#
# The decode engine (inference/decode) keeps the KV cache as a POOL of
# fixed-size blocks ([N, Hkv, BS, D] kernel layout here) and gives every
# sequence a block table: token position p of sequence b lives at pool
# block tables[b, p // BS], row p % BS. Reading the cache through the
# table is a gather; this kernel does the gather IN the block index_map
# (scalar-prefetched tables pick each grid cell's pool block, so only the
# blocks a sequence actually owns ever leave HBM) and accumulates softmax
# online across a sequence's blocks — flash-decoding over a paged cache.
# Per-sequence positions (pos[b]) make it batch-heterogeneous: exactly
# what iteration-level scheduling needs.
#
# Like the dense kernel above it is the measured TPU-native record for
# bytes-bound regimes; the engine's portable path expresses the same
# gather in XLA (`paged_decode_attention(..., use_kernel=False)`), which
# is what CPU tier-1 runs and what docs/decode_perf.md shows winning at
# today's bench shapes.

def _paged_kernel(tables_ref, pos_ref, q_ref, kq_ref, ks_ref, vq_ref,
                  vs_ref, o_ref, m_scr, l_scr, acc_scr, *, scale,
                  block_size, nblocks):
    # grid (B, H, NB), j innermost: scratch carries the online-softmax
    # state (m, l, acc) across a sequence's blocks. Blocks: q [1,1,1,D];
    # kq/vq [1,1,BS,D]; ks/vs [1,1,BS,1]; o [1,1,1,D].
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_scr[0, 0] = -jnp.inf
        l_scr[0, 0] = 0.0
        acc_scr[0, :] = jnp.zeros_like(acc_scr[0, :])

    q = q_ref[0, 0].astype(jnp.float32)                    # [1, D]
    kf = kq_ref[0, 0].astype(jnp.float32)                  # [BS, D]
    ks = ks_ref[0, 0]                                      # [BS, 1]
    scores = jnp.sum(kf * q, axis=1, keepdims=True)        # [BS, 1]
    scores = scores * ks * scale
    pos = pos_ref[b]
    t_idx = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (block_size, 1), 0)
    scores = jnp.where(t_idx <= pos, scores, -jnp.inf)

    m_old = m_scr[0, 0]
    # block 0 always holds position 0 <= pos, so m is finite from j == 0
    # on and the -inf - -inf = NaN corner can never materialize
    m_new = jnp.maximum(m_old, jnp.max(scores))
    # j == 0: alpha = exp(-inf - m_new) = 0, zeroing the (zero) carry-in;
    # a fully-masked later block leaves m_new = m_old, alpha = 1, p = 0
    alpha = jnp.exp(m_old - m_new)
    p = jnp.exp(scores - m_new)                            # [BS, 1]
    vf = vq_ref[0, 0].astype(jnp.float32)                  # [BS, D]
    vs = vs_ref[0, 0]                                      # [BS, 1]
    m_scr[0, 0] = m_new
    l_scr[0, 0] = l_scr[0, 0] * alpha + jnp.sum(p)
    acc_scr[0, :] = acc_scr[0, :] * alpha \
        + jnp.sum((p * vs) * vf, axis=0)

    @pl.when(j == nblocks - 1)
    def _():
        o_ref[0, 0, 0] = (acc_scr[0, :] / l_scr[0, 0]).astype(o_ref.dtype)


def paged_decode_attention(q, kq, ks, vq, vs, tables, pos, *,
                           use_kernel=None, interpret=None):
    """Single-position decode attention over a PAGED (block-table) KV
    pool with per-sequence positions.

    q [B,1,H,D]; kq/vq [N, Hkv, BS, D] pool blocks (int8 or float, kernel
    layout); ks/vs [N, Hkv, BS, 1] f32 dequant scales (ones for float
    pools); tables [B, NB] int32 block tables (unused tail entries must
    point at a reserved block — they are masked, never attended); pos
    [B] int32 per-sequence position of the query. Returns [B,1,H,D].

    `use_kernel=False` (the default off-TPU) computes the identical
    result as an XLA gather + masked softmax — the portable path the
    CPU tier-1 suite exercises; `use_kernel=True` runs the Pallas
    flash-decoding kernel (`interpret=True` to run it anywhere)."""
    B, s, H, D = q.shape
    if s != 1:
        raise ValueError("paged_decode_attention handles q_len == 1 only")
    N, Hkv, BS, _ = kq.shape
    NB = tables.shape[-1]
    if tables.shape != (B, NB):
        raise ValueError(f"tables must be [B, NB], got {tables.shape}")
    if H % Hkv:
        raise ValueError(
            f"num_heads {H} must be a multiple of kv heads {Hkv} (an "
            "uneven ratio would silently clamp block indices past the "
            "pool's head axis)")
    scale = 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = _default_interpret()
    if use_kernel is None:
        use_kernel = not interpret

    if not use_kernel:
        # XLA gather fallback: dense per-sequence view through the table
        rep = H // Hkv
        T = NB * BS

        def view(pool):                       # [N,Hkv,BS,*] -> [B,Hkv,T,*]
            g = pool[tables]                  # [B, NB, Hkv, BS, *]
            g = jnp.swapaxes(g, 1, 2)         # [B, Hkv, NB, BS, *]
            return g.reshape(B, Hkv, T, *pool.shape[3:])

        kf = view(kq).astype(jnp.float32)
        vf = view(vq).astype(jnp.float32)
        ksf, vsf = view(ks), view(vs)
        if rep > 1:
            kf = jnp.repeat(kf, rep, axis=1)
            vf = jnp.repeat(vf, rep, axis=1)
            ksf = jnp.repeat(ksf, rep, axis=1)
            vsf = jnp.repeat(vsf, rep, axis=1)
        qf = jnp.transpose(q, (0, 2, 1, 3)).astype(jnp.float32)  # [B,H,1,D]
        scores = jnp.einsum("bhqd,bhtd->bhqt", qf, kf)
        scores = scores * jnp.swapaxes(ksf, 2, 3) * scale        # [B,H,1,T]
        t_idx = jnp.arange(T, dtype=jnp.int32)
        mask = t_idx[None, None, None, :] <= pos[:, None, None, None]
        scores = jnp.where(mask, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        probs = probs * jnp.swapaxes(vsf, 2, 3)
        out = jnp.einsum("bhqt,bhtd->bhqd", probs, vf)
        return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)

    rep = H // Hkv
    qh = jnp.transpose(q, (0, 2, 1, 3))                    # [B, H, 1, D]
    grid = (B, H, NB)
    q_spec = pl.BlockSpec((1, 1, 1, D), lambda b, h, j, tr, pr: (b, h, 0, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec(
        (1, 1, BS, D), lambda b, h, j, tr, pr: (tr[b, j], h // rep, 0, 0),
        memory_space=pltpu.VMEM)
    sc_spec = pl.BlockSpec(
        (1, 1, BS, 1), lambda b, h, j, tr, pr: (tr[b, j], h // rep, 0, 0),
        memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                # tables, pos
        grid=grid,
        in_specs=[q_spec, kv_spec, sc_spec, kv_spec, sc_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.float32),
                        pltpu.VMEM((1, 1), jnp.float32),
                        pltpu.VMEM((1, D), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, block_size=BS,
                          nblocks=NB),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(pos, jnp.int32),
      qh, kq, ks, vq, vs)
    return jnp.transpose(out, (0, 2, 1, 3))
