"""Flash attention as Pallas TPU kernels.

Reference analog: the CUDA flash-attention kernels
(paddle/phi/kernels/fusion/gpu/flash_attn_kernel.cu, surfaced as
python/paddle/nn/functional/flash_attention.py:146). TPU-native redesign:
three Pallas kernels (fwd, dq, dkv) implementing the FlashAttention-2
recurrence with fp32 accumulators in VMEM:

- forward streams K/V blocks from VMEM against one query block per grid
  step, maintaining the online-softmax (m, l, o) state; saves the final
  logsumexp row statistics for the backward;
- backward follows FA-2: delta = rowsum(do * o) precomputed outside; one
  kernel accumulates dq over K blocks, a second accumulates (dk, dv) over
  Q blocks — no atomics, each output is owned by exactly one grid step.

Layouts: public API is [batch, seq, heads, head_dim] (reference layout);
kernels run on [batch*heads, seq, head_dim]. Causal masking uses global
row/col indices, so the kernels also serve sliding blocks. On non-TPU
backends the same kernels run under `interpret=True` (tests), but callers
should prefer XLA's fused attention there.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# tuned on v5e: 512-square blocks beat 128 by 3-4x (fewer grid steps, the
# MXU stays fed from VMEM); sequence lengths below 512 use one block
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30
# long sequences (s*d near the supported() cap) stage >16MB of K/V/dO in
# VMEM; the chip allows more than Mosaic's 16MB default scoped budget
# (same fix as ops/pallas/weight_only.py)
_VMEM_LIMIT = 64 * 1024 * 1024


def _compiler_params(interpret):
    """Shared Mosaic budget for all three kernels (fwd/dq/dkv must never
    diverge); the interpret backend takes no compiler params."""
    if interpret:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


def _ceil_to(x, m):
    return (x + m - 1) // m * m


def flash_attention_supported(q_shape, causal=True):
    """Whether the Pallas kernel handles this problem (else caller falls
    back to XLA fused attention)."""
    b, s, h, d = q_shape
    # the kernels stage whole K/V (and Q/dO in the backward) per head in
    # VMEM (~16 MB/core): cap s*d so 4 full [s, d] bf16 tensors + block
    # scratch stay within budget; beyond this, use ring attention over cp.
    # Ragged tails (s % 128 != 0) run through the pad+mask path, so only
    # the PADDED length must fit.
    s_pad = _ceil_to(max(s, 128), 128)
    return s >= 128 and d <= 256 and s_pad * d <= (1 << 20)


def pick_block(s):
    """Largest tuned block size dividing s."""
    for blk in (512, 256, 128):
        if s % blk == 0:
            return blk
    raise ValueError(
        f"flash_attention needs seq_len divisible by 128, got {s}; "
        "pad the sequence or use scaled_dot_product_attention")


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_k, kv_valid):
    # matmul operands stay in the INPUT dtype (bf16 in prod) with fp32
    # accumulation — casting operands to fp32 would run the MXU at its
    # fp32 rate (~4x slower on v5e); softmax statistics stay fp32
    q = q_ref[0]                                      # [bq, d]
    mm_dtype = q.dtype
    bq, d = q.shape
    s_k = k_ref.shape[1]
    qi = pl.program_id(1)
    q_lo = qi * bq
    ragged = kv_valid < s_k            # static: aligned shapes skip masking

    o = jnp.zeros((bq, d), jnp.float32)
    m = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)

    def body(j, carry):
        o, m, l = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = None
        if causal or ragged:
            rows = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            mask = (rows >= cols) if causal else (cols < kv_valid)
            if causal and ragged:
                mask &= cols < kv_valid
            s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1, keepdims=True)
        o = o * corr + jax.lax.dot_general(
            p.astype(mm_dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return o, m_new, l

    n_kv = -(-kv_valid // block_k)     # blocks holding any valid K column
    if causal:
        # dynamic upper bound: only blocks intersecting the causal band
        hi = jax.lax.div(q_lo + bq + block_k - 1, block_k)
        hi = jnp.minimum(hi, n_kv)
    else:
        hi = n_kv
    o, m, l = jax.lax.fori_loop(0, hi, body, (o, m, l))

    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (o / l_safe).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l_safe)                   # [bq, 1]


def _fwd(q, k, v, *, scale, causal, block_q, block_k, interpret,
         kv_valid=None):
    bh, s, d = q.shape
    nq = s // block_q
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_k=block_k,
                          kv_valid=s if kv_valid is None else kv_valid),
        grid=(bh, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            # lse rides as [bh, s, 1] — Mosaic block rules want the last two
            # dims (sublane, lane) aligned; lane==1 equals the array dim
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Backward (FlashAttention-2)
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               scale, causal, block_k, kv_valid):
    q = q_ref[0]
    do = do_ref[0]
    mm_dtype = q.dtype
    lse = lse_ref[0]                                   # [bq, 1]
    delta = delta_ref[0]
    bq, d = q.shape
    s_k = k_ref.shape[1]
    q_lo = pl.program_id(1) * bq
    ragged = kv_valid < s_k

    def body(j, dq):
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)
        if causal or ragged:
            rows = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            mask = (rows >= cols) if causal else (cols < kv_valid)
            if causal and ragged:
                mask &= cols < kv_valid
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(mm_dtype)
        return dq + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    n_kv = -(-kv_valid // block_k)
    if causal:
        hi = jax.lax.div(q_lo + bq + block_k - 1, block_k)
        hi = jnp.minimum(hi, n_kv)
    else:
        hi = n_kv
    dq = jax.lax.fori_loop(0, hi, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, *, scale, causal, block_q, kv_valid):
    k = k_ref[0]
    v = v_ref[0]
    mm_dtype = k.dtype
    bk, d = k.shape
    s_q = q_ref.shape[1]
    k_lo = pl.program_id(1) * bk
    ragged = kv_valid < q_ref.shape[1]   # q and k/v share the padded length

    def body(i, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(i * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse_blk = lse_ref[0, pl.ds(i * block_q, block_q), :]   # [bq, 1]
        delta_blk = delta_ref[0, pl.ds(i * block_q, block_q), :]
        s = jax.lax.dot_general(q_blk, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse_blk)                       # [bq, bk]
        if causal or ragged:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0)
            cols = k_lo + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 1)
            mask = (rows >= cols) if causal \
                else jnp.full((block_q, bk), True)
            if ragged:
                # padded K columns never contribute; padded Q rows are
                # masked too so their (garbage) softmax stats cannot leak
                # NaNs into valid dk/dv rows
                mask &= (cols < kv_valid) & (rows < kv_valid)
            p = jnp.where(mask, p, 0.0)
        p_mm = p.astype(mm_dtype)
        dv = dv + jax.lax.dot_general(p_mm, do_blk, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do_blk, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_blk) * scale).astype(mm_dtype)
        dk = dk + jax.lax.dot_general(ds, q_blk, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    if causal:
        # Q blocks strictly above this K block's diagonal see only masked
        # entries: start at the first Q block whose rows reach k_lo
        lo = jax.lax.div(k_lo, block_q)
    else:
        lo = 0
    dk, dv = jax.lax.fori_loop(
        lo, s_q // block_q, body,
        (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd(q, k, v, out, lse, do, *, scale, causal, block_q, block_k,
         interpret, kv_valid=None):
    bh, s, d = q.shape
    kv_valid = s if kv_valid is None else kv_valid
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [bh, s, 1]
    qspec = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM)
    full = pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0),
                        memory_space=pltpu.VMEM)
    row_blk = pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0),
                           memory_space=pltpu.VMEM)
    row_full = pl.BlockSpec((1, s, 1), lambda b, i: (b, 0, 0),
                            memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, kv_valid=kv_valid),
        grid=(bh, s // block_q),
        in_specs=[qspec, full, full, qspec, row_blk, row_blk],
        out_specs=[qspec],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), q.dtype)],
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(q, k, v, do, lse, delta)[0]

    kspec = pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0),
                         memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, kv_valid=kv_valid),
        grid=(bh, s // block_k),
        in_specs=[full, kspec, kspec, full, row_full, row_full],
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), v.dtype)],
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper, [B, S, H, D] public layout
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret, kv_valid):
    out, _ = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, interpret=interpret, kv_valid=kv_valid)
    return out


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k, interpret,
                    kv_valid):
    out, lse = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q,
                    block_k=block_k, interpret=interpret, kv_valid=kv_valid)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(scale, causal, block_q, block_k, interpret, kv_valid,
                    res, do):
    q, k, v, out, lse = res
    return _bwd(q, k, v, out, lse, do, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k, interpret=interpret,
                kv_valid=kv_valid)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, *, causal=True, scale=None, block_q=None,
                    block_k=None, interpret=None):
    """Flash attention on [batch, seq, heads, head_dim] arrays.

    Differentiable (FlashAttention-2 backward). `interpret=None` auto-picks
    interpreter mode off-TPU so the same kernels run in CPU tests.

    Ragged tails are handled by padding: a sequence length that is not a
    multiple of 128 is zero-padded up to the next kernel-aligned length
    and a static `kv_valid` watermark masks the padded keys out of the
    softmax (and the padded rows/columns out of the backward), so the
    sliced result is exactly the unpadded attention.
    """
    b, s, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # one operand dtype: the kernels run matmuls in the input dtype (fp32
    # accumulation), so mixed-precision callers normalize to q's dtype here
    if k.dtype != q.dtype:
        k = k.astype(q.dtype)
    if v.dtype != q.dtype:
        v = v.astype(q.dtype)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    s_pad = _ceil_to(max(s, 128), 128)
    if s_pad != s:
        # pad OUTSIDE the custom_vjp: autodiff of pad/slice routes the
        # padded rows' zero cotangents for free
        pad = [(0, 0), (0, s_pad - s), (0, 0), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    block_q = block_q or min(DEFAULT_BLOCK_Q, pick_block(s_pad))
    block_k = block_k or min(DEFAULT_BLOCK_K, pick_block(s_pad))
    if s_pad % block_q or s_pad % block_k:
        raise ValueError(f"seq len {s_pad} must divide block sizes "
                         f"({block_q}, {block_k})")

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s_pad, d)

    out = _flash(to_bh(q), to_bh(k), to_bh(v), scale, bool(causal),
                 int(block_q), int(block_k), bool(interpret), int(s))
    out = out.reshape(b, h, s_pad, d).transpose(0, 2, 1, 3)
    return out[:, :s] if s_pad != s else out


# ---------------------------------------------------------------------------
# Position-masked variants (ring / context-parallel steps)
# ---------------------------------------------------------------------------
# A ring step holds a LOCAL query shard and one visiting KV shard whose
# global positions are arbitrary (zigzag causal placement rotates
# non-contiguous chunks). Masking therefore runs off explicit int32
# position vectors — q_pos as a [s_q, 1] column, k_pos as a [1, s_k] row,
# so a [bq, bk] mask is one broadcast compare — instead of grid-derived
# indices. These kernels are building blocks: distributed/
# context_parallel.py owns the online-softmax merge across steps and the
# custom_vjp, so no vjp is attached here.


def _fwd_pos_kernel(q_ref, k_ref, v_ref, qpos_ref, kpos_ref, o_ref,
                    lse_ref, *, scale, causal, block_k):
    q = q_ref[0]
    mm_dtype = q.dtype
    bq, d = q.shape
    s_k = k_ref.shape[1]
    qp = qpos_ref[...]                                 # [bq, 1]

    o = jnp.zeros((bq, d), jnp.float32)
    m = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)

    def body(j, carry):
        o, m, l = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = None
        if causal:
            kp = kpos_ref[:, pl.ds(j * block_k, block_k)]   # [1, bk]
            mask = qp >= kp                                 # [bq, bk]
            s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1, keepdims=True)
        o = o * corr + jax.lax.dot_general(
            p.astype(mm_dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return o, m_new, l

    o, m, l = jax.lax.fori_loop(0, s_k // block_k, body, (o, m, l))
    # a fully-masked row (whole visiting shard in this row's future) keeps
    # l == 0: emit out = 0 with lse ~ -inf so the cross-step lse-merge
    # assigns it zero weight
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (o / l_safe).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l_safe)


def flash_fwd_pos(q, k, v, q_pos, k_pos, *, scale, causal=True,
                  block_q=None, block_k=None, interpret=None):
    """One ring-step forward on [bh, s, d] shards: returns the UNMERGED
    partial (out, lse) of local queries against one visiting KV shard,
    masked by global positions (`q_pos` [s_q], `k_pos` [s_k], int32)."""
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = block_q or min(DEFAULT_BLOCK_Q, pick_block(s_q))
    block_k = block_k or min(DEFAULT_BLOCK_K, pick_block(s_k))
    qp = q_pos.astype(jnp.int32).reshape(s_q, 1)
    kp = k_pos.astype(jnp.int32).reshape(1, s_k)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_pos_kernel, scale=scale, causal=causal,
                          block_k=block_k),
        grid=(bh, s_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, s_k, d), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, s_k, d), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_q, 1), lambda b, i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, s_k), lambda b, i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s_q, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(q, k, v, qp, kp)
    return out, lse


def _dq_pos_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   qpos_ref, kpos_ref, dq_ref, *, scale, causal, block_k):
    q = q_ref[0]
    do = do_ref[0]
    mm_dtype = q.dtype
    lse = lse_ref[0]
    delta = delta_ref[0]
    bq, d = q.shape
    s_k = k_ref.shape[1]
    qp = qpos_ref[...]

    def body(j, dq):
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)
        if causal:
            kp = kpos_ref[:, pl.ds(j * block_k, block_k)]
            p = jnp.where(qp >= kp, p, 0.0)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(mm_dtype)
        return dq + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, s_k // block_k, body,
                           jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_pos_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    qpos_ref, kpos_ref, dk_ref, dv_ref, *, scale, causal,
                    block_q):
    k = k_ref[0]
    v = v_ref[0]
    mm_dtype = k.dtype
    bk, d = k.shape
    s_q = q_ref.shape[1]
    kp = kpos_ref[...]                                 # [1, bk]

    def body(i, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(i * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse_blk = lse_ref[0, pl.ds(i * block_q, block_q), :]
        delta_blk = delta_ref[0, pl.ds(i * block_q, block_q), :]
        s = jax.lax.dot_general(q_blk, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse_blk)
        if causal:
            qp = qpos_ref[pl.ds(i * block_q, block_q), :]   # [bq, 1]
            p = jnp.where(qp >= kp, p, 0.0)
        p_mm = p.astype(mm_dtype)
        dv = dv + jax.lax.dot_general(p_mm, do_blk, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do_blk, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_blk) * scale).astype(mm_dtype)
        dk = dk + jax.lax.dot_general(ds, q_blk, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        0, s_q // block_q, body,
        (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def flash_bwd_pos(q, k, v, do, lse, delta, q_pos, k_pos, *, scale,
                  causal=True, block_q=None, block_k=None, interpret=None):
    """One ring-step backward: (dq, dk, dv) of this step's partial
    contribution, given the GLOBAL (merged) `lse` and
    `delta = rowsum(do * out_merged)` — the FA-2 identity makes each
    step's gradient independently computable from global statistics."""
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = block_q or min(DEFAULT_BLOCK_Q, pick_block(s_q))
    block_k = block_k or min(DEFAULT_BLOCK_K, pick_block(s_k))
    qp = q_pos.astype(jnp.int32).reshape(s_q, 1)
    kp = k_pos.astype(jnp.int32).reshape(1, s_k)
    qspec = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM)
    kfull = pl.BlockSpec((1, s_k, d), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.VMEM)
    row_blk = pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0),
                           memory_space=pltpu.VMEM)
    qpos_blk = pl.BlockSpec((block_q, 1), lambda b, i: (i, 0),
                            memory_space=pltpu.VMEM)
    kpos_full = pl.BlockSpec((1, s_k), lambda b, i: (0, 0),
                             memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        functools.partial(_dq_pos_kernel, scale=scale, causal=causal,
                          block_k=block_k),
        grid=(bh, s_q // block_q),
        in_specs=[qspec, kfull, kfull, qspec, row_blk, row_blk,
                  qpos_blk, kpos_full],
        out_specs=[qspec],
        out_shape=[jax.ShapeDtypeStruct((bh, s_q, d), q.dtype)],
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(q, k, v, do, lse, delta, qp, kp)[0]

    qfull = pl.BlockSpec((1, s_q, d), lambda b, j: (b, 0, 0),
                         memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0),
                         memory_space=pltpu.VMEM)
    row_full = pl.BlockSpec((1, s_q, 1), lambda b, j: (b, 0, 0),
                            memory_space=pltpu.VMEM)
    qpos_full = pl.BlockSpec((s_q, 1), lambda b, j: (0, 0),
                             memory_space=pltpu.VMEM)
    kpos_blk = pl.BlockSpec((1, block_k), lambda b, j: (0, j),
                            memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_pos_kernel, scale=scale, causal=causal,
                          block_q=block_q),
        grid=(bh, s_k // block_k),
        in_specs=[qfull, kspec, kspec, qfull, row_full, row_full,
                  qpos_full, kpos_blk],
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((bh, s_k, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, s_k, d), v.dtype)],
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(q, k, v, do, lse, delta, qp, kp)
    return dq, dk, dv
