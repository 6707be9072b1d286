"""Block-sparse flash attention (Pallas/Mosaic).

Reference analog: /root/reference/paddle/phi/kernels/sparse/gpu/
fused_attention_kernel.cu (CSR-pattern attention). TPU-first redesign: the
token-level CSR pattern is coarsened to a [num_q_blocks, num_k_blocks] block
pattern; the kernel runs flash-style online softmax visiting ONLY the active
K/V blocks of each Q block, driven by a per-Q-block index table. Compute and
HBM traffic scale with nnz blocks, not S² — the same shape as the CUDA
kernel's gains, expressed MXU-natively.

The dense-per-active-block jnp formulation (`_bs_reference`) doubles as the
CPU/interpret fallback AND the custom-vjp backward (exact gradients, O(nnz)
compute) so the Pallas forward stays simple.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

__all__ = ["block_sparse_attention", "csr_to_block_tables"]


def csr_to_block_tables(offset, columns, seq_len, block_size):
    """Host-side: token CSR pattern -> (block_idx [nq, max_nb] int32 padded
    with 0, block_cnt [nq] int32, exact: bool).

    `exact` is True when the token pattern is exactly the union of full
    (block_size x block_size) tiles — then the block kernel reproduces the
    CSR semantics bit-for-bit; otherwise the caller must apply an in-block
    elementwise correction (we fall back to the SDDMM path).
    """
    offset = np.asarray(offset).ravel()
    columns = np.asarray(columns).ravel()
    nq = seq_len // block_size
    blocks = [set() for _ in range(nq)]
    rows_per_block = [[set() for _ in range(seq_len // block_size)]
                      for _ in range(nq)]
    for r in range(seq_len):
        cols = columns[offset[r]:offset[r + 1]]
        qb = r // block_size
        for c in cols:
            kb = int(c) // block_size
            blocks[qb].add(kb)
            rows_per_block[qb][kb].add((r % block_size, int(c) % block_size))
    exact = all(
        len(rows_per_block[qb][kb]) == block_size * block_size
        for qb in range(nq) for kb in blocks[qb])
    max_nb = max((len(b) for b in blocks), default=0) or 1
    idx = np.zeros((nq, max_nb), np.int32)
    cnt = np.zeros((nq,), np.int32)
    for qb, b in enumerate(blocks):
        srt = sorted(b)
        idx[qb, :len(srt)] = srt
        cnt[qb] = len(srt)
    return idx, cnt, exact


def _bs_reference(q, k, v, block_idx, block_cnt, *, scale, block_size):
    """Dense-per-active-block jnp formulation. q/k/v: [BH, S, D].
    Visits only listed blocks: compute is O(nq * max_nb * block²)."""
    bh, s, d = q.shape
    bs = block_size
    nq, max_nb = block_idx.shape
    qb = q.reshape(bh, nq, bs, d)
    kb = k.reshape(bh, s // bs, bs, d)
    vb = v.reshape(bh, s // bs, bs, d)
    kg = kb[:, block_idx]                      # [BH, nq, max_nb, bs, d]
    vg = vb[:, block_idx]
    logits = jnp.einsum("bnqd,bnmkd->bnqmk", qb, kg,
                        preferred_element_type=jnp.float32) * scale
    alive = (jnp.arange(max_nb)[None, :]
             < block_cnt[:, None])             # [nq, max_nb]
    logits = jnp.where(alive[None, :, None, :, None], logits, _NEG_INF)
    flat = logits.reshape(bh, nq, bs, max_nb * bs)
    m = flat.max(-1, keepdims=True)
    p = jnp.exp(flat - m)
    p = jnp.where(flat <= _NEG_INF / 2, 0.0, p)
    den = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    p = (p / den).astype(q.dtype)
    out = jnp.einsum("bnqmk,bnmkd->bnqd",
                     p.reshape(bh, nq, bs, max_nb, bs), vg,
                     preferred_element_type=jnp.float32)
    return out.reshape(bh, s, d).astype(q.dtype)


def _bs_fwd_kernel(cnt_ref, idx_ref, q_ref, k_ref, v_ref, o_ref, *,
                   scale, block_size):
    q = q_ref[0]                                  # [bq, d]
    mm_dtype = q.dtype
    bq, d = q.shape
    qi = pl.program_id(1)
    n = cnt_ref[qi]

    o = jnp.zeros((bq, d), jnp.float32)
    m = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)

    def body(j, carry):
        o, m, l = carry
        blk = idx_ref[qi, j]
        k_blk = k_ref[0, pl.ds(blk * block_size, block_size), :]
        v_blk = v_ref[0, pl.ds(blk * block_size, block_size), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1, keepdims=True)
        o = o * corr + jax.lax.dot_general(
            p.astype(mm_dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return o, m_new, l

    o, m, l = jax.lax.fori_loop(0, n, body, (o, m, l))
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _bs_pallas(q, k, v, block_idx, block_cnt, *, scale, block_size,
               interpret):
    bh, s, d = q.shape
    nq = s // block_size
    kwargs = {}
    if not interpret:
        smem = pltpu.SMEM
        vmem = pltpu.VMEM
        kwargs["in_specs"] = [
            pl.BlockSpec(memory_space=smem),
            pl.BlockSpec(memory_space=smem),
            pl.BlockSpec((1, block_size, d), lambda b, i: (b, i, 0),
                         memory_space=vmem),
            pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0),
                         memory_space=vmem),
            pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0),
                         memory_space=vmem),
        ]
        kwargs["out_specs"] = pl.BlockSpec(
            (1, block_size, d), lambda b, i: (b, i, 0), memory_space=vmem)
    else:
        kwargs["in_specs"] = [
            pl.BlockSpec(block_cnt.shape, lambda b, i: (0,)),
            pl.BlockSpec(block_idx.shape, lambda b, i: (0, 0)),
            pl.BlockSpec((1, block_size, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),
        ]
        kwargs["out_specs"] = pl.BlockSpec((1, block_size, d),
                                           lambda b, i: (b, i, 0))
    return pl.pallas_call(
        functools.partial(_bs_fwd_kernel, scale=scale,
                          block_size=block_size),
        grid=(bh, nq),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        interpret=interpret,
        **kwargs,
    )(block_cnt, block_idx, q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def block_sparse_attention(q, k, v, block_idx, block_cnt, scale,
                           block_size, interpret=False):
    """q/k/v: [BH, S, D]; block_idx [nq, max_nb] int32 (padded), block_cnt
    [nq] int32. Returns [BH, S, D]."""
    return _bs_forward(q, k, v, block_idx, block_cnt, scale, block_size,
                       interpret)


def _bs_forward(q, k, v, block_idx, block_cnt, scale, block_size, interpret):
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu or interpret:
        return _bs_pallas(q, k, v, block_idx, block_cnt, scale=scale,
                          block_size=block_size, interpret=not on_tpu)
    return _bs_reference(q, k, v, block_idx, block_cnt, scale=scale,
                         block_size=block_size)


def _bs_fwd_rule(q, k, v, block_idx, block_cnt, scale, block_size,
                 interpret):
    out = _bs_forward(q, k, v, block_idx, block_cnt, scale, block_size,
                      interpret)
    return out, (q, k, v, block_idx, block_cnt)


def _bs_bwd_rule(scale, block_size, interpret, res, g):
    # exact gradients through the dense-per-active-block formulation —
    # O(nnz-blocks) compute, mirrors the Pallas forward's visit set
    q, k, v, block_idx, block_cnt = res
    f = lambda q_, k_, v_: _bs_reference(q_, k_, v_, block_idx, block_cnt,
                                         scale=scale, block_size=block_size)
    _, vjp = jax.vjp(f, q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None, None


block_sparse_attention.defvjp(_bs_fwd_rule, _bs_bwd_rule)
