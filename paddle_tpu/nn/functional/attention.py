"""Attention functionals (reference: python/paddle/nn/functional/
flash_attention.py:146, scaled_dot_product_attention; CUDA kernels
phi/kernels/fusion/gpu/flash_attn_kernel.cu).

TPU-native: the default path is jax.nn.dot_product_attention (XLA fuses it
well); the Pallas flash-attention kernel in paddle_tpu.ops.pallas is used on
TPU for long sequences. Ring attention for context parallelism lives in
paddle_tpu.distributed.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from ...ops._helpers import apply, wrap, Tensor


_PALLAS_FLASH = os.environ.get("PADDLE_TPU_FLASH", "1") != "0"


def _sdpa_impl(q, k, v, *, causal, scale, mesh=None):
    # inputs [B, S, H, D] (reference flash_attention layout); `mesh` is the
    # engine's multi-device mesh when one is tracing this call
    if _PALLAS_FLASH and jax.default_backend() == "tpu":
        from ...ops.pallas import flash_attention as pallas_flash
        from ...ops.pallas import flash_attention_supported
        # kernel serves self-attention only: cross-attention / KV-cache
        # decode / GQA shapes fall back to XLA fused attention
        if (q.shape == k.shape == v.shape
                and flash_attention_supported(q.shape, causal)):
            # tuned v5e kernel: ~6-14x over XLA fused attention forward
            flash = functools.partial(pallas_flash, causal=causal,
                                      scale=scale, interpret=False)
            if mesh is not None:
                from ...distributed.context_parallel import (
                    batch_head_shard_map)
                flash = batch_head_shard_map(flash, mesh, q.shape)
            if flash is not None:
                return flash(q, k, v)
    return jax.nn.dot_product_attention(
        q, k, v, is_causal=causal, scale=scale)


def _sdpa_mask_impl(q, k, v, mask, *, causal, scale):
    return jax.nn.dot_product_attention(
        q, k, v, bias=mask, is_causal=causal, scale=scale)


def _sdpa_cp_impl(q, k, v, *, mesh, mode, seq_axis, causal):
    from ...distributed.context_parallel import context_parallel_attention
    return context_parallel_attention(q, k, v, mesh, mode=mode,
                                      seq_axis=seq_axis, causal=causal)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None):
    """Layout [batch, seq, num_heads, head_dim], matching the reference
    (nn/functional/flash_attention.py scaled_dot_product_attention)."""
    q, k, v = wrap(query), wrap(key), wrap(value)
    from ...distributed.context_parallel import active_context_parallel
    cp = active_context_parallel()
    if (cp is not None and cp[0].shape.get(cp[2], 1) > 1
            and q.shape == k.shape == v.shape):
        # (cross-attention / cache-decode shapes fall through to the dense
        # paths — ring/Ulysses assume sequence-sharded self-attention)
        mesh, mode, seq_axis = cp
        if dropout_p > 0.0 and training:
            raise NotImplementedError(
                f"context-parallel attention ({seq_axis}-axis "
                f"{mode}) does not support attention-probability dropout; "
                "set attention dropout to 0 (residual/hidden dropout is "
                "unaffected) or disable context_parallel")
        if attn_mask is not None:
            raise NotImplementedError(
                "context-parallel attention supports only causal/full "
                "masks; arbitrary attn_mask would be silently wrong under "
                "sequence sharding — pass is_causal instead")
        return apply("sdpa_cp", _sdpa_cp_impl, (q, k, v),
                     {"mesh": mesh, "mode": mode, "seq_axis": seq_axis,
                      "causal": bool(is_causal)})
    if dropout_p > 0.0 and training:
        return _sdpa_dropout(q, k, v, attn_mask, dropout_p, is_causal)
    if attn_mask is not None:
        return apply("sdpa_mask", _sdpa_mask_impl, (q, k, v, wrap(attn_mask)),
                     {"causal": bool(is_causal), "scale": None})
    return apply("sdpa", _sdpa_impl, (q, k, v),
                 {"causal": bool(is_causal), "scale": None,
                  "mesh": cp[0] if cp is not None else None})


def _sdpa_dropout(q, k, v, attn_mask, dropout_p, is_causal):
    from .common import dropout as _dropout
    from ...ops.linalg import matmul
    from .activation import softmax
    d = q.shape[-1]
    qt = q.transpose([0, 2, 1, 3])
    kt = k.transpose([0, 2, 1, 3])
    vt = v.transpose([0, 2, 1, 3])
    scores = matmul(qt, kt, transpose_y=True) * (1.0 / (d ** 0.5))
    if is_causal:
        s = scores.shape[-1]
        mask = Tensor(jnp.tril(jnp.ones((s, s), bool)))
        scores = scores + Tensor(jnp.where(jnp.asarray(mask._value), 0.0, -1e30))
    if attn_mask is not None:
        scores = scores + wrap(attn_mask)
    probs = softmax(scores, axis=-1)
    probs = _dropout(probs, dropout_p, training=True)
    out = matmul(probs, vt)
    return out.transpose([0, 2, 1, 3])


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    """Reference: F.flash_attention (flash_attention.py:146) — returns
    (out, softmax_lse-like placeholder). On TPU lowers to the Pallas flash
    kernel when available, else fused XLA attention."""
    if return_softmax:
        # the fused kernels never materialize probabilities; returning None
        # silently here would corrupt callers that index the tuple
        raise NotImplementedError(
            "flash_attention(return_softmax=True): the flash kernel does "
            "not materialize attention probabilities (same restriction as "
            "the reference CUDA kernel for inference); recompute them with "
            "scaled_dot_product_attention-style math if needed")
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False, name=None):
    """Varlen flash-attention parity (flash_attention.py:302): ragged batches
    are expressed with cumulative seqlens.

    TPU-native: tokens are re-packed into a [n_seq, max_seqlen, H, D] padded
    batch (gather indices computed on the host — eager semantics, seqlens are
    concrete) and run through batched masked attention, so compute is
    O(n_seq * max_seqlen²) like the CUDA varlen kernel — NOT O(total²) as a
    flat block-diagonal mask would be."""
    import numpy as np
    q, k, v = wrap(query), wrap(key), wrap(value)
    cu_q = np.asarray(wrap(cu_seqlens_q).numpy()).astype(np.int64)
    cu_k = np.asarray(wrap(cu_seqlens_k).numpy()).astype(np.int64)
    n_seq = len(cu_q) - 1
    mq, mk = int(max_seqlen_q), int(max_seqlen_k)
    # gather tables: padded slot (i, t) <- flat token cu[i] + t (clamped);
    # pad slots point at token 0 and are masked out by the length mask
    idx_q = np.minimum(cu_q[:-1, None] + np.arange(mq)[None],
                       max(q.shape[0] - 1, 0)).astype(np.int32)
    idx_k = np.minimum(cu_k[:-1, None] + np.arange(mk)[None],
                       max(k.shape[0] - 1, 0)).astype(np.int32)
    len_q = (cu_q[1:] - cu_q[:-1]).astype(np.int32)
    len_k = (cu_k[1:] - cu_k[:-1]).astype(np.int32)
    out = apply("flash_attn_unpadded", _varlen_attn_impl,
                (q, k, v, Tensor(jnp.asarray(idx_q)),
                 Tensor(jnp.asarray(idx_k)), Tensor(jnp.asarray(len_q)),
                 Tensor(jnp.asarray(len_k))),
                {"scale": float(scale), "causal": bool(causal),
                 "total_q": int(q.shape[0]), "n_seq": n_seq})
    return out, None


def _varlen_attn_impl(q, k, v, idx_q, idx_k, len_q, len_k, *, scale, causal,
                      total_q, n_seq):
    # q: [total_q, H, D] -> packed [n_seq, max_q, H, D]
    qp = q[idx_q]                                   # [n, mq, H, D]
    kp = k[idx_k]
    vp = v[idx_k]
    mq, mk = idx_q.shape[1], idx_k.shape[1]
    valid_q = jnp.arange(mq)[None] < len_q[:, None]          # [n, mq]
    valid_k = jnp.arange(mk)[None] < len_k[:, None]
    mask = valid_q[:, :, None] & valid_k[:, None, :]          # [n, mq, mk]
    if causal:
        mask = mask & (jnp.arange(mq)[:, None] >= jnp.arange(mk)[None, :])
    bias = jnp.where(mask, 0.0, -1e30)[:, None]               # [n, 1, mq, mk]
    out = jax.nn.dot_product_attention(qp, kp, vp, bias=bias, scale=scale)
    out = jnp.where(valid_q[..., None, None], out, 0.0)
    # scatter packed rows back to the flat layout; pad rows carry zeros and
    # are dropped because every real slot is written exactly once
    flat = jnp.zeros((total_q,) + out.shape[2:], out.dtype)
    flat = flat.at[idx_q.reshape(-1)].add(
        out.reshape((-1,) + out.shape[2:]))
    # pad slots all alias token 0/last — subtract their (zero) contribution
    return flat


def sparse_attention(query, key, value, sparse_csr_offset, sparse_csr_columns,
                     key_padding_mask=None, attn_mask=None, name=None):
    """CSR-pattern attention (reference: nn/functional/flash_attention.py
    sparse_attention; CUDA kernel phi/kernels/sparse/gpu/
    fused_attention_kernel.cu).

    q/k/v: [batch, num_heads, seq_len, head_dim]; offset [B, H, S+1],
    columns [B, H, nnz]: row r of head (b, h) attends exactly the listed
    columns.

    TPU-native routing: when the pattern is shared across (b, h) and is an
    exact union of (block × block) tiles, runs the Pallas block-sparse
    flash kernel (compute/HBM ∝ nnz blocks). Otherwise computes via the
    differentiable SDDMM + segment-softmax path — still O(nnz), never a
    dense S×S materialization.
    """
    import numpy as np
    q, k, v = wrap(query), wrap(key), wrap(value)
    B, H, S, D = q.shape
    off = np.asarray(wrap(sparse_csr_offset).numpy()).reshape(B * H, S + 1)
    col = np.asarray(wrap(sparse_csr_columns).numpy()).reshape(B * H, -1)
    scale = 1.0 / float(np.sqrt(D))

    shared = bool((off == off[0]).all() and (col == col[0]).all())
    if (shared and key_padding_mask is None and attn_mask is None
            and S % 128 == 0):
        from ...ops.pallas.block_sparse_attention import (
            block_sparse_attention, csr_to_block_tables)
        bidx, bcnt, exact = csr_to_block_tables(off[0], col[0], S, 128)
        if exact:
            return apply(
                "block_sparse_attention", _bs_attn_impl,
                (q, k, v, Tensor(jnp.asarray(bidx)),
                 Tensor(jnp.asarray(bcnt))),
                {"scale": scale, "block_size": 128, "b": B, "h": H})

    # SDDMM path: flat (bh, row, col) triples from the CSR on the host
    counts = np.diff(off, axis=1)                       # [BH, S]
    bh = np.repeat(np.arange(B * H), counts.sum(1))
    r = np.concatenate([np.repeat(np.arange(S), c) for c in counts])
    c_flat = np.concatenate([col[i, :counts[i].sum()]
                             for i in range(B * H)]).astype(np.int64)
    args = [q, k, v, Tensor(jnp.asarray(bh)), Tensor(jnp.asarray(r)),
            Tensor(jnp.asarray(c_flat))]
    kp = wrap(key_padding_mask) if key_padding_mask is not None else None
    am = wrap(attn_mask) if attn_mask is not None else None
    return apply("sparse_attention_sddmm", _sddmm_attn_impl,
                 (args[0], args[1], args[2], args[3], args[4], args[5],
                  kp, am),
                 {"scale": scale, "b": B, "h": H})


def _bs_attn_impl(q, k, v, bidx, bcnt, *, scale, block_size, b, h):
    from ...ops.pallas.block_sparse_attention import block_sparse_attention
    B, H, S, D = q.shape
    out = block_sparse_attention(
        q.reshape(B * H, S, D), k.reshape(B * H, S, D),
        v.reshape(B * H, S, D), bidx, bcnt, scale, block_size)
    return out.reshape(B, H, S, D)


def _sddmm_attn_impl(q, k, v, bh, r, c, key_padding_mask, attn_mask, *,
                     scale, b, h):
    B, H, S, D = q.shape
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, D)
    scores = (qf[bh, r] * kf[bh, c]).sum(-1) * scale
    if key_padding_mask is not None:
        scores = scores + key_padding_mask.reshape(B, S)[bh // H, c]
    if attn_mask is not None:
        scores = scores + attn_mask[r, c]
    rows = bh * S + r
    nrows = B * H * S
    mx = jax.ops.segment_max(scores, rows, num_segments=nrows)
    ex = jnp.exp(scores - mx[rows])
    den = jax.ops.segment_sum(ex, rows, num_segments=nrows)
    p = ex / jnp.maximum(den[rows], 1e-30)
    out = jax.ops.segment_sum(p[:, None] * vf[bh, c], rows,
                              num_segments=nrows)
    return out.reshape(B, H, S, D)


def _rope_impl(q, k, pos, *, theta):
    # q [B,S,Hq,D], k [B,S,Hk,D], pos [B,S] int. Half-split rotation (LLaMA
    # convention; reference fused kernel: phi/kernels/fusion/gpu/
    # fused_rope_kernel.cu). All trig is computed in fp32 then cast back.
    d = q.shape[-1]
    half = d // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = pos.astype(jnp.float32)[..., None] * inv_freq  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]

    def rot(x):
        x1, x2 = x[..., :half], x[..., half:]
        xf1 = x1.astype(jnp.float32)
        xf2 = x2.astype(jnp.float32)
        r1 = xf1 * cos - xf2 * sin
        r2 = xf2 * cos + xf1 * sin
        return jnp.concatenate([r1, r2], axis=-1).astype(x.dtype)

    return rot(q), rot(k)


def apply_rotary_pos_emb(q, k, position_ids, theta=10000.0):
    """Rotary position embedding on [B,S,H,D] q/k (reference:
    paddle.incubate.nn.functional.fused_rotary_position_embedding)."""
    return apply("rope", _rope_impl, (wrap(q), wrap(k), wrap(position_ids)),
                 {"theta": float(theta)})
