"""Context parallelism: ring attention + Ulysses (all-to-all) attention.

The reference has NO ring attention / Ulysses (SURVEY.md §5 "long-context":
it only offers Megatron-style sequence parallel around TP blocks,
fleet/utils/sequence_parallel_utils.py:230, and the `sep` hybrid-topology
axis with model-level sequence splitting, fleet/base/topology.py:64,184).
This module is the TPU-native long-context answer that *exceeds* the
reference: sequence shards live on the context-parallel mesh axis ("cp" on
MeshConfig meshes, "sep" on the legacy hybrid topology) and

- **ring attention** streams K/V blocks around the ICI ring with
  `jax.lax.ppermute`, combining per-block partial attention with the
  online-softmax (flash) recurrence, so peak memory is O(S_local) and the
  ppermute overlaps with the block matmuls. Two interchangeable step
  implementations: an einsum body (any shape, CPU-friendly) and the Pallas
  flash fwd/bwd kernels (`ops/pallas/flash_attention.flash_fwd_pos` /
  `flash_bwd_pos`) composed under one custom_vjp (`impl="flash"`);
- **Ulysses attention** trades sequence sharding for head sharding with two
  `all_to_all`s, running dense flash attention on full sequences per head
  group.

Causal load balancing: with naive contiguous placement, ring step t is all
useful work for late shards and all masked work for early ones. The zigzag
placement (Ring Attention / llama3 recipe) gives device p the global
chunks (p, 2n-1-p) — each device owns an early AND a late chunk, so every
ring step carries ~the same number of unmasked (query, key) pairs. The
permutation is applied to the GLOBAL arrays outside the shard_map (a
static gather the surrounding jit fuses into the sharding transfer) and
masking runs off explicit per-row global positions that rotate around the
ring alongside K/V.

Both run inside `jax.shard_map` regions nested in the engine's single jitted
train step, composing with dp/fsdp batch split and tp/mp head split.
"""
from __future__ import annotations

import contextlib
import math
import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import sharding as _shardlib

__all__ = [
    "ring_attention",
    "ulysses_attention",
    "context_parallel_attention",
    "context_parallel_guard",
    "active_context_parallel",
]


# ---------------------------------------------------------------------------
# Placement helpers.
# ---------------------------------------------------------------------------


def zigzag_permutation(seq_len, n_shards):
    """Global row permutation for load-balanced causal placement: shard p
    receives chunks (p, 2n-1-p) of size seq_len/(2n). Returns (perm,
    inverse) index arrays; `x[:, perm]` places rows, `y[:, inverse]`
    restores the natural order."""
    if seq_len % (2 * n_shards):
        raise ValueError(f"zigzag placement needs seq_len divisible by "
                         f"2*n_shards, got {seq_len} / {n_shards}")
    c = seq_len // (2 * n_shards)
    perm = np.concatenate([
        np.concatenate([np.arange(p * c, (p + 1) * c),
                        np.arange((2 * n_shards - 1 - p) * c,
                                  (2 * n_shards - p) * c)])
        for p in range(n_shards)])
    return perm, np.argsort(perm)


def _local_positions(axis_name, s_loc, balanced):
    """Global positions of this shard's rows (int32 [s_loc]), matching
    `zigzag_permutation` when balanced else contiguous placement."""
    p = jax.lax.axis_index(axis_name)
    n = jax.lax.psum(1, axis_name)
    if balanced:
        c = s_loc // 2
        lo = p * c + jnp.arange(c, dtype=jnp.int32)
        hi = (2 * n - 1 - p) * c + jnp.arange(c, dtype=jnp.int32)
        return jnp.concatenate([lo, hi])
    return p * s_loc + jnp.arange(s_loc, dtype=jnp.int32)


def _to_bh(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bh(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Local (inside-shard_map) bodies. q/k/v: [batch, seq_local, heads, head_dim].
# ---------------------------------------------------------------------------


def _ring_attention_local(q, k, v, *, axis_name, causal, scale, balanced):
    """Flash-style streaming attention over K/V blocks rotating on the ring.

    Device p starts with its own K/V block; after t rotations it holds the
    block originally owned by (p - t) mod n. Per block: masked scores →
    online-softmax update of (o, m, l); K/V (and their global position
    vector) then hop one step around the `axis_name` ring (ppermute — XLA
    maps this onto neighbouring ICI links).
    """
    n = jax.lax.psum(1, axis_name)
    b, s_loc, h, d = q.shape
    qf = (q.astype(jnp.float32) * scale).transpose(0, 2, 1, 3)  # [b,h,sq,d]
    q_pos = _local_positions(axis_name, s_loc, balanced)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def block_update(acc, k_blk, v_blk, k_pos):
        o, m, l = acc
        kf = k_blk.astype(jnp.float32).transpose(0, 2, 1, 3)
        vf = v_blk.astype(jnp.float32).transpose(0, 2, 1, 3)
        s_ = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
        if causal:
            mask = (q_pos[:, None] >= k_pos[None, :])[None, None]
            s_ = jnp.where(mask, s_, -1e30)
        m_new = jnp.maximum(m, s_.max(-1))
        p_ = jnp.exp(s_ - m_new[..., None])
        if causal:
            p_ = p_ * mask  # robust when a whole row is masked (m_new=-1e30)
        corr = jnp.exp(m - m_new)
        l = l * corr + p_.sum(-1)
        o = o * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p_, vf)
        return o, m_new, l

    def body(t, carry):
        acc, k_blk, v_blk, kp = carry
        # send the current block onward BEFORE consuming it: the ppermute
        # has no data dependency on the block matmuls, so XLA can overlap
        # the ICI hop with compute; n-1 hops total (the last arrival is
        # consumed after the loop)
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        kp_nxt = jax.lax.ppermute(kp, axis_name, perm)
        acc = block_update(acc, k_blk, v_blk, kp)
        return acc, k_nxt, v_nxt, kp_nxt

    acc = (jnp.zeros((b, h, s_loc, d), jnp.float32),
           jnp.full((b, h, s_loc), -1e30, jnp.float32),
           jnp.zeros((b, h, s_loc), jnp.float32))
    acc, k_last, v_last, kp_last = jax.lax.fori_loop(
        0, n - 1, body, (acc, k, v, q_pos))
    o, m, l = block_update(acc, k_last, v_last, kp_last)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# -- ring steps through the Pallas flash kernels (one custom_vjp) -----------


def _merge_partial(out, lse, o_blk, lse_blk):
    """Online-softmax merge of one ring step's normalized partial: a
    fully-masked partial arrives as (0, ~-inf) and gets weight 0."""
    lse_new = jnp.logaddexp(lse, lse_blk)
    out = out * jnp.exp(lse - lse_new) \
        + o_blk.astype(jnp.float32) * jnp.exp(lse_blk - lse_new)
    return out, lse_new


def _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale, balanced,
                         interpret):
    from ..ops.pallas.flash_attention import flash_fwd_pos

    n = jax.lax.psum(1, axis_name)
    b, s_loc, h, d = q.shape
    q_pos = _local_positions(axis_name, s_loc, balanced)
    perm = [(i, (i + 1) % n) for i in range(n)]
    qb, kb, vb = _to_bh(q), _to_bh(k), _to_bh(v)

    def body(t, carry):
        out, lse, k_c, v_c, kp_c = carry
        k_n = jax.lax.ppermute(k_c, axis_name, perm)
        v_n = jax.lax.ppermute(v_c, axis_name, perm)
        kp_n = jax.lax.ppermute(kp_c, axis_name, perm)
        o_blk, lse_blk = flash_fwd_pos(
            qb, k_c, v_c, q_pos, kp_c, scale=scale, causal=causal,
            interpret=interpret)
        out, lse = _merge_partial(out, lse, o_blk, lse_blk)
        return out, lse, k_n, v_n, kp_n

    init = (jnp.zeros(qb.shape, jnp.float32),
            jnp.full((b * h, s_loc, 1), -1e30, jnp.float32), kb, vb, q_pos)
    out, lse, k_l, v_l, kp_l = jax.lax.fori_loop(0, n - 1, body, init)
    o_blk, lse_blk = flash_fwd_pos(
        qb, k_l, v_l, q_pos, kp_l, scale=scale, causal=causal,
        interpret=interpret)
    out, lse = _merge_partial(out, lse, o_blk, lse_blk)
    return out.astype(q.dtype), lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash_local(q, k, v, axis_name, causal, scale, balanced,
                      interpret):
    out, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale,
                                  balanced, interpret)
    b, s_loc, h, d = q.shape
    return _from_bh(out, b, h)


def _ring_flash_fwd_rule(q, k, v, axis_name, causal, scale, balanced,
                         interpret):
    out_bh, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale,
                                       balanced, interpret)
    b, s_loc, h, d = q.shape
    return _from_bh(out_bh, b, h), (q, k, v, out_bh, lse)


def _ring_flash_bwd_rule(axis_name, causal, scale, balanced, interpret,
                         res, do):
    """Ring backward: dq accumulates at home; (k, v, dk, dv) rotate
    TOGETHER for n full hops, each visited device adding its q-shard's
    contribution — after n rotations the accumulated dk/dv are home. The
    FA-2 identity (p from the GLOBAL merged lse, ds = p*(dp - delta))
    makes every step independently computable from global statistics."""
    from ..ops.pallas.flash_attention import flash_bwd_pos

    q, k, v, out_bh, lse = res
    n = jax.lax.psum(1, axis_name)
    b, s_loc, h, d = q.shape
    q_pos = _local_positions(axis_name, s_loc, balanced)
    perm = [(i, (i + 1) % n) for i in range(n)]
    qb, kb, vb, dob = _to_bh(q), _to_bh(k), _to_bh(v), _to_bh(do)
    delta = jnp.sum(dob.astype(jnp.float32) * out_bh.astype(jnp.float32),
                    axis=-1, keepdims=True)

    def body(t, carry):
        dq, k_c, v_c, kp_c, dk_c, dv_c = carry
        dq_i, dk_i, dv_i = flash_bwd_pos(
            qb, k_c, v_c, dob, lse, delta, q_pos, kp_c, scale=scale,
            causal=causal, interpret=interpret)
        dq = dq + dq_i.astype(jnp.float32)
        dk_c = dk_c + dk_i.astype(jnp.float32)
        dv_c = dv_c + dv_i.astype(jnp.float32)
        return (dq,
                jax.lax.ppermute(k_c, axis_name, perm),
                jax.lax.ppermute(v_c, axis_name, perm),
                jax.lax.ppermute(kp_c, axis_name, perm),
                jax.lax.ppermute(dk_c, axis_name, perm),
                jax.lax.ppermute(dv_c, axis_name, perm))

    init = (jnp.zeros(qb.shape, jnp.float32), kb, vb, q_pos,
            jnp.zeros(kb.shape, jnp.float32),
            jnp.zeros(vb.shape, jnp.float32))
    dq, _, _, _, dk, dv = jax.lax.fori_loop(0, n, body, init)
    return (_from_bh(dq.astype(q.dtype), b, h),
            _from_bh(dk.astype(k.dtype), b, h),
            _from_bh(dv.astype(v.dtype), b, h))


_ring_flash_local.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd_rule)


def _ulysses_attention_local(q, k, v, *, axis_name, causal, scale):
    """All-to-all head/sequence exchange: [b, S/n, h, d] -> [b, S, h/n, d],
    dense flash attention on the full sequence per head group, then the
    inverse exchange. One all_to_all pair per tensor — O(S·h·d/n) bytes on
    ICI, independent of S² (the attention itself never crosses chips)."""
    a2a = partial(jax.lax.all_to_all, axis_name=axis_name, tiled=True)
    q = a2a(q, split_axis=2, concat_axis=1)
    k = a2a(k, split_axis=2, concat_axis=1)
    v = a2a(v, split_axis=2, concat_axis=1)
    out = jax.nn.dot_product_attention(q, k, v, is_causal=causal, scale=scale)
    return a2a(out, split_axis=1, concat_axis=2)


# ---------------------------------------------------------------------------
# shard_map wrappers.
# ---------------------------------------------------------------------------


def _cp_spec(mesh, seq_axis, batch_axes, head_axes):
    batch = tuple(a for a in batch_axes
                  if a in mesh.shape and mesh.shape[a] > 1)
    head = next((a for a in head_axes
                 if a in mesh.shape and mesh.shape[a] > 1), None)
    return _shardlib.spec(batch if batch else None, seq_axis, head, None)


def batch_head_shard_map(fn, mesh, q_shape,
                         batch_axes=("dp", "sharding", "fsdp"),
                         head_axis=("mp", "tp")):
    """`fn(q, k, v)` over [batch, seq, heads, head_dim] arrays, run per
    shard with batch split over the mesh's data axes and heads over its
    tensor axis — exact, since attention is independent per batch row and
    per head. This is how a Pallas kernel runs on a multi-device mesh:
    GSPMD refuses to partition a Mosaic call ("wrap the call in a
    shard_map"). Returns None when the shape does not divide the mesh."""
    from jax import shard_map

    spec = _cp_spec(mesh, None, batch_axes, head_axis)

    def ways(entry):
        names = () if entry is None else \
            (entry,) if isinstance(entry, str) else entry
        return math.prod(mesh.shape[a] for a in names)

    if q_shape[0] % ways(spec[0]) or q_shape[2] % ways(spec[2]):
        return None
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)


def _ring_flash_shapes_ok(s_loc, d, balanced):
    """Whether the Pallas pos-kernels handle this per-shard problem (same
    VMEM envelope as flash_attention_supported, on the LOCAL length)."""
    if balanced and s_loc % 2:
        return False
    return (s_loc >= 128 and s_loc % 128 == 0 and d <= 256
            and s_loc * d <= (1 << 20))


def context_parallel_attention(q, k, v, mesh, *, mode="ring", seq_axis="sep",
                               causal=True, scale=None, impl=None,
                               balanced=None,
                               batch_axes=("dp", "sharding", "fsdp"),
                               head_axis=("mp", "tp")):
    """Sequence-sharded self-attention over `seq_axis` of `mesh`.

    q/k/v: [batch, seq, heads, head_dim] global arrays (or tracers inside a
    jit using `mesh`); seq must divide by mesh.shape[seq_axis]; with
    mode="ulysses", local heads must also divide by it.

    `impl` selects the ring step body: "einsum" (any shape), "flash" (the
    Pallas pos-kernels; per-shard length must be 128-aligned), or
    None/"auto" — flash on TPU when the shapes qualify, einsum otherwise.
    `mode="ring_flash"` is shorthand for mode="ring", impl="flash".
    `balanced` (default: on for causal ring when divisibility allows)
    applies the zigzag causal placement so every ring step does even work.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if mode == "ring_flash":
        mode, impl = "ring", "flash"
    head_axes = (head_axis,) if isinstance(head_axis, str) else head_axis
    spec = _cp_spec(mesh, seq_axis, batch_axes, head_axes)
    from jax import shard_map

    if mode == "ring":
        n = int(mesh.shape[seq_axis])
        b, s, h, d = q.shape
        if s % n:
            raise ValueError(f"seq len {s} must divide the {seq_axis!r} "
                             f"axis size {n}")
        s_loc = s // n
        # heads may additionally be sharded over the head axis; that does
        # not change s_loc/d so the flash qualification below holds
        if balanced is None:
            balanced = bool(causal) and n > 1 and s % (2 * n) == 0
        if impl in (None, "auto"):
            impl = "flash" if (jax.default_backend() == "tpu"
                               and _ring_flash_shapes_ok(s_loc, d, balanced)) \
                else "einsum"
        if impl == "flash":
            if not _ring_flash_shapes_ok(s_loc, d, balanced):
                raise ValueError(
                    f"ring flash needs a 128-aligned per-shard length "
                    f"(and head_dim <= 256), got seq {s} over "
                    f"{seq_axis}={n} -> {s_loc}, head_dim {d}")
            interpret = jax.default_backend() != "tpu"
            body = partial(_ring_flash_local, axis_name=seq_axis,
                           causal=causal, scale=scale, balanced=balanced,
                           interpret=interpret)
        elif impl == "einsum":
            body = partial(_ring_attention_local, axis_name=seq_axis,
                           causal=causal, scale=scale, balanced=balanced)
        else:
            raise ValueError(f"unknown ring impl {impl!r}")
    elif mode == "ulysses":
        balanced = False
        body = partial(_ulysses_attention_local, axis_name=seq_axis,
                       causal=causal, scale=scale)
    else:
        raise ValueError(f"unknown context-parallel mode {mode!r}")

    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    if balanced and mode == "ring":
        perm, inv = zigzag_permutation(q.shape[1], int(mesh.shape[seq_axis]))
        out = fn(q[:, perm], k[:, perm], v[:, perm])
        return out[:, inv]
    return fn(q, k, v)


def ring_attention(q, k, v, mesh, *, seq_axis="sep", causal=True, scale=None,
                   impl=None, balanced=None,
                   batch_axes=("dp", "sharding", "fsdp"),
                   head_axis=("mp", "tp")):
    """Ring attention (ppermute K/V rotation + online softmax)."""
    return context_parallel_attention(
        q, k, v, mesh, mode="ring", seq_axis=seq_axis, causal=causal,
        scale=scale, impl=impl, balanced=balanced, batch_axes=batch_axes,
        head_axis=head_axis)


def ulysses_attention(q, k, v, mesh, *, seq_axis="sep", causal=True,
                      scale=None, batch_axes=("dp", "sharding", "fsdp"),
                      head_axis=("mp", "tp")):
    """Ulysses all-to-all sequence/head-parallel attention."""
    return context_parallel_attention(
        q, k, v, mesh, mode="ulysses", seq_axis=seq_axis, causal=causal,
        scale=scale, batch_axes=batch_axes, head_axis=head_axis)


# ---------------------------------------------------------------------------
# Trace-time routing state: the engine enables this around its traced loss so
# model-level `F.scaled_dot_product_attention` calls transparently become
# context-parallel when the mesh has a sequence axis ("cp"/"sep") > 1.
# ---------------------------------------------------------------------------


class _CPState(threading.local):
    def __init__(self):
        # (mesh, mode, seq_axis); seq_axis None = no sequence axis in use,
        # the mesh is published for the per-shard flash kernel alone
        self.config = None


_cp_state = _CPState()


def active_context_parallel():
    """(mesh, mode, seq_axis) if a context_parallel_guard is active."""
    return _cp_state.config


@contextlib.contextmanager
def context_parallel_guard(mesh, mode="ring", seq_axis="sep"):
    prev = _cp_state.config
    _cp_state.config = (mesh, mode, seq_axis)
    try:
        yield
    finally:
        _cp_state.config = prev
