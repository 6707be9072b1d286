"""Batched-gather LoRA matmul (BGMV) — multi-tenant adapter decode.

Reference analog: Punica's BGMV / S-LoRA's unified-paging kernels — one
dispatch applies EVERY sequence's own low-rank adapter:

    delta[b] = (x[b] @ A[ids[b]]) @ B[ids[b]]

with the adapter stacks A [slots, d_in, r] / B [slots, r, d_out]
resident on device (slot 0 all-zero = "no adapter").  Gathering by
per-sequence slot id inside the dispatch is what lets a heterogeneous-
adapter batch share one compiled executable — the adapter analog of
reading the KV pool through block tables.

The Pallas kernel scalar-prefetches `ids` and uses it in the A/B block
index_map, so only the slots the batch actually references leave HBM.
The XLA fallback (`use_kernel=False`, the default off-TPU) expresses the
identical math as a `take` + two matmuls — the path CPU tier-1 runs; a
parity test pins kernel-vs-fallback agreement in interpret mode.  All
accumulation is f32 regardless of the x/A/B dtypes (the engine stores
stacks in f32; `B` is pre-scaled by alpha/r at load so no scale rides
the graph).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_VMEM_LIMIT = 64 * 1024 * 1024

__all__ = ["lora_delta"]


def _default_interpret():
    if os.environ.get("PADDLE_TPU_PALLAS_INTERPRET") == "1":
        return True
    return jax.devices()[0].platform != "tpu"


def _kernel(ids_ref, x_ref, a_ref, b_ref, o_ref):
    # grid (B,): blocks x [1,S,Din]; a [1,Din,R]; b [1,R,Dout];
    # o [1,S,Dout]. Two MXU dots, f32 accumulation.
    x = x_ref[0].astype(jnp.float32)                       # [S, Din]
    a = a_ref[0].astype(jnp.float32)                       # [Din, R]
    b = b_ref[0].astype(jnp.float32)                       # [R, Dout]
    h = jnp.dot(x, a, preferred_element_type=jnp.float32)  # [S, R]
    o_ref[0] = jnp.dot(h, b, preferred_element_type=jnp.float32)


def lora_delta(x, A, B, ids, *, use_kernel=None, interpret=None):
    """Per-sequence LoRA delta through slot-stacked adapter weights.

    x [batch, s, d_in]; A [slots, d_in, r]; B [slots, r, d_out] (B
    pre-scaled by alpha/r); ids int32 — a scalar (one adapter for the
    whole batch: the engine's per-sequence scan sub-step) or [batch]
    (one slot per row: the batched BGMV). Returns f32
    [batch, s, d_out]; the caller adds it into the base projection
    (slot 0 rows are selected back to the base output bitwise by the
    engine's hook, so an all-zero slot never perturbs greedy traffic).
    """
    ids = jnp.asarray(ids, jnp.int32)
    if ids.ndim == 0:
        # scalar slot: plain gather + two matmuls — the per-sequence
        # decode path, identical math at every batch composition
        a = jnp.take(A, ids, 0).astype(jnp.float32)        # [d_in, r]
        b = jnp.take(B, ids, 0).astype(jnp.float32)        # [r, d_out]
        h = jnp.matmul(x.astype(jnp.float32), a)
        return jnp.matmul(h, b)

    bsz, s, d_in = x.shape
    slots, _, r = A.shape
    d_out = B.shape[-1]
    if ids.shape != (bsz,):
        raise ValueError(f"ids must be scalar or [batch], got "
                         f"{ids.shape} for batch {bsz}")
    if interpret is None:
        interpret = _default_interpret()
    if use_kernel is None:
        use_kernel = not interpret

    if not use_kernel:
        a = jnp.take(A, ids, 0).astype(jnp.float32)        # [b, d_in, r]
        b = jnp.take(B, ids, 0).astype(jnp.float32)        # [b, r, d_out]
        h = jnp.einsum("bsd,bdr->bsr", x.astype(jnp.float32), a)
        return jnp.einsum("bsr,bro->bso", h, b)

    x_spec = pl.BlockSpec((1, s, d_in), lambda b, ids: (b, 0, 0),
                          memory_space=pltpu.VMEM)
    a_spec = pl.BlockSpec((1, d_in, r), lambda b, ids: (ids[b], 0, 0),
                          memory_space=pltpu.VMEM)
    b_spec = pl.BlockSpec((1, r, d_out), lambda b, ids: (ids[b], 0, 0),
                          memory_space=pltpu.VMEM)
    o_spec = pl.BlockSpec((1, s, d_out), lambda b, ids: (b, 0, 0),
                          memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                 # ids
        grid=(bsz,),
        in_specs=[x_spec, a_spec, b_spec],
        out_specs=o_spec,
    )
    return pl.pallas_call(
        functools.partial(_kernel),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, s, d_out), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(ids, x, A, B)
