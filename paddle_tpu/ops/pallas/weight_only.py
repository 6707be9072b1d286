"""Weight-only quantized matmul as a Pallas TPU kernel.

Reference analog: the CUTLASS mixed-dtype GEMMs behind
python/paddle/nn/quant/quantized_linear.py's weight_only_linear.

Why a kernel: inside a decode scan, XLA hoists a jnp dequant
(`w_int8.astype(bf16) * scale`) out of the loop as loop-invariant code,
materializing the full-precision weight — HBM traffic right back to
bf16 size, erasing the entire point of weight-only quantization. This
kernel DMAs the int8 block into VMEM and converts there, so HBM only
ever sees int8: the activation-side matmul streams at ~half (int8) the
bf16 byte volume.

Layout: x [m, k] (m = batch*seq, small in decode), qweight [n, k] int8
(the reference's transposed layout), scale [n] f32 → out [m, n].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



# v5e scoped-VMEM default is 16MB; the 8MB double-buffered weight blocks
# sit right at (and for k=8192, 168KB past) that line — raise it.
_VMEM_LIMIT = 64 * (1 << 20)


def _kernel(x_ref, qw_ref, scale_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)            # [m, k]
    w = qw_ref[...].astype(jnp.float32)           # [bn, k] int8 -> f32 in VMEM
    out = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[...] = (out * scale_ref[...]).astype(o_ref.dtype)  # scale [1, bn]


def _kernel_int4(x_ref, qw_ref, scale_ref, o_ref):
    """Nibble-packed int4: qw [bn, k//2] int8 holds w[:, :k/2] in the low
    nibble and w[:, k/2:] in the high nibble, BOTH as raw two's-complement
    nibbles — arithmetic shifts sign-extend each for free (high: >>4;
    low: <<28 then >>28 on the int32 promotion), so the unpack is pure
    shift work feeding the matmul taps: no bias, no rank-1 rowsum
    correction chain (a k/2-length f32 reduction + fused
    multiply-subtract per x-row that the old biased encoding paid on
    every dispatch), and no materialized int8 intermediate — the packed
    block is the only thing DMA'd from HBM. Halves packing: no lane
    interleave, just two half-K matmuls. The nibble ops run on an int32
    promotion of the block (Mosaic lowers no int8 shift)."""
    k2 = qw_ref.shape[1]
    x = x_ref[...].astype(jnp.float32)
    p = qw_ref[...].astype(jnp.int32)   # Mosaic has no int8 shift/and
    high = (p >> 4).astype(jnp.float32)
    low = ((p << 28) >> 28).astype(jnp.float32)   # sign-extended nibble
    xl = jax.lax.slice(x, (0, 0), (x.shape[0], k2))
    xh = jax.lax.slice(x, (0, k2), (x.shape[0], 2 * k2))
    out = jax.lax.dot_general(xl, low, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32) \
        + jax.lax.dot_general(xh, high, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[...] = (out * scale_ref[...]).astype(o_ref.dtype)  # scale [1, bn]


def _pick_block(n, k, m):
    """Largest out-block with the int8 block bytes within the empirically
    validated envelope. Mosaic streams the dequant rather than holding a
    full fp32 copy: bn=1024 x k=8192 (8 MB int8) compiles and runs at
    full bandwidth on v5e, while a paper model that charges double-buffer
    + fp32 copies picks bn=128 blocks that FAIL tpu compilation — block
    choices here must track what the compiler accepts, not the naive
    arithmetic."""
    for blk in (1024, 512, 256, 128):
        if n % blk == 0 and blk * k <= (8 << 20) and m * blk * 8 <= (2 << 20):
            return blk
    return None


def weight_only_matmul(x, qweight, scale, out_dtype=None, interpret=None,
                       weight_dtype="int8"):
    """x [m, k] float; qweight [n, k] int8 or, for weight_dtype='int4',
    [n, k//2] halves-packed nibbles; scale [n] f32 -> [m, n].
    Returns None if the shapes don't fit the kernel (caller falls back)."""
    m, k = x.shape
    n, kw = qweight.shape
    int4 = weight_dtype == "int4"
    if (int4 and kw * 2 != k) or (not int4 and kw != k):
        raise ValueError(
            f"weight_only_matmul: qweight width {kw} inconsistent with "
            f"k={k} for weight_dtype={weight_dtype!r}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if kw % 128 or m > 512:
        return None
    bn = _pick_block(n, kw, m)
    if bn is None:
        return None
    out_dtype = out_dtype or x.dtype
    # scale ships as [1, n]: a 1-D f32 operand gets an XLA minor tiling
    # (T(1024) at n=22016, llama ffn) that can disagree with Mosaic's
    # block-derived T(bn) and fail layout verification; 2-D operands use
    # the unambiguous (8, 128) tiling.
    return pl.pallas_call(
        _kernel_int4 if int4 else _kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((m, k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, kw), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(x, qweight, scale.reshape(1, n))


def weight_only_matmul_nd(x, qweight, scale, interpret=None,
                          weight_dtype="int8"):
    """Rank-N wrapper: flattens leading dims of x to m."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    m = 1
    for d in lead:
        m *= d
    out = weight_only_matmul(x.reshape(m, k), qweight, scale,
                             interpret=interpret,
                             weight_dtype=weight_dtype)
    if out is None:
        return None
    return out.reshape(*lead, qweight.shape[0])
