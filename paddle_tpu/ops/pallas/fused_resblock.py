"""Fused ResNet bottleneck block as a Pallas TPU kernel family.

Reference analog: the conv+BN+relu fusion chain the reference ships as a
CUDA kernel for exactly the same reason —
paddle/phi/kernels/fusion/gpu/fused_scale_bias_relu_conv_bn_kernel.cu
(cuDNN ConvScaleBiasActivation + BN-stats emission).

Why a kernel: docs/resnet50_roofline.md measures that XLA streams every
conv->BN->relu link of the ResNet-50 train step through HBM (~700 GB/s
sustained, 12-13% MFU) while the same convs sustain 81-97% of MXU peak fed
from VMEM. The fix is moving whole bottleneck blocks through VMEM:

  forward (stride-1 identity block, channels 4C -> C -> C -> 4C):
    K1  r1 = x @ w1                      reads x(4C)  writes r1(C) + stats
    K2  r2 = conv3x3(relu(bn1(r1)))      reads r1(C)  writes r2(C) + stats
    K3  stats of r3 = relu(bn2(r2))@w3   reads r2(C)  writes stats only
    K4  y = relu(bn3(r3) + x)            reads r2(C)+x(4C) writes y(4C)
  r3 (the widest intermediate) never touches HBM: K4 *recomputes* the 1x1
  conv3 — FLOPs are free on a bandwidth-bound workload. Block traffic
  ~17C*HW*2B vs XLA's ~34C, with exact train-mode BN semantics (each BN's
  batch-stat barrier forces the kernel split; channel sums accumulate in
  VMEM across the sequentially-iterated TPU grid).

  backward mirrors it (full BN backward incl. the stats' dependence on the
  data; relu masks and intermediates recomputed from the saved C-wide
  tensors):
    B1  dz = dy*relu'(y); bn3 sums       reads dy,y(8C)+r2(C) writes dz(4C)
    B2  dr3, dW3, da2', bn2 sums         reads dz(4C)+r2(C)   writes da2'(C)
    B3  dr2, conv2^T, dW2, da1', bn1 sums reads da2',r2,r1(3C) writes da1'(C)
    B4  dr1, dW1, dx = dr1@w1^T + dz     reads da1',r1(2C)+x,dz(8C) w dx(4C)

Layout: activations stay FLAT [N*H*W, C] end to end — the XLA-side
reshape from NHWC is a free row-major bitcast, and the kernels never
reshape (4D<->2D reshapes force Mosaic relayouts when H*W is not
tile-aligned, which dominated runtime in the first version). The 3x3 conv
is 9 x (row-roll + iota-mask + matmul): a shift by di*W+dj in flat row
space reads the (h+di, w+dj) pixel, the iota mask zeroes out-of-image
taps, and because each grid block holds whole images, the rows a roll
wraps around the block edge are exactly the rows the mask already zeroes.
All matmuls run bf16 x bf16 -> f32 on the MXU; stats and weight-grad
accumulators are f32 and VMEM-resident across grid steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _default_interpret():
    return jax.default_backend() != "tpu"


# Matmul operand dtype. bf16 is the MXU-native production setting; tests
# flip this to f32 to compare bitwise-tight against the jnp reference
# (isolating logic bugs from bf16 rounding).
MATMUL_DTYPE = jnp.bfloat16

# v5e VMEM is 128MB; Mosaic's default 16MB scoped limit is far below what
# the f32 temporaries of the wide (4C) kernels need at useful batch tiles.
_VMEM_LIMIT = 100 * (1 << 20)


def _affine_relu(r, scale, bias):
    """relu(bn(r)) with bn folded to per-channel scale/bias; f32."""
    return jnp.maximum(r.astype(jnp.float32) * scale + bias, 0.0)


def _mm(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _mm_t(a, b):
    """a[m,k] @ b[n,k]^T -> [m,n]."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _mm_tn(a, b):
    """a[m,k]^T @ b[m,n] -> [k,n] (contract rows)."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tap_masks(Mb, H, W):
    """valid[di+1][dj+1]: [Mb,1] bool — input pixel (h+di, w+dj) in-image
    for the flat output row. Also masks the rows a block-local roll wraps
    (wrap rows are exactly image-edge rows when blocks hold whole images)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (Mb, 1), 0)
    h = (rows % (H * W)) // W
    w = rows % W
    masks = []
    for di in (-1, 0, 1):
        row = []
        for dj in (-1, 0, 1):
            v = jnp.logical_and(
                jnp.logical_and(h + di >= 0, h + di < H),
                jnp.logical_and(w + dj >= 0, w + dj < W))
            row.append(v)
        masks.append(row)
    return masks


def _shift(f, delta):
    """f[rho + delta] at output row rho (block-wrapping; wrap rows must be
    masked by the caller)."""
    if delta == 0:
        return f
    return pltpu.roll(f, (-delta) % f.shape[0], 0)


def _conv3x3_flat(f, w2, H, W, masks):
    """f [Mb, Cin] f32, w2 [3,3,Cin,Cout] -> [Mb, Cout] f32.
    Shifts run in f32 (Mosaic's dynamic_rotate has no 16-bit support);
    each masked tap casts to MATMUL_DTYPE right before the MXU."""
    acc = None
    for di in (-1, 0, 1):
        for dj in (-1, 0, 1):
            g = _shift(f, di * W + dj)
            g = jnp.where(masks[di + 1][dj + 1], g, 0).astype(MATMUL_DTYPE)
            t = _mm(g, w2[di + 1, dj + 1])
            acc = t if acc is None else acc + t
    return acc


def flip_transpose_w2(w2):
    """conv3x3^T kernel: spatial flip + in/out channel swap (glue-side).
    conv_transpose(dr2, w2) == conv3x3(dr2, flip_transpose_w2(w2))."""
    return jnp.transpose(w2[::-1, ::-1], (0, 1, 3, 2))


# ---------------------------------------------------------------- forward


def _k1(x_ref, w1_ref, r1_ref, st_ref):
    r1 = _mm(x_ref[...], w1_ref[...])
    r1_ref[...] = r1.astype(r1_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    st_ref[0, :] += jnp.sum(r1, axis=0)
    st_ref[1, :] += jnp.sum(r1 * r1, axis=0)


def _k2(r1_ref, s1_ref, b1_ref, w2_ref, r2_ref, st_ref, *, H, W):
    Mb = r1_ref.shape[0]
    f1 = _affine_relu(r1_ref[...], s1_ref[...], b1_ref[...])
    r2 = _conv3x3_flat(f1, w2_ref[...], H, W, _tap_masks(Mb, H, W))
    r2_ref[...] = r2.astype(r2_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    st_ref[0, :] += jnp.sum(r2, axis=0)
    st_ref[1, :] += jnp.sum(r2 * r2, axis=0)


def _k3(r2_ref, s2_ref, b2_ref, w3_ref, st_ref):
    f2 = _affine_relu(r2_ref[...], s2_ref[...], b2_ref[...]) \
        .astype(MATMUL_DTYPE)
    r3 = _mm(f2, w3_ref[...])

    @pl.when(pl.program_id(0) == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    st_ref[0, :] += jnp.sum(r3, axis=0)
    st_ref[1, :] += jnp.sum(r3 * r3, axis=0)


def _k4(r2_ref, x_ref, s2_ref, b2_ref, w3_ref, s3_ref, b3_ref, y_ref):
    f2 = _affine_relu(r2_ref[...], s2_ref[...], b2_ref[...]) \
        .astype(MATMUL_DTYPE)
    r3 = _mm(f2, w3_ref[...])
    z = r3 * s3_ref[...] + b3_ref[...] \
        + x_ref[...].astype(jnp.float32)
    y_ref[...] = jnp.maximum(z, 0.0).astype(y_ref.dtype)


# ---------------------------------------------------------------- backward


def _b1(dy_ref, y_ref, r2_ref, s2_ref, b2_ref, w3_ref, mu3_ref, inv3_ref,
        dz_ref, st_ref):
    dy = dy_ref[...].astype(jnp.float32)
    # f32 compare: Mosaic on v5e has no bf16 vector cmpf
    y = y_ref[...].astype(jnp.float32)
    dz = jnp.where(y > 0, dy, 0.0)
    dz_ref[...] = dz.astype(dz_ref.dtype)
    f2 = _affine_relu(r2_ref[...], s2_ref[...], b2_ref[...]) \
        .astype(MATMUL_DTYPE)
    r3 = _mm(f2, w3_ref[...])
    xh3 = (r3 - mu3_ref[...]) * inv3_ref[...]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    st_ref[0, :] += jnp.sum(dz, axis=0)
    st_ref[1, :] += jnp.sum(dz * xh3, axis=0)


def _b2(dz_ref, r2_ref, s2_ref, b2_ref, w3_ref, mu3_ref, inv3_ref,
        c03_ref, m13_ref, m23_ref, mu2_ref, inv2_ref,
        da2_ref, dw3_ref, st_ref):
    dz = dz_ref[...].astype(jnp.float32)
    r2f = r2_ref[...].astype(jnp.float32)
    f2 = jnp.maximum(r2f * s2_ref[...] + b2_ref[...], 0.0)
    f2b = f2.astype(MATMUL_DTYPE)
    r3 = _mm(f2b, w3_ref[...])
    xh3 = (r3 - mu3_ref[...]) * inv3_ref[...]
    dr3 = c03_ref[...] * (dz - m13_ref[...] - xh3 * m23_ref[...])
    dr3b = dr3.astype(MATMUL_DTYPE)
    df2 = _mm_t(dr3b, w3_ref[...])
    da2 = jnp.where(f2 > 0, df2, 0.0)
    da2_ref[...] = da2.astype(da2_ref.dtype)
    xh2 = (r2f - mu2_ref[...]) * inv2_ref[...]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw3_ref[...] = jnp.zeros_like(dw3_ref)
        st_ref[...] = jnp.zeros_like(st_ref)

    dw3_ref[...] += _mm_tn(f2b, dr3b)
    st_ref[0, :] += jnp.sum(da2, axis=0)
    st_ref[1, :] += jnp.sum(da2 * xh2, axis=0)


def _b3(da2_ref, r2_ref, r1_ref, s1_ref, b1_ref, w2t_ref, mu2_ref,
        inv2_ref, c02_ref, m12_ref, m22_ref, mu1_ref, inv1_ref,
        da1_ref, dw2_ref, st_ref, *, H, W):
    Mb, C = r2_ref.shape
    masks = _tap_masks(Mb, H, W)
    da2 = da2_ref[...].astype(jnp.float32)
    r2f = r2_ref[...].astype(jnp.float32)
    xh2 = (r2f - mu2_ref[...]) * inv2_ref[...]
    dr2 = c02_ref[...] * (da2 - m12_ref[...] - xh2 * m22_ref[...])
    dr2b = dr2.astype(MATMUL_DTYPE)
    df1 = _conv3x3_flat(dr2, w2t_ref[...], H, W, masks)
    r1f = r1_ref[...].astype(jnp.float32)
    f1 = jnp.maximum(r1f * s1_ref[...] + b1_ref[...], 0.0)
    da1 = jnp.where(f1 > 0, df1, 0.0)
    da1_ref[...] = da1.astype(da1_ref.dtype)
    xh1 = (r1f - mu1_ref[...]) * inv1_ref[...]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw2_ref[...] = jnp.zeros_like(dw2_ref)
        st_ref[...] = jnp.zeros_like(st_ref)

    # dW2[i,j] = shift_ij(f1)^T @ dr2, same masked shifts as the conv
    for di in (-1, 0, 1):
        for dj in (-1, 0, 1):
            g = _shift(f1, di * W + dj)
            g = jnp.where(masks[di + 1][dj + 1], g, 0).astype(MATMUL_DTYPE)
            dw2_ref[di + 1, dj + 1] += _mm_tn(g, dr2b)
    st_ref[0, :] += jnp.sum(da1, axis=0)
    st_ref[1, :] += jnp.sum(da1 * xh1, axis=0)


def _b4(da1_ref, r1_ref, x_ref, dz_ref, w1_ref, mu1_ref, inv1_ref,
        c01_ref, m11_ref, m21_ref, dx_ref, dw1_ref):
    da1 = da1_ref[...].astype(jnp.float32)
    xh1 = (r1_ref[...].astype(jnp.float32)
           - mu1_ref[...]) * inv1_ref[...]
    dr1 = c01_ref[...] * (da1 - m11_ref[...] - xh1 * m21_ref[...])
    dr1b = dr1.astype(MATMUL_DTYPE)
    dx = _mm_t(dr1b, w1_ref[...]) + dz_ref[...].astype(jnp.float32)
    dx_ref[...] = dx.astype(dx_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw1_ref[...] = jnp.zeros_like(dw1_ref)

    dw1_ref[...] += _mm_tn(x_ref[...], dr1b)


# ------------------------------------------------------------ orchestration


def _pick_nb(N, H, W, C4, cap_bytes=4 << 20):
    """Batch-tile size: largest divisor of N whose 4C-wide tile stays under
    cap_bytes, with nb*H*W a multiple of 16 (bf16 sublane tile)."""
    per_img = H * W * C4 * 2
    best = None
    for nb in range(1, N + 1):
        if N % nb or (nb * H * W) % 16:
            continue
        if best is not None and nb * per_img > cap_bytes:
            break
        best = nb
    return best or N


def _stats_to_scale_bias(st, n, gamma, beta, eps):
    mean = st[0] / n
    var = jnp.maximum(st[1] / n - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + eps)
    scale = gamma * inv
    bias = beta - mean * scale
    return mean, var, scale, bias, inv


def _spec(shape, const=False):
    if const:
        return pl.BlockSpec(shape, lambda i: tuple(0 for _ in shape),
                            memory_space=pltpu.VMEM)
    return pl.BlockSpec(shape, lambda i: (i,) + tuple(0 for _ in shape[1:]),
                        memory_space=pltpu.VMEM)


def _call(kernel, grid, in_arrays, in_specs, out_shapes, out_specs,
          interpret):
    return pl.pallas_call(
        kernel, grid=(grid,), in_specs=in_specs,
        out_shape=out_shapes, out_specs=out_specs,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)(*in_arrays)


def _vec(v):
    return v.astype(jnp.float32)


def fused_bottleneck_fwd(x, w1, w2, w3, g1, be1, g2, be2, g3, be3,
                         eps=1e-5, nb=None, interpret=None):
    """x [N,H,W,C4] (bf16/f32 NHWC); w1 [C4,C], w2 [3,3,C,C], w3 [C,C4];
    per-BN gamma/beta vectors. Returns (y, residuals, stats) where stats is
    ((mean_i, var_i) per BN, f32) for running-stat updates."""
    if interpret is None:
        interpret = _default_interpret()
    N, H, W, C4 = x.shape
    C = w1.shape[1]
    if nb is None:
        nb = _pick_nb(N, H, W, C4)
    grid = N // nb
    M = N * H * W
    Mb = nb * H * W
    n = float(M)
    cdt = x.dtype
    w1c = w1.astype(MATMUL_DTYPE)
    w2c = w2.astype(MATMUL_DTYPE)
    w3c = w3.astype(MATMUL_DTYPE)
    xb = x.astype(MATMUL_DTYPE).reshape(M, C4)   # free bitcast (row-major)

    r1, st1 = _call(
        _k1, grid, (xb, w1c),
        [_spec((Mb, C4)), _spec((C4, C), const=True)],
        (jax.ShapeDtypeStruct((M, C), MATMUL_DTYPE),
         jax.ShapeDtypeStruct((2, C), jnp.float32)),
        (_spec((Mb, C)), _spec((2, C), const=True)),
        interpret)
    mu1, var1, s1, b1, inv1 = _stats_to_scale_bias(
        st1, n, _vec(g1), _vec(be1), eps)

    r2, st2 = _call(
        functools.partial(_k2, H=H, W=W), grid, (r1, s1, b1, w2c),
        [_spec((Mb, C)), _spec((C,), const=True),
         _spec((C,), const=True), _spec((3, 3, C, C), const=True)],
        (jax.ShapeDtypeStruct((M, C), MATMUL_DTYPE),
         jax.ShapeDtypeStruct((2, C), jnp.float32)),
        (_spec((Mb, C)), _spec((2, C), const=True)),
        interpret)
    mu2, var2, s2, b2, inv2 = _stats_to_scale_bias(
        st2, n, _vec(g2), _vec(be2), eps)

    st3 = _call(
        _k3, grid, (r2, s2, b2, w3c),
        [_spec((Mb, C)), _spec((C,), const=True),
         _spec((C,), const=True), _spec((C, C4), const=True)],
        jax.ShapeDtypeStruct((2, C4), jnp.float32),
        _spec((2, C4), const=True),
        interpret)
    mu3, var3, s3, b3, inv3 = _stats_to_scale_bias(
        st3, n, _vec(g3), _vec(be3), eps)

    y = _call(
        _k4, grid, (r2, xb, s2, b2, w3c, s3, b3),
        [_spec((Mb, C)), _spec((Mb, C4)),
         _spec((C,), const=True), _spec((C,), const=True),
         _spec((C, C4), const=True), _spec((C4,), const=True),
         _spec((C4,), const=True)],
        jax.ShapeDtypeStruct((M, C4), cdt),
        _spec((Mb, C4)),
        interpret)

    residuals = (xb, r1, r2, y, w1c, w2c, w3c,
                 (mu1, inv1, s1, b1, _vec(g1)),
                 (mu2, inv2, s2, b2, _vec(g2)),
                 (mu3, inv3, s3, b3, _vec(g3)))
    y4 = y.reshape(N, H, W, C4)
    return y4, residuals, ((mu1, var1), (mu2, var2), (mu3, var3))


def fused_bottleneck_bwd(residuals, dy4, nb=None, interpret=None,
                         shape=None):
    """Returns (dx, dw1, dw2, dw3, dg1, dbe1, dg2, dbe2, dg3, dbe3), all
    f32 except dx (dy's dtype). nb/interpret are re-derived when None (the
    custom_vjp path cannot thread static python values through residuals)."""
    (xb, r1, r2, y, w1c, w2c, w3c, bn1, bn2, bn3) = residuals
    N, H, W, C4 = shape if shape is not None else dy4.shape
    if interpret is None:
        interpret = _default_interpret()
    if nb is None:
        nb = _pick_nb(N, H, W, C4)
    mu1, inv1, s1, b1, g1 = bn1
    mu2, inv2, s2, b2, g2 = bn2
    mu3, inv3, s3, b3, g3 = bn3
    C = r1.shape[-1]
    grid = N // nb
    M = N * H * W
    Mb = nb * H * W
    n = float(M)
    cdt = dy4.dtype
    dy = dy4.reshape(M, C4)

    dz, stz = _call(
        _b1, grid, (dy, y, r2, s2, b2, w3c, mu3, inv3),
        [_spec((Mb, C4)), _spec((Mb, C4)),
         _spec((Mb, C)), _spec((C,), const=True),
         _spec((C,), const=True), _spec((C, C4), const=True),
         _spec((C4,), const=True), _spec((C4,), const=True)],
        (jax.ShapeDtypeStruct((M, C4), cdt),
         jax.ShapeDtypeStruct((2, C4), jnp.float32)),
        (_spec((Mb, C4)), _spec((2, C4), const=True)),
        interpret)
    dbe3, dg3 = stz[0], stz[1]
    c03 = g3 * inv3
    m13, m23 = stz[0] / n, stz[1] / n

    da2, dw3, st2 = _call(
        _b2, grid, (dz, r2, s2, b2, w3c, mu3, inv3, c03, m13, m23,
                    mu2, inv2),
        [_spec((Mb, C4)), _spec((Mb, C)),
         _spec((C,), const=True), _spec((C,), const=True),
         _spec((C, C4), const=True), _spec((C4,), const=True),
         _spec((C4,), const=True), _spec((C4,), const=True),
         _spec((C4,), const=True), _spec((C4,), const=True),
         _spec((C,), const=True), _spec((C,), const=True)],
        (jax.ShapeDtypeStruct((M, C), cdt),
         jax.ShapeDtypeStruct((C, C4), jnp.float32),
         jax.ShapeDtypeStruct((2, C), jnp.float32)),
        (_spec((Mb, C)), _spec((C, C4), const=True),
         _spec((2, C), const=True)),
        interpret)
    dbe2, dg2 = st2[0], st2[1]
    c02 = g2 * inv2
    m12, m22 = st2[0] / n, st2[1] / n

    w2t = flip_transpose_w2(w2c)
    da1, dw2, st1 = _call(
        functools.partial(_b3, H=H, W=W), grid,
        (da2, r2, r1, s1, b1, w2t, mu2, inv2, c02, m12, m22, mu1, inv1),
        [_spec((Mb, C)), _spec((Mb, C)), _spec((Mb, C)),
         _spec((C,), const=True), _spec((C,), const=True),
         _spec((3, 3, C, C), const=True), _spec((C,), const=True),
         _spec((C,), const=True), _spec((C,), const=True),
         _spec((C,), const=True), _spec((C,), const=True),
         _spec((C,), const=True), _spec((C,), const=True)],
        (jax.ShapeDtypeStruct((M, C), cdt),
         jax.ShapeDtypeStruct((3, 3, C, C), jnp.float32),
         jax.ShapeDtypeStruct((2, C), jnp.float32)),
        (_spec((Mb, C)), _spec((3, 3, C, C), const=True),
         _spec((2, C), const=True)),
        interpret)
    dbe1, dg1 = st1[0], st1[1]
    c01 = g1 * inv1
    m11, m21 = st1[0] / n, st1[1] / n

    dx, dw1 = _call(
        _b4, grid, (da1, r1, xb, dz, w1c, mu1, inv1, c01, m11, m21),
        [_spec((Mb, C)), _spec((Mb, C)), _spec((Mb, C4)),
         _spec((Mb, C4)), _spec((C4, C), const=True),
         _spec((C,), const=True), _spec((C,), const=True),
         _spec((C,), const=True), _spec((C,), const=True),
         _spec((C,), const=True)],
        (jax.ShapeDtypeStruct((M, C4), cdt),
         jax.ShapeDtypeStruct((C4, C), jnp.float32)),
        (_spec((Mb, C4)), _spec((C4, C), const=True)),
        interpret)

    return (dx.reshape(N, H, W, C4), dw1, dw2, dw3,
            dg1, dbe1, dg2, dbe2, dg3, dbe3)


# ------------------------------------------------------- reference (jnp)


def bottleneck_reference(x, w1, w2, w3, g1, be1, g2, be2, g3, be3,
                         eps=1e-5):
    """Pure-jnp train-mode bottleneck — the semantic spec for the kernels
    (matches the nn.Conv2D/BatchNorm2D composition in models/resnet.py).
    f32 math throughout; output cast to x.dtype."""
    f32 = jnp.float32
    xf = x.astype(f32)

    def bn(r, g, be):
        mu = jnp.mean(r, axis=(0, 1, 2))
        var = jnp.var(r, axis=(0, 1, 2))
        xh = (r - mu) * jax.lax.rsqrt(var + eps)
        return xh * g.astype(f32) + be.astype(f32), mu, var

    r1 = jax.lax.dot_general(xf, w1.astype(f32), (((3,), (0,)), ((), ())))
    a1, mu1, var1 = bn(r1, g1, be1)
    f1 = jnp.maximum(a1, 0.0)
    r2 = jax.lax.conv_general_dilated(
        f1, w2.astype(f32), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    a2, mu2, var2 = bn(r2, g2, be2)
    f2 = jnp.maximum(a2, 0.0)
    r3 = jax.lax.dot_general(f2, w3.astype(f32), (((3,), (0,)), ((), ())))
    a3, mu3, var3 = bn(r3, g3, be3)
    y = jnp.maximum(a3 + xf, 0.0).astype(x.dtype)
    return y, ((mu1, var1), (mu2, var2), (mu3, var3))


# ------------------------------------------------------- custom_vjp op


@functools.partial(jax.custom_vjp, nondiff_argnums=(10,))
def fused_bottleneck(x, w1, w2, w3, g1, be1, g2, be2, g3, be3, eps=1e-5):
    """Differentiable fused bottleneck.
    Returns (y, mu1, var1, mu2, var2, mu3, var3); the stats are detached
    (running-stat updates only, like the reference's BN)."""
    y, _res, stats = fused_bottleneck_fwd(
        x, w1, w2, w3, g1, be1, g2, be2, g3, be3, eps=eps)
    return (y,) + _flat(stats)


def _flat(stats):
    (mu1, v1), (mu2, v2), (mu3, v3) = stats
    return (mu1, v1, mu2, v2, mu3, v3)


def _fwd_rule(x, w1, w2, w3, g1, be1, g2, be2, g3, be3, eps):
    y, res, stats = fused_bottleneck_fwd(
        x, w1, w2, w3, g1, be1, g2, be2, g3, be3, eps=eps)
    return (y,) + _flat(stats), res


def _bwd_rule(eps, res, cts):
    dy = cts[0]
    grads = fused_bottleneck_bwd(res, dy)
    # contract: x's cotangent matches x/y dtype; params are f32 (see
    # fused_bottleneck_auto) so the f32 kernel grads already match
    return (grads[0].astype(dy.dtype),) + tuple(grads[1:])


fused_bottleneck.defvjp(_fwd_rule, _bwd_rule)


def fused_bottleneck_auto(x, w1, w2, w3, g1, be1, g2, be2, g3, be3,
                          eps=1e-5):
    """Caller-facing wrapper: casts params to f32 before the custom_vjp
    boundary (the cast's transpose re-casts grads to the caller's param
    dtype automatically), so the op has one canonical signature."""
    f32 = jnp.float32
    return fused_bottleneck(
        x, w1.astype(f32), w2.astype(f32), w3.astype(f32),
        g1.astype(f32), be1.astype(f32), g2.astype(f32), be2.astype(f32),
        g3.astype(f32), be3.astype(f32), eps)

def fused_block_impl(x, cw1, cw2, cw3, g1, be1, g2, be2, g3, be3, *, eps):
    """Dispatch-layer impl (models/resnet.py): takes the layer's native
    OIHW conv weights and re-views them for the flat kernels."""
    w1 = jnp.transpose(cw1[:, :, 0, 0], (1, 0))       # [C,C4,1,1]->[C4,C]
    w2 = jnp.transpose(cw2, (2, 3, 1, 0))             # OIHW -> HWIO
    w3 = jnp.transpose(cw3[:, :, 0, 0], (1, 0))       # [C4,C,1,1]->[C,C4]
    return fused_bottleneck_auto(x, w1, w2, w3, g1, be1, g2, be2, g3, be3,
                                 eps)


# ---------------------------------------------------------------- stage probe
# Round-5 (VERDICT r4 item 3): the only cross-block fusion the BN stat
# barriers permit is the block BOUNDARY — block N's affine3+residual+relu
# (k4) coupled with block N+1's 1x1 conv + stats (k1), keeping y in VMEM
# between them (y must still WRITE to HBM: it is block N+1's residual input
# and a backward residual). Everything deeper is barred: each BN needs its
# batch statistics complete before its affine, forcing a full pass over the
# activation per BN regardless of fusion. This kernel + the chain below
# exist to MEASURE that boundary coupling (tools/bench_resstage.py).


def _k41(r2_ref, x_ref, s2_ref, b2_ref, w3_ref, s3_ref, b3_ref, w1n_ref,
         y_ref, r1n_ref, st_ref):
    f2 = _affine_relu(r2_ref[...], s2_ref[...], b2_ref[...]) \
        .astype(MATMUL_DTYPE)
    r3 = _mm(f2, w3_ref[...])
    z = r3 * s3_ref[...] + b3_ref[...] + x_ref[...].astype(jnp.float32)
    y = jnp.maximum(z, 0.0)
    y_ref[...] = y.astype(y_ref.dtype)
    r1n = _mm(y.astype(MATMUL_DTYPE), w1n_ref[...])
    r1n_ref[...] = r1n.astype(r1n_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    st_ref[0, :] += jnp.sum(r1n, axis=0)
    st_ref[1, :] += jnp.sum(r1n * r1n, axis=0)


def fused_bottleneck2_fwd(x, params1, params2, eps=1e-5, nb=None,
                          interpret=None):
    """Two stride-1 bottleneck blocks chained with the k4->k1 boundary
    coupling. params_i = (w1, w2, w3, g1, be1, g2, be2, g3, be3).
    Forward-only probe (the measured stage-coupling record)."""
    if interpret is None:
        interpret = _default_interpret()
    N, H, W, C4 = x.shape
    w1, w2, w3, g1, be1, g2, be2, g3, be3 = params1
    w1n = params2[0]
    C = w1.shape[1]
    if nb is None:
        nb = _pick_nb(N, H, W, C4)
    grid = N // nb
    M, Mb, n = N * H * W, nb * H * W, float(N * H * W)
    cdt = x.dtype
    w1c, w2c, w3c = (w.astype(MATMUL_DTYPE) for w in (w1, w2, w3))
    w1nc = w1n.astype(MATMUL_DTYPE)
    xb = x.astype(MATMUL_DTYPE).reshape(M, C4)

    r1, st1 = _call(
        _k1, grid, (xb, w1c),
        [_spec((Mb, C4)), _spec((C4, C), const=True)],
        (jax.ShapeDtypeStruct((M, C), MATMUL_DTYPE),
         jax.ShapeDtypeStruct((2, C), jnp.float32)),
        (_spec((Mb, C)), _spec((2, C), const=True)), interpret)
    _, _, s1, b1, _ = _stats_to_scale_bias(st1, n, _vec(g1), _vec(be1), eps)

    r2, st2 = _call(
        functools.partial(_k2, H=H, W=W), grid, (r1, s1, b1, w2c),
        [_spec((Mb, C)), _spec((C,), const=True),
         _spec((C,), const=True), _spec((3, 3, C, C), const=True)],
        (jax.ShapeDtypeStruct((M, C), MATMUL_DTYPE),
         jax.ShapeDtypeStruct((2, C), jnp.float32)),
        (_spec((Mb, C)), _spec((2, C), const=True)), interpret)
    _, _, s2, b2, _ = _stats_to_scale_bias(st2, n, _vec(g2), _vec(be2), eps)

    st3 = _call(
        _k3, grid, (r2, s2, b2, w3c),
        [_spec((Mb, C)), _spec((C,), const=True),
         _spec((C,), const=True), _spec((C, C4), const=True)],
        jax.ShapeDtypeStruct((2, C4), jnp.float32),
        _spec((2, C4), const=True), interpret)
    _, _, s3, b3, _ = _stats_to_scale_bias(st3, n, _vec(g3), _vec(be3), eps)

    # boundary coupling: y1 stays in VMEM for block2's k1
    y1, r1b, st1b = _call(
        _k41, grid, (r2, xb, s2, b2, w3c, s3, b3, w1nc),
        [_spec((Mb, C)), _spec((Mb, C4)),
         _spec((C,), const=True), _spec((C,), const=True),
         _spec((C, C4), const=True), _spec((C4,), const=True),
         _spec((C4,), const=True), _spec((C4, C), const=True)],
        (jax.ShapeDtypeStruct((M, C4), cdt),
         jax.ShapeDtypeStruct((M, C), MATMUL_DTYPE),
         jax.ShapeDtypeStruct((2, C), jnp.float32)),
        (_spec((Mb, C4)), _spec((Mb, C)), _spec((2, C), const=True)),
        interpret)

    _, w2b, w3b, g1b, be1b, g2b, be2b, g3b, be3b = params2
    w2bc, w3bc = w2b.astype(MATMUL_DTYPE), w3b.astype(MATMUL_DTYPE)
    _, _, s1b, b1b, _ = _stats_to_scale_bias(
        st1b, n, _vec(g1b), _vec(be1b), eps)

    r2b, st2b = _call(
        functools.partial(_k2, H=H, W=W), grid, (r1b, s1b, b1b, w2bc),
        [_spec((Mb, C)), _spec((C,), const=True),
         _spec((C,), const=True), _spec((3, 3, C, C), const=True)],
        (jax.ShapeDtypeStruct((M, C), MATMUL_DTYPE),
         jax.ShapeDtypeStruct((2, C), jnp.float32)),
        (_spec((Mb, C)), _spec((2, C), const=True)), interpret)
    _, _, s2b, b2b, _ = _stats_to_scale_bias(
        st2b, n, _vec(g2b), _vec(be2b), eps)

    st3b = _call(
        _k3, grid, (r2b, s2b, b2b, w3bc),
        [_spec((Mb, C)), _spec((C,), const=True),
         _spec((C,), const=True), _spec((C, C4), const=True)],
        jax.ShapeDtypeStruct((2, C4), jnp.float32),
        _spec((2, C4), const=True), interpret)
    _, _, s3b, b3b, _ = _stats_to_scale_bias(
        st3b, n, _vec(g3b), _vec(be3b), eps)

    y2 = _call(
        _k4, grid, (r2b, y1.astype(MATMUL_DTYPE).reshape(M, C4), s2b, b2b,
                    w3bc, s3b, b3b),
        [_spec((Mb, C)), _spec((Mb, C4)),
         _spec((C,), const=True), _spec((C,), const=True),
         _spec((C, C4), const=True), _spec((C4,), const=True),
         _spec((C4,), const=True)],
        jax.ShapeDtypeStruct((M, C4), cdt),
        _spec((Mb, C4)), interpret)
    return y2.reshape(N, H, W, C4)
