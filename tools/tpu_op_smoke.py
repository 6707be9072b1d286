"""On-chip op and kernel sanity sweep (the tier-1 suite is CPU-only).

Two sweeps on the REAL TPU device:

* a representative subset of the schema registry's sampled ops against the
  numpy references — evidence the op surface is numerically correct on the
  hardware the framework targets, not just on the CPU stand-in;
* every Pallas kernel in `paddle_tpu/ops/pallas/`, compiled by Mosaic at a
  shape its caller uses and compared with its XLA reference (or with the
  same kernel in interpret mode). Each line reads "compiles" with the
  error against the reference, or carries the compiler's message verbatim.

Run: python tools/tpu_op_smoke.py        (refuses anything but a TPU)
     python tools/tpu_op_smoke.py --topology v5e:2x2
The second form needs no chip: it only COMPILES the kernels against that
TPU topology (libtpu's compile-only client), which is how to screen a
kernel for Mosaic refusals from a machine without an accelerator. Nothing
runs, so it proves nothing about results.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.ops import schema
from paddle_tpu.ops.samples import install_samples

REPRESENTATIVE = [
    # one per family: elementwise, reduction, manipulation, linalg, nn
    "add", "multiply", "exp", "tanh", "sigmoid", "logsumexp", "softmax_like",
    "sum", "mean", "max", "cumsum", "sort", "topk",
    "concat", "reshape", "transpose", "gather", "scatter_nd_add", "where",
    "matmul", "bmm", "einsum", "tril", "norm",
    "nn.functional.relu", "nn.functional.gelu", "nn.functional.softmax",
    "nn.functional.layer_norm", "nn.functional.linear",
    "nn.functional.conv2d", "nn.functional.max_pool2d",
    "nn.functional.cross_entropy", "nn.functional.mse_loss",
    "nn.functional.scaled_dot_product_attention",
    "incubate.nn.functional.swiglu",
]


def _to_tensors(v):
    if isinstance(v, np.ndarray):
        return paddle.to_tensor(v)
    if isinstance(v, (list, tuple)) and v and isinstance(v[0], np.ndarray):
        return type(v)(paddle.to_tensor(a) for a in v)
    return v


def _rel_err(got, want):
    """Largest error over the pytree, relative to each leaf's scale."""
    import jax

    worst = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g, "float64"), np.asarray(w, "float64")
        worst = max(worst, float(np.max(np.abs(g - w))
                                 / (np.max(np.abs(w)) + 1e-6)))
    return worst


def kernel_cases():
    """`(name, kernel, reference, args)` per Pallas kernel: `kernel(*args)`
    goes through Mosaic (interpret=False), `reference(*args)` is the XLA
    twin (or the same kernel in interpret mode)."""
    import functools

    import jax
    import jax.numpy as jnp
    from importlib import import_module

    # by module path: the package re-exports `flash_attention` the function
    bgmv, bsa, da, fa, wo = (
        import_module(f"paddle_tpu.ops.pallas.{m}") for m in (
            "bgmv", "block_sparse_attention", "decode_attn",
            "flash_attention", "weight_only"))

    rng = np.random.RandomState(0)

    def r(*shape, dtype="bfloat16", scale=1.0):
        return jnp.asarray(rng.randn(*shape) * scale, dtype)

    def i8(*shape):
        return jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)

    def with_grads(f):
        def run(*args):
            out, vjp = jax.vjp(f, *args)
            return (out,) + vjp(jnp.ones_like(out))
        return run

    cases = []
    # the flagship trainer's kernels at its shape, and one ragged length
    xla_attn = functools.partial(jax.nn.dot_product_attention,
                                 is_causal=True)
    for s in (1024, 1000):
        cases.append((
            f"flash_attention fwd+dq+dkv (seq {s}, 12 x 64 heads)",
            with_grads(functools.partial(fa.flash_attention, causal=True,
                                         interpret=False)),
            with_grads(xla_attn), (r(2, s, 12, 64), r(2, s, 12, 64),
                                   r(2, s, 12, 64))))
    # ring-attention building blocks: arbitrary global positions
    bh, s, d = 8, 512, 64
    q_pos = jnp.arange(s, dtype=jnp.int32) + s
    k_pos = jnp.arange(s, dtype=jnp.int32)[::-1] * 2
    pos_args = (r(bh, s, d), r(bh, s, d), r(bh, s, d), q_pos, k_pos)
    fwd_pos = functools.partial(fa.flash_fwd_pos, scale=0.125)
    cases.append(("flash_fwd_pos (ring step fwd)",
                  functools.partial(fwd_pos, interpret=False),
                  functools.partial(fwd_pos, interpret=True), pos_args))
    bwd_pos = functools.partial(fa.flash_bwd_pos, scale=0.125)
    bwd_args = (*pos_args[:3], r(bh, s, d), r(bh, s, 1, dtype="float32"),
                r(bh, s, 1, dtype="float32"), q_pos, k_pos)
    cases.append(("flash_bwd_pos (ring step dq + dkv)",
                  functools.partial(bwd_pos, interpret=False),
                  functools.partial(bwd_pos, interpret=True), bwd_args))
    # multi-tenant LoRA: one adapter slot per row, rank 8
    ids = jnp.asarray([0, 1, 2, 3, 3, 2, 1, 0], jnp.int32)
    cases.append(("bgmv.lora_delta (8 rows, rank 8, 768 -> 768)",
                  functools.partial(bgmv.lora_delta, use_kernel=True,
                                    interpret=False),
                  functools.partial(bgmv.lora_delta, use_kernel=False),
                  (r(8, 1, 768), r(4, 768, 8, scale=0.05),
                   r(4, 8, 768, scale=0.05), ids)))
    # int8-KV decode attention: dense cache, then through block tables
    B, H, T, D = 4, 12, 256, 64
    dense = (r(B, 1, H, D), i8(B, H, T, D),
             jnp.abs(r(B, H, T, 1, dtype="float32", scale=0.01)),
             i8(B, H, T, D),
             jnp.abs(r(B, H, T, 1, dtype="float32", scale=0.01)),
             jnp.asarray(200, jnp.int32))
    cases.append(("decode_attn.decode_attention (int8 KV, T 256)",
                  functools.partial(da.decode_attention, interpret=False),
                  functools.partial(da.decode_attention, interpret=True),
                  dense))
    N, BS, NB = 40, 16, 8
    tables = jnp.asarray(rng.permutation(np.arange(1, N))[:B * NB]
                         .reshape(B, NB), jnp.int32)
    paged = (r(B, 1, H, D), i8(N, H, BS, D),
             jnp.abs(r(N, H, BS, 1, dtype="float32", scale=0.01)),
             i8(N, H, BS, D),
             jnp.abs(r(N, H, BS, 1, dtype="float32", scale=0.01)),
             tables, jnp.asarray([5, 40, 77, 120], jnp.int32))
    cases.append(("decode_attn.paged_decode_attention (block 16 x 8)",
                  functools.partial(da.paged_decode_attention,
                                    use_kernel=True, interpret=False),
                  functools.partial(da.paged_decode_attention,
                                    use_kernel=False), paged))
    # weight-only matmuls
    scale = jnp.abs(r(3072, dtype="float32", scale=0.01))
    for wd, kw in (("int8", 768), ("int4", 384)):
        mm = functools.partial(wo.weight_only_matmul, weight_dtype=wd)
        cases.append((f"weight_only_matmul {wd} (8 x 768 -> 3072)",
                      functools.partial(mm, interpret=False),
                      functools.partial(mm, interpret=True),
                      (r(8, 768), i8(3072, kw), scale)))
    # block-sparse attention over a causal block pattern
    nq = 4
    idx = jnp.asarray(np.tril(np.ones((nq, nq), int))
                      * np.arange(nq)[None], jnp.int32)
    cnt = jnp.arange(1, nq + 1, dtype=jnp.int32)
    bs_kw = dict(scale=0.125, block_size=128)
    cases.append(("block_sparse_attention fwd (seq 512, block 128)",
                  functools.partial(bsa._bs_pallas, interpret=False,
                                    **bs_kw),
                  functools.partial(bsa._bs_reference, **bs_kw),
                  (r(4, 512, 64), r(4, 512, 64), r(4, 512, 64), idx, cnt)))
    return cases


def kernel_sweep(topology=None):
    """Compile (and, on a chip, run and compare) every Pallas kernel.
    Returns the names that failed."""
    import jax

    device = None
    if topology:
        from jax.experimental import topologies

        device = topologies.get_topology_desc(
            platform="tpu", topology_name=topology).devices[0]
    failures = []
    for name, kernel, reference, args in kernel_cases():
        try:
            if device is not None:
                sh = jax.sharding.SingleDeviceSharding(device)
                jax.jit(kernel).lower(*(jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=sh) for a in args)).compile()
                print(f"  {name:58s} compiles")
                continue
            got = jax.block_until_ready(jax.jit(kernel)(*args))
        except Exception as e:  # noqa: BLE001 — the sweep's product IS the
            # compiler's message, per kernel
            print(f"  {name:58s} REFUSED\n{e}\n")
            failures.append(name)
            continue
        finite = all(bool(np.all(np.isfinite(np.asarray(g, "float32"))))
                     for g in jax.tree_util.tree_leaves(got))
        err = _rel_err(got, jax.jit(reference)(*args))
        # bf16 operands: a few bf16 ulps of the result's scale
        ok = finite and err <= 3e-2
        print(f"  {name:58s} compiles, rel err {err:.1e}"
              + ("" if ok else "  MISMATCH"))
        if not ok:
            failures.append(name)
    return failures


def op_sweep():
    install_samples()
    failures = []
    ran = 0
    for name in REPRESENTATIVE:
        spec = schema.OPS.get(name)
        if spec is None or spec.sample is None or spec.np_ref is None:
            continue
        args, kwargs = spec.sample()
        out = spec.fn(*[_to_tensors(a) for a in args], **kwargs)
        out = out[0] if isinstance(out, (tuple, list)) else out
        got = np.asarray(out._value if isinstance(out, Tensor) else out,
                         "float64")
        want = np.asarray(spec.np_ref(*args, **kwargs), "float64")
        ran += 1
        # TPU default matmul/conv precision is bf16-class: convs
        # accumulate more terms, so they get a wider budget
        tol = max(spec.tol, 2e-2 if "conv" in name else 2e-3)
        ok = np.allclose(got, want, rtol=tol, atol=tol)
        print(f"  {name:48s} {'OK' if ok else 'FAIL'}")
        if not ok:
            failures.append(name)
    print(f"{ran} ops on-chip, {len(failures)} failures: {failures}")
    return failures


def main(argv=None):
    import argparse

    import jax

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--topology", help="compile the kernels against this "
                    "TPU topology (e.g. v5e:2x2) without a chip; runs "
                    "nothing")
    args = ap.parse_args(argv)
    if args.topology:
        print(f"compile-only against topology {args.topology}")
        failures = kernel_sweep(args.topology)
        print(f"{len(failures)} kernel(s) refused: {failures}")
        return 1 if failures else 0
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"tpu_op_smoke: found platform {dev.platform!r} "
              f"({dev.device_kind}), not a TPU — an on-chip sweep on "
              f"another device proves nothing", file=sys.stderr)
        return 1
    print(f"platform: {dev.platform} ({dev.device_kind}, "
          f"{jax.device_count()} device(s))")
    failures = op_sweep()
    kfail = kernel_sweep()
    print(f"{len(kfail)} kernel(s) refused or mismatched: {kfail}")
    return 1 if failures or kfail else 0


if __name__ == "__main__":
    sys.exit(main())
