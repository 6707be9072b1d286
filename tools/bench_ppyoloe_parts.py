"""Per-part bound analysis for the PP-YOLOE bench (VERDICT r4 weak #2):
is the detector head/assignment overhead-bound, or is the whole model in
the same HBM-bound conv regime as ResNet (docs/resnet50_roofline.md)?

Times three nested jitted programs — backbone only, backbone+head
(forward), full loss — fwd and fwd+bwd, fenced by host readback with a
pipelined inner loop (bench discipline, see bench.py). FLOPs come from
XLA's cost analysis of each compiled program, so per-part MFU and the
differential costs (head = forward - backbone, assignment = loss -
forward) are accounted against the code actually run.

Run on the real chip: `python tools/bench_ppyoloe_parts.py`.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BATCH = int(os.environ.get("BENCH_BATCH", "16"))
SIZE = int(os.environ.get("BENCH_SIZE", "640"))
STEPS = int(os.environ.get("BENCH_STEPS", "20"))


def main():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.device import chip_peaks

    # the one sourced table; a device_kind without a row is an error
    peak = chip_peaks(jax.devices()[0].device_kind)["bf16_flops"]
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.distributed.functional import functionalize
    from paddle_tpu.vision.models import ppyoloe_s

    on_tpu = jax.devices()[0].platform != "cpu"
    if not on_tpu:
        print("WARNING: not on TPU; numbers are not meaningful")

    paddle.seed(0)
    model = ppyoloe_s(num_classes=80, max_boxes=16, data_format="NHWC")

    rng = np.random.RandomState(0)
    img = jnp.asarray(rng.randn(BATCH, SIZE, SIZE, 3), jnp.bfloat16)
    x0 = rng.uniform(0, SIZE * 0.6, (BATCH, 16, 2))
    wh = rng.uniform(SIZE * 0.05, SIZE * 0.35, (BATCH, 16, 2))
    gb = jnp.asarray(np.concatenate([x0, np.minimum(x0 + wh, SIZE - 1)], -1),
                     jnp.float32)
    gl = jnp.asarray(rng.randint(0, 80, (BATCH, 16)), jnp.int32)
    gm = jnp.asarray((rng.rand(BATCH, 16) < 0.5), jnp.bool_)

    def build(method):
        apply_fn, params, buffers = functionalize(model, method=method)
        pvals = {n: (p._value.astype(jnp.bfloat16)
                     if jnp.issubdtype(p._value.dtype, jnp.floating)
                     else p._value) for n, p in params.items()}
        bvals = {n: b._value for n, b in buffers.items()}
        return apply_fn, pvals, bvals

    ap_bb, pv, bv = build(lambda x: model.backbone(x))
    ap_fw, _, _ = build(lambda x: model.forward(x))
    ap_ls, _, _ = build(
        lambda x, b, l, m: model.loss(x, b, l, m))

    def leaves_sum(o):
        return sum(jnp.sum(v.astype(jnp.float32))
                   for v in jax.tree_util.tree_leaves(o)
                   if hasattr(v, "dtype")
                   and jnp.issubdtype(v.dtype, jnp.floating))

    def fwd_fn(apply_fn, *batch):
        def f(pvals, *b):
            out, _ = apply_fn(pvals, bv, *[Tensor(x) for x in b])
            return leaves_sum(out if not isinstance(out, Tensor) else [out])
        return f

    progs = {
        "backbone_fwd": (fwd_fn(ap_bb), (img,)),
        "forward_fwd": (fwd_fn(ap_fw), (img,)),
        "loss_fwd": (fwd_fn(ap_ls), (img, gb, gl, gm)),
    }
    for name in list(progs):
        f, batch = progs[name]
        progs[name.replace("_fwd", "_fwdbwd")] = (
            (lambda f=f: lambda pvals, *b: jax.grad(f)(pvals, *b))(),
            batch)

    results = {}
    for name, (f, batch) in progs.items():
        jf = jax.jit(f)
        flops = float(
            jf.lower(pv, *batch).compile().cost_analysis()["flops"])
        out = jf(pv, *batch)
        _fence(out)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            o = None
            for _ in range(STEPS):
                o = jf(pv, *batch)
            _fence(o)
            best = min(best, (time.perf_counter() - t0) / STEPS)
        mfu = flops / best / peak
        results[name] = (best, flops, mfu)
        print(f"{name:18s} {best * 1e3:8.2f} ms  {flops / 1e9:9.1f} GF  "
              f"MFU {mfu * 100:5.1f}%")

    # differentials: where the non-conv time lives
    for tag, a, b in (("head (fwd)", "forward_fwd", "backbone_fwd"),
                      ("assign+loss (fwd)", "loss_fwd", "forward_fwd"),
                      ("head (fwdbwd)", "forward_fwdbwd", "backbone_fwdbwd"),
                      ("assign+loss (fwdbwd)", "loss_fwdbwd",
                       "forward_fwdbwd")):
        dt = results[a][0] - results[b][0]
        df = results[a][1] - results[b][1]
        mfu = df / dt / peak if dt > 0 else float("nan")
        print(f"{tag:22s} {dt * 1e3:8.2f} ms  {df / 1e9:9.1f} GF  "
              f"differential MFU {mfu * 100:5.1f}%")

    tot = results["loss_fwdbwd"]
    print(f"\ntrain-step-equivalent (loss fwd+bwd): {tot[0] * 1e3:.2f} ms "
          f"-> {BATCH / tot[0]:.0f} img/s, MFU {tot[2] * 100:.1f}%")


def _fence(tree):
    import jax
    for v in jax.tree_util.tree_leaves(tree):
        if hasattr(v, "dtype"):
            np.asarray(v)
            break


if __name__ == "__main__":
    main()
