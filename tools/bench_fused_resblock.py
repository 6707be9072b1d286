"""On-chip microbench: fused Pallas bottleneck vs XLA composition, per
ResNet-50 stage shape. Times a lax.scan chain inside ONE jit (a
host-readback fence ends each window; chained carries so nothing is
hoisted)."""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))
from paddle_tpu.ops.pallas import fused_resblock as fr  # noqa: E402

STAGES = {
    # name: (H, C, C4)
    "s1_56x64": (56, 64, 256),
    "s2_28x128": (28, 128, 512),
    "s3_14x256": (14, 256, 1024),
    "s4_7x512": (7, 512, 2048),
}


def make_args(H, C, C4, N):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(N, H, H, C4).astype(np.float32) * 0.5
                    ).astype(jnp.bfloat16)
    w1 = jnp.asarray(rng.randn(C4, C).astype(np.float32) * (C4 ** -0.5))
    w2 = jnp.asarray(rng.randn(3, 3, C, C).astype(np.float32) * 0.06)
    w3 = jnp.asarray(rng.randn(C, C4).astype(np.float32) * (C ** -0.5))
    g1, b1 = jnp.ones(C), jnp.zeros(C)
    g2, b2 = jnp.ones(C) * 1.1, jnp.zeros(C) + 0.05
    g3, b3 = jnp.ones(C4) * 0.9, jnp.zeros(C4) - 0.02
    return (x, w1, w2, w3, g1, b1, g2, b2, g3, b3)


def timed(fn, x, L):
    """The fixed dispatch+readback cost sits in every window, so time two
    scan lengths and difference them: what is left is the chain."""
    out = fn(x, L)
    float(jnp.sum(out[0].astype(jnp.float32)))  # fence warmup (compile L)
    L2 = L * 6
    out = fn(x, L2)
    float(jnp.sum(out[0].astype(jnp.float32)))  # fence warmup (compile L2)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = fn(x, L)
        float(jnp.sum(out[0].astype(jnp.float32)))
        t1 = time.perf_counter()
        out = fn(x, L2)
        float(jnp.sum(out[0].astype(jnp.float32)))
        t2 = time.perf_counter()
        best = min(best, ((t2 - t1) - (t1 - t0)) / (L2 - L))
    return best


def bench_stage(name, H, C, C4, N=128, L=500, mode="fwdbwd"):
    args = make_args(H, C, C4, N)
    x0, params = args[0], args[1:]

    def fused_fwd(x):
        return fr.fused_bottleneck_auto(x, *params)[0]

    def ref_fwd(x):
        return fr.bottleneck_reference(x, *params)[0]

    results = {}
    for label, f in (("fused", fused_fwd), ("xla", ref_fwd)):
        if mode == "fwd":
            def body(x, _):
                y = f(x)
                return y, ()
        else:
            def body(x, _):
                y, vjp = jax.vjp(f, x)
                (dx,) = vjp(y)  # dy := y, keeps the chain data-dependent
                return dx, ()


        stepper = jax.jit(
            lambda x, n: jax.lax.scan(body, x, None, length=n)[0],
            static_argnums=1)
        try:
            dt = timed(stepper, x0, L)
        except Exception as e:  # noqa: BLE001
            results[label] = None
            print(f"  {label}: FAILED {type(e).__name__}: {str(e)[:200]}")
            continue
        results[label] = dt
        # traffic model (fused): fwd 17C + bwd 27C units of HW*2B
        print(f"  {label}: {dt*1e3:8.3f} ms/block")
    if results.get("fused") and results.get("xla"):
        print(f"  speedup: {results['xla']/results['fused']:.2f}x")
    return results


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "fwdbwd"
    N = int(sys.argv[2]) if len(sys.argv) > 2 else 128
    only = sys.argv[3] if len(sys.argv) > 3 else None
    for name, (H, C, C4) in STAGES.items():
        if only and only != name:
            continue
        print(f"{name} (H={H}, C={C}, C4={C4}, N={N}, {mode}):")
        bench_stage(name, H, C, C4, N=N, mode=mode)
