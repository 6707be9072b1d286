"""Stage-coupling probe (VERDICT r4 item 3): measure the ONLY cross-block
fusion the BN stat barriers permit — the k4→k1 block-boundary coupling —
against 2× the round-4 fused block and XLA's per-op path, on the stride-1
stage3 bottleneck shape. Run on the real chip:
`python tools/bench_resstage.py`.

Expectation from arithmetic (docs/resnet50_roofline.md round-4 section):
the coupling saves one HBM re-read of y (~13 MB at bs=128 ≈ 0.016 ms)
against a measured ~0.2 ms/block MXU-efficiency deficit of the fused
path; a stage kernel cannot win. This probe turns that argument into a
measurement.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N = int(os.environ.get("BENCH_BATCH", "128"))
H = W = int(os.environ.get("BENCH_HW", "14"))
C = int(os.environ.get("BENCH_C", "256"))
STEPS = int(os.environ.get("BENCH_STEPS", "30"))


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.fused_resblock import (
        bottleneck_reference, fused_bottleneck_fwd, fused_bottleneck2_fwd)

    C4 = 4 * C
    rng = np.random.RandomState(0)

    def params(seed):
        r = np.random.RandomState(seed)
        return (jnp.asarray(r.randn(C4, C) * 0.05, jnp.bfloat16),
                jnp.asarray(r.randn(3, 3, C, C) * 0.05, jnp.bfloat16),
                jnp.asarray(r.randn(C, C4) * 0.05, jnp.bfloat16),
                jnp.ones((C,), jnp.float32), jnp.zeros((C,), jnp.float32),
                jnp.ones((C,), jnp.float32), jnp.zeros((C,), jnp.float32),
                jnp.ones((C4,), jnp.float32), jnp.zeros((C4,), jnp.float32))

    p1, p2 = params(1), params(2)
    x = jnp.asarray(rng.randn(N, H, W, C4) * 0.5, jnp.bfloat16)

    @jax.jit
    def xla2(x, p1, p2):
        y = bottleneck_reference(x, *p1)[0]
        return bottleneck_reference(y, *p2)[0]

    @jax.jit
    def fused2(x, p1, p2):
        y = fused_bottleneck_fwd(x, *p1)[0]
        return fused_bottleneck_fwd(y, *p2)[0]

    @jax.jit
    def coupled2(x, p1, p2):
        return fused_bottleneck2_fwd(x, p1, p2)

    # numerics first: the coupled chain must match the XLA reference
    ref = np.asarray(xla2(x, p1, p2), np.float32)
    got = np.asarray(coupled2(x, p1, p2), np.float32)
    err = np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6)
    print(f"coupled-chain rel err vs XLA reference: {err:.2e}")
    assert err < 5e-2, err

    # differential scan-chain timing: the per-call dispatch overhead sits
    # in every window — chain R repetitions inside ONE jit, measure at R
    # and 2R, and difference it out
    def chain(f, reps):
        @jax.jit
        def run(x, p1, p2):
            def body(c, _):
                return f(c, p1, p2).astype(c.dtype), ()
            y, _ = jax.lax.scan(body, x, None, length=reps)
            return y
        return run

    R = int(os.environ.get("BENCH_REPS", "20"))

    def bench_diff(f):
        f1, f2 = chain(f, R), chain(f, 2 * R)
        np.asarray(f1(x, p1, p2)), np.asarray(f2(x, p1, p2))  # compile
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(f1(x, p1, p2))
            t1 = time.perf_counter()
            np.asarray(f2(x, p1, p2))
            t2 = time.perf_counter()
            best = min(best, ((t2 - t1) - (t1 - t0)) / R)
        return best

    t_xla = bench_diff(xla2)
    t_fused = bench_diff(fused2)
    t_coupled = bench_diff(coupled2)
    print(f"XLA per-op 2-block fwd : {t_xla * 1e3:7.3f} ms")
    print(f"fused 2x single-block  : {t_fused * 1e3:7.3f} ms")
    print(f"fused + k4->k1 coupling: {t_coupled * 1e3:7.3f} ms "
          f"(coupling saves {(t_fused - t_coupled) * 1e3:+.3f} ms)")


if __name__ == "__main__":
    main()
