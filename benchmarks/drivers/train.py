"""Training cells: `dist.parallelize(...)` -> `train_batches`, a fused
dispatch of several optimizer steps on batches that all differ.

Set-up builds one engine, drives it from the seed through its first dispatch
(the window's own call and feed), keeps what that dispatch produced for the
comparison, and hands the same engine to the window. The plain reference
follows those first steps once the window has closed and the engine is freed.
"""
from __future__ import annotations

import collections
import gc
import time

import numpy as np

from benchmarks import harness, weights
from benchmarks.reference import gpt_ref
from benchmarks.traffic import generate

def build_engine(cell, devices, w0):
    """The program's trainer for this cell, holding the benchmark's
    weights."""
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.sharding import MeshConfig

    mix = cell["mix"]
    o = mix["optimizer"]
    net = GPTForCausalLM(GPTConfig(**cell["model"]))
    for n, p in net.named_parameters():
        p._value = w0[n]
    opt = getattr(paddle.optimizer, o["name"])(
        learning_rate=o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        parameters=net.parameters(),
        grad_clip=paddle.nn.ClipGradByGlobalNorm(o["clip_norm"]))
    mesh = MeshConfig(**mix["mesh"]) if mix.get("mesh") \
        else dist.build_mesh(dp=-1, devices=devices[:1])
    return dist.parallelize(net, opt, mesh=mesh,
                            compute_dtype=mix["compute_dtype"])


def leaf_readings(moment1, params, model, seed):
    """Per leaf the norm of the first moment and of the parameters' change
    from the seed's weights (made again here: neither side keeps them)."""
    import jax

    w0 = weights.make(model, seed, "float32")
    delta = jax.jit(lambda a, b: {k: a[k] - b[k] for k in a})(
        dict(params), w0)
    parts = weights.fused_parts(model)
    return {"moment1": harness.leaf_norms(moment1, parts),
            "delta": harness.leaf_norms(delta, parts)}


def first_dispatch_readings(eng, losses, model, seed):
    """What the first dispatch produced, as far as the comparison needs it:
    per-step loss and gradient norm, and the leaves' readings."""
    return {"losses": [float(x) for x in np.asarray(losses)],
            "gnorms": [float(x) for x in np.asarray(eng.last_grad_norms)],
            **leaf_readings({n: s["moment1"]
                             for n, s in eng.opt_state.items()},
                            eng.param_vals, model, seed)}


def compare(got, ref, checks):
    """The program's first dispatch against the reference's same steps;
    every limit is the cell's own (`limits/<cell>.json`)."""
    rel = lambda a, b: max(abs(x - y) / abs(y)  # noqa: E731
                           for x, y in zip(a, b))
    ref_m, ref_d = ref["moment1"], ref["delta"]
    # a leaf whose gradient is nought to rounding in the reference moves
    # under Adam by round-off alone: it is left out of the change
    floor = 1e-3 * float(np.median(list(ref_m.values())))
    still = [n for n, v in ref_m.items() if v < floor]
    m_gap, m_leaf, _ = harness.leaf_gaps(got["moment1"], ref_m)
    d_gap, d_leaf, d_mean = harness.leaf_gaps(got["delta"], ref_d,
                                              skip=still)
    # the loss comes back in bfloat16 (steps of 1/16 at 11): its gap sits at
    # half a step whatever is computed, no control or fault reads 3x it, so
    # it is printed and not compared (PERF.md section 7)
    checks.add("gnorm_gap", rel(got["gnorms"], ref["gnorms"]))
    checks.add("moment_gap", m_gap)
    checks.add("delta_gap", d_gap)
    # the worst leaf is the noise of one small leaf and swings from seed to
    # seed; float8's error lies evenly over the leaves, and only their mean
    # keeps the control three times above sound runs (PERF.md section 2)
    checks.add("delta_mean_gap", d_mean)
    return {"loss_gap": rel(got["losses"], ref["losses"]),
            "moment_leaf": m_leaf, "delta_leaf": d_leaf,
            "left_out_of_delta": len(still)}


def reference_readings(model, mix, batches, seed, quantized=False,
                       rows=None):
    """The reference over the same steps, from the same seed."""
    w0 = weights.make(model, seed, "float32")
    rows = rows or min(8, batches.shape[1])
    out = gpt_ref.train_steps(w0, list(batches), model, mix["optimizer"],
                              quantized=quantized, rows=rows)
    del w0
    return {"losses": out["losses"], "gnorms": out["gnorms"],
            **leaf_readings(out["moment1"], out["params"], model, seed)}


def run(cell, args, tools):
    import jax

    model, mix = cell["model"], cell["mix"]
    spans, counter = tools["spans"], tools["counter"]
    devices = jax.devices()[:cell["chips"]]
    steps = mix["steps_per_dispatch"]
    batches = generate.token_batches(mix, model["vocab_size"], args.seed)

    def feed(k):
        return [(b,) for b in batches[k % len(batches)]]

    phase = tools["phase"]
    phase("batches made on the host")
    w0 = weights.make(model, args.seed, "float32")
    phase("weights made on the device")
    eng = build_engine(cell, devices, w0)
    del w0
    phase("engine built")
    with spans.span("dispatch"):
        first = eng.train_batches(feed(0))
    jax.block_until_ready(first._value)
    phase("first dispatch done")
    got = first_dispatch_readings(eng, first._value, model, args.seed)
    phase("first dispatch read")
    jax.block_until_ready(eng.train_batches(feed(1))._value)
    phase("second dispatch done")
    print(f"[train] first dispatch: losses {got['losses']} gnorms "
          f"{got['gnorms']}; executables built so far {counter.builds} "
          f"({counter.hits} from the persistent cache)", flush=True)

    # ---- the window: the same engine, steps 2*steps+1 onwards
    tracer = tools["tracer"]
    builds0 = counter.builds
    pending = collections.deque()
    k, t_open = 2, time.perf_counter()
    tools["window_opened"](t_open)
    while True:
        now = time.perf_counter() - t_open
        tracer.poll(now)
        if now >= args.seconds:
            break
        with spans.span("dispatch"):
            lv = eng.train_batches(feed(k))
        pending.append(lv._value)
        k += 1
        if len(pending) > mix["in_flight"]:
            with spans.span("wait"):
                jax.block_until_ready(pending.popleft())
    with spans.span("wait"):
        last = np.asarray(jax.block_until_ready(pending[-1]))
    t_close = time.perf_counter()
    tracer.stop()
    dispatched = k - 2
    window_s = t_close - t_open
    tokens = dispatched * steps * mix["batch"] * mix["seq_len"]
    builds = counter.builds - builds0
    print(f"[train] window {window_s:.3f} s, {dispatched} dispatches of "
          f"{steps} steps, {builds} executable build(s) inside it",
          flush=True)
    device, reserved = harness.device_info(jax.devices(), cell["chips"])
    print(f"[train] memory_stats {jax.devices()[0].memory_stats()}",
          flush=True)
    stats = dict(eng.stats)

    # ---- free the program, then let the reference follow the first steps
    pending.clear()
    del eng, lv, first
    gc.collect()
    checks = harness.Checks(cell["limits"])
    t0 = time.perf_counter()
    ref = reference_readings(model, mix, batches[0], args.seed)
    notes = compare(got, ref, checks)
    checks.add("loss_finite", float(np.all(np.isfinite(last))), 1.0,
               at_most=False)
    checks.add("compiles_in_window", builds, 0)
    print(f"[train] reference followed {steps} steps in "
          f"{time.perf_counter() - t0:.1f} s; worst leaves {notes}",
          flush=True)
    return {
        "attempted": dispatched * steps, "failed": 0, "checks": checks,
        "device": device,
        "end_to_end": {"train_tokens_per_s": tokens / window_s},
        "counters": {"steps": dispatched * steps, "dispatches": dispatched,
                     "tokens": tokens, "window_s": window_s,
                     "t_open": t_open, "t_close": t_close,
                     "engine": stats, "reserved_peak_bytes": reserved},
    }


def trace_scope(ctx):
    """The traced stretch the per-layer readers share: whole launches of the
    step program, the device's busy time in it, and the time of the flash
    kernels, found by the families of operation that the mix names
    (`trace_names.flash`: fwd is `jvp_jit*`, dq and dkv `transpose_jvp_jit*`).
    A step in which none of them ran is an error, not a silent metric."""
    import fnmatch

    from benchmarks import trace_reader as tr

    trace = ctx["trace"]
    win = tr.main_module_window(trace)
    if not win:
        return {}
    scope = tr.reduce_window(trace, win["lo"], win["hi"])
    ops = tr.within(trace["devices"][0]["ops"], win["lo"], win["hi"])
    wanted = ctx["mix"]["trace_names"]["flash"]
    by_family = {}
    for n, ns in tr.totals(ops).items():
        by_family[tr.family(n)] = by_family.get(tr.family(n), 0) + ns
    flash = {f: ns for f, ns in by_family.items()
             if any(fnmatch.fnmatchcase(f, w) for w in wanted)}
    if not flash:
        raise RuntimeError(
            f"no operation of the families {wanted} in the traced step; "
            f"custom calls there: "
            f"{sorted(f for f in by_family if 'custom-call' in f)}")
    scope.update(win, flash_s=sum(flash.values()) / 1e9)
    return scope
