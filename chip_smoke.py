"""chip_smoke.py — the quickest proof that the system starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of `gpt_base` (12 layers, hidden 768, 12 x 64 heads, vocab
50304; weights random from a seed):

* trainer — `dist.parallelize(gpt, AdamW + ClipGradByGlobalNorm,
  compute_dtype="bfloat16")`, batch 16 x seq 1024: one `train_batch`, then
  two fused `train_batches([...] * 5)` dispatches. Every loss finite and
  falling, dispatch/step arithmetic right, zero compilations after each
  shape's warm-up dispatch, the Mosaic flash kernels (fwd, dq, dkv) present
  in the step's own lowered HLO, `block_until_ready` a true fence.
* server — `DecodeEngine(gpt in bf16)` behind
  `ServingPool(decode_engine=...)`: `warmup()`, then 8 concurrent
  `submit_generate` streams over prompts that share one prefix and split
  into prefill chunks. Tokens equal an identical second engine's solo run
  of each prompt; that second engine's `warmup()` loads every executable
  from the persistent cache ("disk"); no failed / timed-out / wedged /
  isolated step; pool drained after shutdown.
* four chips (when jax reports >= 4 devices) — the trainer over
  `MeshConfig(fsdp=4)`: loss trajectory against the one-chip phase, every
  large parameter at ~1/4 per device, memory in use on every device,
  all-gather and the gradient reduction in the compiled step.

Refuses anything but a TPU: there is no CPU mode and no flag. One process
(it holds the chip), no arguments, no network. A failed check does not stop
the run; the failed checks are listed at the end and the exit code is 1.
The last line of stdout is one JSON object:
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`.

The phase functions take the model name and sizes as arguments so that
tests/test_chip_smoke.py can walk the same control flow at `gpt_tiny` size
on the CPU mesh.
"""
from __future__ import annotations

import concurrent.futures
import json
import sys
import time

import numpy as np

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Report:
    """Collects named checks; a failed one is remembered, not raised, so
    one run lists everything that is wrong."""

    def __init__(self):
        self.failed = []

    def check(self, name, ok, detail=""):
        print(f"  [{'ok' if ok else 'FAIL'}] {name}"
              + (f": {detail}" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)
        return bool(ok)


class CompileCounter:
    """Counts executables XLA was asked for (`builds`: every jit/AOT cache
    miss in this process, whether it then compiled or read jax's persistent
    cache) and how many of those the persistent cache served (`hits`)."""

    def __init__(self):
        import jax.monitoring as mon

        self.builds = 0
        self.hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == _BACKEND_COMPILE:
            self.builds += 1

    def _on_event(self, event, **kw):
        if event == _CACHE_HIT:
            self.hits += 1


def _custom_call_kernels(lowered_text):
    """Names of the Mosaic kernels in a lowered step (each `pallas_call`
    lowers to a `tpu_custom_call` carrying its kernel's name)."""
    import re

    return re.findall(r'kernel_name = "([^"]+)"', lowered_text) \
        if "tpu_custom_call" in lowered_text else []


def trainer_phase(report, counter, *, model="gpt_base", batch=16,
                  seq_len=1024, fused=5, mesh=None, expect_flash=True,
                  label="trainer"):
    """The flagship trainer's path: one step, then two fused dispatches.
    Returns the loss trajectory and timings."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models import gpt
    from paddle_tpu.models.gpt import CONFIGS

    print(f"[{label}] {model} batch {batch} x seq {seq_len}, bf16, "
          f"mesh {mesh if mesh is not None else 'one device'}", flush=True)
    paddle.seed(0)
    net = gpt(model, max_position_embeddings=max(
        seq_len, CONFIGS[model].get("max_position_embeddings", seq_len)))
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=net.parameters(),
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    if mesh is None:
        mesh = dist.build_mesh(dp=-1, devices=jax.devices()[:1])
    eng = dist.parallelize(net, opt, mesh=mesh, compute_dtype="bfloat16")
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, net.cfg.vocab_size, (batch, seq_len)).astype("int32"))

    # what the step really contains, from its own lowering — not from the
    # platform test in nn/functional/attention.py, which falls back to
    # XLA's attention without a word when the kernel says "unsupported"
    t0 = time.perf_counter()
    lowered = eng.lower_step(ids)
    kernels = _custom_call_kernels(lowered.as_text())
    t_lower = time.perf_counter() - t0
    if expect_flash:
        want = {"_fwd_kernel", "_dq_kernel", "_dkv_kernel"}
        report.check(f"{label}: Mosaic flash fwd/dq/dkv in the lowered step",
                     want <= set(kernels),
                     f"{len(kernels)} tpu_custom_call(s): "
                     f"{sorted(set(kernels))}")

    # warm-up dispatch of each shape; compile seconds are set-up time
    t0 = time.perf_counter()
    losses = [float(eng.train_batch(ids))]
    t_compile_step = time.perf_counter() - t0
    t0 = time.perf_counter()
    lv = eng.train_batches([(ids,)] * fused)
    losses += [float(x) for x in lv.numpy()]
    t_compile_fused = time.perf_counter() - t0

    # the measured dispatch: no compilation, and the two fences timed
    builds0 = counter.builds
    t0 = time.perf_counter()
    lv = eng.train_batches([(ids,)] * fused)
    t_enqueue = time.perf_counter() - t0
    jax.block_until_ready(lv._value)
    t_ready = time.perf_counter() - t0
    tail = [float(x) for x in lv.numpy()]
    t_read = time.perf_counter() - t0
    losses += tail
    builds = counter.builds - builds0

    report.check(f"{label}: every loss finite",
                 bool(np.all(np.isfinite(losses))), f"{losses}")
    report.check(f"{label}: loss falls on a repeated batch",
                 losses[-1] < losses[0],
                 f"{losses[0]:.4f} -> {losses[-1]:.4f} in "
                 f"{len(losses)} steps")
    report.check(f"{label}: dispatch/step arithmetic",
                 eng.stats["dispatches"] == 3
                 and eng.stats["steps"] == 1 + 2 * fused, f"{eng.stats}")
    report.check(f"{label}: zero compilations after warm-up", builds == 0,
                 f"{builds} executable build(s) in the warm dispatch")
    # a true fence: once block_until_ready returns, reading the losses
    # back costs a copy, not the wait for the device
    report.check(f"{label}: block_until_ready is a true fence",
                 t_read - t_ready <= max(0.1 * t_read, 0.02),
                 f"enqueue {t_enqueue * 1e3:.1f} ms, ready "
                 f"{t_ready * 1e3:.1f} ms, loss read back "
                 f"{t_read * 1e3:.1f} ms")
    tokens = batch * seq_len * fused
    print(f"  set-up: lower {t_lower:.1f} s, first step {t_compile_step:.1f}"
          f" s, first fused dispatch {t_compile_fused:.1f} s; warm "
          f"{fused}-step dispatch {t_ready:.3f} s = "
          f"{tokens / t_ready:,.0f} tokens/s on this device", flush=True)
    return {"engine": eng, "lowered": lowered, "losses": losses,
            "setup_s": t_lower + t_compile_step + t_compile_fused,
            "run_s": t_ready}


def server_phase(report, counter, *, model="gpt_base", new_tokens=12,
                 label="server"):
    """8 concurrent streams through ServingPool -> DecodeEngine, checked
    against an identical engine whose executables come from disk."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import DecodeEngine, ServingPool
    from paddle_tpu.models import gpt

    # small geometry: two decode buckets, two prefill buckets, and a chunk
    # of 32 so every prompt longer than it really splits
    geo = dict(max_length=96, block_size=16, decode_buckets=(1, 4),
               prefill_buckets=(32, 64), prefill_chunk=32,
               default_timeout=120.0, step_timeout=30.0, step_retries=1)
    print(f"[{label}] {model} bf16, {geo}", flush=True)
    paddle.seed(7)
    net = gpt(model, max_position_embeddings=geo["max_length"])
    net.eval()
    for _, p in net.named_parameters():
        p._value = p._value.astype("bfloat16")
    rng = np.random.RandomState(3)
    vocab = net.cfg.vocab_size
    prefix = rng.randint(1, vocab - 1, (32,))
    prompts = [np.concatenate([prefix, rng.randint(1, vocab - 1, (n,))])
               .astype(np.int32) for n in (4, 9, 16, 21, 27, 30, 12, 4)]

    eng = DecodeEngine(net, **geo)
    pool = ServingPool(decode_engine=eng, default_timeout=120.0)
    t0 = time.perf_counter()
    eng.warmup()     # a compile inside a step would read as a wedge
    t_warmup = time.perf_counter() - t0
    warm = eng.stats()["compiles"]
    n_exec = warm["built"] + warm["disk"]
    builds0 = counter.builds

    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(len(prompts)) as ex:
        futs = [ex.submit(
            lambda p: pool.submit_generate(p, new_tokens).result(), p)
            for p in prompts]
        done, errors = [], []
        for f in futs:
            try:
                done.append(f.result(timeout=300))
            except Exception as e:  # noqa: BLE001 — a failed stream is a
                errors.append(e)    # failed check below, with its error
                done.append(None)
    t_run = time.perf_counter() - t0
    st = pool.stats()["decode"]
    builds = counter.builds - builds0
    pool.shutdown()
    drained = eng.stats()["blocks"]

    report.check(f"{label}: all {len(prompts)} requests complete",
                 not errors and st["completed"] == len(prompts),
                 f"completed {st['completed']}, errors "
                 f"{[repr(e)[:200] for e in errors]}")
    report.check(f"{label}: nothing failed, timed out, wedged or re-ran "
                 f"isolated",
                 st["failed"] == st["timed_out"] == st["wedged_steps"]
                 == st["isolation_rounds"] == 0
                 and st["step_pool"]["retried"] == 0
                 and st["step_pool"]["wedged"] == 0,
                 f"failed {st['failed']} timed_out {st['timed_out']} "
                 f"wedged_steps {st['wedged_steps']} isolation_rounds "
                 f"{st['isolation_rounds']} step_pool retried "
                 f"{st['step_pool']['retried']} wedged "
                 f"{st['step_pool']['wedged']}")
    report.check(f"{label}: prompts really split into chunks",
                 st["prefill_chunks"] > st["prefills"],
                 f"{st['prefill_chunks']} chunks for {st['prefills']} "
                 f"prefills")
    report.check(f"{label}: prefix cache hit",
                 st["prefix_cache"]["hits"] > 0,
                 f"hits {st['prefix_cache']['hits']}, tokens reused "
                 f"{st['prefix_cache']['tokens_reused']}")
    report.check(f"{label}: zero compilations after warmup()",
                 st["compiles"] == warm and builds == 0,
                 f"engine {warm} -> {st['compiles']}; {builds} executable "
                 f"build(s) under traffic")
    report.check(f"{label}: block pool drained after shutdown",
                 drained["allocated"] == 0, f"{drained}")

    # an identical engine over the same weights: every executable must
    # come back from the persistent cache, and — run one prompt at a time
    # — must reproduce the concurrent tokens (the engine's
    # bucket-invariance contract)
    ref_eng = DecodeEngine(net, **geo)
    t0 = time.perf_counter()
    ref_eng.warmup()
    t_rewarm = time.perf_counter() - t0
    again = ref_eng.stats()["compiles"]
    report.check(f"{label}: second engine warms up from disk",
                 again == {"built": 0, "disk": n_exec},
                 f"{again} (first engine {warm}); {t_rewarm:.1f} s against "
                 f"{t_warmup:.1f} s")
    refs = [ref_eng.generate(p, new_tokens) for p in prompts]
    ref_eng.shutdown()
    same = [d == r for d, r in zip(done, refs)]
    report.check(f"{label}: concurrent tokens equal the solo run",
                 all(same) and all(len(r) == new_tokens for r in refs),
                 f"{sum(same)}/{len(same)} streams identical"
                 + ("" if all(same) else f"; first mismatch: got "
                    f"{done[same.index(False)]} want "
                    f"{refs[same.index(False)]}"))
    tokens = sum(len(d) for d in done if d)
    print(f"  set-up: warmup {t_warmup:.1f} s ({warm}); traffic "
          f"{t_run:.2f} s for {tokens} tokens over {st['steps']} decode "
          f"steps + {st['prefill_chunks']} prefill chunks", flush=True)
    return {"setup_s": t_warmup, "run_s": t_run}


def four_chip_phase(report, counter, one_chip, *, model="gpt_base",
                    batch=16, seq_len=1024, fused=5, ways=4,
                    expect_flash=True, loss_tol=0.05, large=1 << 16):
    """The trainer over MeshConfig(fsdp=ways), against the one-chip run.
    `large` is the element count from which a parameter must be sharded."""
    import jax
    from paddle_tpu.sharding import MeshConfig, shard_fraction

    out = trainer_phase(report, counter, model=model, batch=batch,
                        seq_len=seq_len, fused=fused,
                        mesh=MeshConfig(fsdp=ways),
                        expect_flash=expect_flash, label=f"fsdp={ways}")
    eng = out["engine"]
    # same seed, same batch, same steps: the sharded trajectory tracks the
    # one-chip one to within bf16 reduction-order noise
    gap = max(abs(a - b) for a, b in zip(out["losses"], one_chip["losses"]))
    report.check(f"fsdp={ways}: loss trajectory within {loss_tol} of one "
                 f"chip", gap <= loss_tol, f"max |diff| {gap:.4f}")
    big = {n: v for n, v in eng.param_vals.items() if v.size >= large}
    spread = all(
        len({s.device for s in v.addressable_shards}) == ways
        and all(s.data.size * ways == v.size for s in v.addressable_shards)
        and shard_fraction(eng.param_specs[n], eng.mesh) == 1.0 / ways
        for n, v in big.items())
    report.check(f"fsdp={ways}: every large parameter 1/{ways} per device",
                 bool(big) and spread, f"{len(big)} parameters >= {large} "
                 f"elements")
    # the CPU backend reports no memory statistics; a TPU must
    devs = jax.devices()[:ways]
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]
    report.check(f"fsdp={ways}: memory in use on every device",
                 all(b > 1 << 20 for b in in_use if b is not None)
                 and (devs[0].platform != "tpu" or None not in in_use),
                 f"bytes_in_use {in_use}")
    # parameters gathered at their use sites, gradients reduced back onto
    # the shards (as reduce-scatter, or all-reduce + slice where the
    # backend does not fuse the pair)
    hlo = out["lowered"].compile().as_text()
    n_ag, n_rs, n_ar = (hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
                        for op in ("all-gather", "reduce-scatter",
                                   "all-reduce"))
    report.check(f"fsdp={ways}: all-gather and gradient reduction in the "
                 f"compiled step", n_ag > 0 and n_rs + n_ar > 0,
                 f"all-gather x{n_ag}, reduce-scatter x{n_rs}, all-reduce "
                 f"x{n_ar}")
    return out


def main():
    t_start = time.perf_counter()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: found platform {dev.platform!r} "
              f"({dev.device_kind}, {len(devices)} device(s)), not a TPU — "
              f"this script has no CPU mode", file=sys.stderr)
        return 1

    import jaxlib
    from importlib import metadata
    from paddle_tpu.jit.aot import enable_compile_cache

    cache_root = enable_compile_cache()
    print(f"platform: {dev.platform}  device_kind: {dev.device_kind}  "
          f"devices: {len(devices)}\njax {jax.__version__}  jaxlib "
          f"{jaxlib.__version__}  libtpu {metadata.version('libtpu')}\n"
          f"compile cache: {cache_root}", flush=True)

    report = Report()
    counter = CompileCounter()
    phases = {"trainer": trainer_phase(report, counter)}
    phases["server"] = server_phase(report, counter)
    if len(devices) >= 4:
        phases["fsdp=4"] = four_chip_phase(report, counter,
                                           phases["trainer"])
    else:
        print(f"[fsdp=4] skipped: {len(devices)} device(s)", flush=True)

    for name, out in phases.items():
        print(f"{name}: set-up {out['setup_s']:.1f} s, run "
              f"{out['run_s']:.2f} s")
    print(f"executables built: {counter.builds} ({counter.hits} from jax's "
          f"persistent cache); total {time.perf_counter() - t_start:.0f} s")
    if report.failed:
        print("FAILED checks:\n  " + "\n  ".join(report.failed),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
