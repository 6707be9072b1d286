"""Benchmark gate: flagship GPT (ERNIE-3.0-base-class) pretrain step
throughput on the TPU.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tokens/sec/chip", "vs_baseline": N,
   "device": {"platform": ..., "kind": ..., "count": N}}

A machine without a TPU is refused (exit 1) unless JAX_PLATFORMS names
`cpu`: that explicit smoke runs toy sizes to cover the control flow, and
its numbers are not device metrics. An error on the chip is an error —
nothing here retries. Compiled programs persist under the one cache root
(`paddle_tpu.jit.aot.enable_compile_cache`).

The reference publishes no in-tree numbers (BASELINE.md) — `vs_baseline` is
measured against an MFU-derived NCCL/GPU-class target: the north-star asks
for >=40% MFU; we report our measured MFU fraction relative to that target
(vs_baseline = our_MFU / 0.40), so >1.0 beats the reference target.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _measure(make_engine, batch, steps):
    """Warmup + timed loop, one dispatch per step. The timed window ends in
    a host readback of the LAST loss: the steps chain through donated
    state, so it is ready only when every step has run. `batch` is the
    tuple of train_batch arguments."""
    eng = make_engine()
    float(eng.train_batch(*batch))  # warmup/compile
    t0 = time.perf_counter()
    loss = None
    for _ in range(steps):
        loss = eng.train_batch(*batch)
    final_loss = float(loss)
    dt = time.perf_counter() - t0
    return final_loss, dt


def _multistep_k(steps):
    """Steps-per-dispatch for the pipelined `Engine.train_batches` hot
    path: the largest divisor of `steps` at most BENCH_MULTISTEP
    (default 5). k=1 falls back to one dispatch per step."""
    ms = int(os.environ.get("BENCH_MULTISTEP", "5"))
    return max(i for i in range(1, max(1, min(ms, steps)) + 1)
               if steps % i == 0)


def _measure_multistep(make_engine, batch, steps, k):
    """Warmup + timed loop over the fused k-step `train_batches` path
    (every micro-batch the same object -> the scan-invariant variant:
    zero per-step host work, docs/performance.md). Fenced like
    `_measure`, by reading back the last dispatch's losses."""
    eng = make_engine()
    lv = eng.train_batches([batch] * k)   # warmup/compile fused step
    float(lv.numpy()[-1])
    t0 = time.perf_counter()
    for _ in range(steps // k):
        lv = eng.train_batches([batch] * k)
    final_loss = float(lv.numpy()[-1])
    dt = time.perf_counter() - t0
    return final_loss, dt


def _export_profile(make_engine, batch, steps=3):
    """BENCH_PROFILE=1: capture host spans (engine dispatch / device_put /
    write-back plus eager op dispatches) over a few post-compile steps and
    export a chrome trace (path: BENCH_PROFILE_PATH, default
    bench_host_trace.json). A requested profile that fails fails the
    run."""
    from paddle_tpu.profiler import Profiler, ProfilerTarget

    eng = make_engine()
    float(eng.train_batch(*batch))  # compile outside the capture
    prof = Profiler(targets={ProfilerTarget.CPU})
    prof.start()
    try:
        for _ in range(steps):
            eng.train_batch(*batch)
            prof.step()
    finally:
        # a failed capture must not leave the tracer/profile hook live
        # — later benchmarks would silently pay tracing overhead
        prof.stop()
    path = os.environ.get("BENCH_PROFILE_PATH", "bench_host_trace.json")
    prof.export_chrome_tracing(path)
    prof.summary()
    print(f"bench: host chrome trace -> {path}", file=sys.stderr)


def _peak(dev, key):
    """Published peak `key` of this chip, from the one table keyed by
    `device_kind` (paddle_tpu.device.CHIP_PEAKS; an unknown kind raises).
    The explicit CPU smoke has no peak: inf, so every share reads 0."""
    from paddle_tpu.device import chip_peaks

    if dev.platform != "tpu":
        return float("inf")
    return chip_peaks(dev.device_kind)[key]


def _stamp(payload):
    """Name the device on the payload, as jax reports it: a number never
    travels without the platform, device kind and device count it came
    from."""
    import jax

    devs = jax.devices()
    payload["device"] = {"platform": devs[0].platform,
                         "kind": str(devs[0].device_kind),
                         "count": len(devs)}
    return payload


def _emit(payload):
    # under BENCH_ALL the per-config lines go to stderr; the driver
    # contract (ONE json line on stdout) is satisfied by main() printing
    # the flagship payload last
    _stamp(payload)
    if os.environ.get("BENCH_ALL") == "1":
        print(json.dumps(payload), file=sys.stderr)
    else:
        print(json.dumps(payload))
    return payload


CONV_BASELINE_FILENAME = "CONV_BASELINE.json"


def _conv_objectives(row, on_tpu):
    """Declared ratchet objectives for one conv bench row. CPU smokes
    ratchet images/sec (generous slack: machine-to-machine variance);
    TPU rows ratchet the MFU itself — the number ROADMAP item 1 is
    actually about."""
    from paddle_tpu.obs.slo import Objective

    if on_tpu:
        return [Objective(
            f"{row}.tpu_mfu", "min",
            description=f"TPU train-step MFU of the {row} bench row",
            unit="mfu", slack=1.25)]
    return [Objective(
        f"{row}.cpu_images_per_sec", "min",
        description=f"CPU-smoke train images/sec of the {row} bench row",
        unit="img/s", slack=3.0)]


def _conv_gate(row, on_tpu, ips, mfu):
    """vs_baseline ratchet for the conv bench rows (ROADMAP item 1
    lever (c)), mirroring the BENCH_SLO gate shape: the measured row is
    evaluated against the checked-in CONV_BASELINE.json bound and a
    regression beyond the slack FAILS the bench like a correctness bug
    (e.g. the vision flagships silently falling off the multi-step scan
    path, or an NHWC relayout creeping back in). BENCH_CONV_WRITE=1
    re-ratchets THIS row's bound (merging — resnet50/ppyoloe/TPU rows
    ratchet independently). A platform with no ratcheted bound yet (no
    TPU conv rows exist) notes it and passes — the checked-in CPU
    bounds keep the gate real where measurement exists."""
    from paddle_tpu.obs import slo as slo_mod

    objectives = _conv_objectives(row, on_tpu)
    values = {o.name: (mfu if on_tpu else ips) for o in objectives}
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        CONV_BASELINE_FILENAME)
    try:
        entries = slo_mod.load_baseline(path)
    except FileNotFoundError:
        entries = {}

    if os.environ.get("BENCH_CONV_WRITE") == "1":
        entries = slo_mod.write_baseline(
            path, values, objectives,
            note="conv bench ratchet bounds (ROADMAP item 1c); "
                 "re-ratchet one row with BENCH_CONV_WRITE=1 only "
                 "for an intentional, explained perf change",
            merge=entries)
        print(f"conv gate: ratcheted {[o.name for o in objectives]} -> "
              f"{path}", file=sys.stderr)

    missing = [o.name for o in objectives if o.name not in entries]
    if missing:
        print(f"conv gate: no ratcheted bound yet for {missing} on this "
              f"platform — BENCH_CONV_WRITE=1 ratchets one; gate skipped",
              file=sys.stderr)
        return True
    report = slo_mod.evaluate(values, entries, objectives)
    print(slo_mod.format_report(report), file=sys.stderr)
    return report["ok"]


def bench_resnet50(on_tpu, dev):
    """BASELINE config 1: ResNet-50 ImageNet-shape train step, images/sec."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models import resnet50, resnet18

    batch = int(os.environ.get("BENCH_BATCH", "128" if on_tpu else "4"))
    steps = int(os.environ.get("BENCH_STEPS", "20" if on_tpu else "2"))
    size = 224 if on_tpu else 64
    # channels-last is the MXU-native conv layout on TPU: it removes the
    # relayout transposes XLA wraps around NCHW convs (measured ~2x MFU on
    # the train step). The CPU smoke now defaults NHWC too — ROADMAP
    # item 1 lever (b): graphcheck GC003 proves the NHWC conv region
    # transpose-free (graph_audit engine smoke + the planted-NCHW test),
    # so the smoke exercises the layout the TPU rows ship with;
    # BENCH_RESNET_FORMAT=NCHW measures the parity layout
    fmt = os.environ.get("BENCH_RESNET_FORMAT", "NHWC")
    model_fn, train_flops_img = (
        (resnet50, 3 * 4.1e9) if on_tpu else (resnet18, 3 * 1.8e9))

    def loss_fn(m, x, y):
        return paddle.nn.functional.cross_entropy(m(x), y).mean()

    def make_engine():
        paddle.seed(0)
        model = model_fn(num_classes=1000, data_format=fmt)
        opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                        parameters=model.parameters())
        mesh = dist.build_mesh(dp=-1, devices=jax.devices()[:1])
        return dist.parallelize(model, opt, loss_fn=loss_fn, mesh=mesh,
                                compute_dtype="bfloat16" if on_tpu else None)

    rng = np.random.RandomState(0)
    img_shape = (batch, 3, size, size) if fmt == "NCHW" \
        else (batch, size, size, 3)
    x = paddle.to_tensor(rng.randn(*img_shape).astype("float32"))
    y = paddle.to_tensor(rng.randint(0, 1000, (batch,)).astype("int64"))

    k = _multistep_k(steps)
    if k > 1:
        final_loss, dt = _measure_multistep(make_engine, (x, y), steps, k)
    else:
        final_loss, dt = _measure(make_engine, (x, y), steps)
    ips = batch * steps / dt
    peak = _peak(dev, "bf16_flops")
    mfu = ips * train_flops_img / peak
    payload = _emit({
        "metric": f"resnet50 train images/sec ({size}px, bs={batch}, "
                  f"{fmt}, {'bf16' if on_tpu else 'f32'})",
        "value": round(ips, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(mfu / 0.40, 4) if on_tpu else 0.0,
        "extra": {"mfu": round(mfu, 4), "loss": round(final_loss, 4),
                  "steps_per_dispatch": k,
                  "platform": dev.platform},
    })
    return payload if _conv_gate("resnet50", on_tpu, ips, mfu) else None


def bench_bert_finetune(on_tpu, dev):
    """BASELINE config 2: BERT-base sequence-classification fine-tune step
    (AMP-O2-equivalent bf16 compute), sequences/sec."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models.bert import (
        bert_for_sequence_classification, BertConfig, CONFIGS,
    )

    mode = os.environ.get("BENCH_MODEL", "")
    if mode in CONFIGS:
        name = mode
    else:
        name = "bert_base" if on_tpu else "bert_tiny"
    seq = int(os.environ.get("BENCH_SEQLEN", "128"))
    batch = int(os.environ.get("BENCH_BATCH", "128" if on_tpu else "4"))
    steps = int(os.environ.get("BENCH_STEPS", "30" if on_tpu else "2"))

    def loss_fn(m, ids, labels):
        return paddle.nn.functional.cross_entropy(m(ids), labels).mean()

    def make_engine():
        paddle.seed(0)
        model = bert_for_sequence_classification(name, num_labels=2)
        opt = paddle.optimizer.AdamW(learning_rate=2e-5,
                                     parameters=model.parameters())
        mesh = dist.build_mesh(dp=-1, devices=jax.devices()[:1])
        return dist.parallelize(model, opt, loss_fn=loss_fn, mesh=mesh,
                                compute_dtype="bfloat16" if on_tpu else None)

    rng = np.random.RandomState(0)
    vocab = BertConfig(**CONFIGS[name]).vocab_size
    ids = paddle.to_tensor(
        rng.randint(0, vocab, (batch, seq)).astype("int32"))
    labels = paddle.to_tensor(rng.randint(0, 2, (batch,)).astype("int64"))

    final_loss, dt = _measure(make_engine, (ids, labels), steps)
    sps = batch * steps / dt
    # fwd+bwd ~ 6*N FLOPs/token over MATMUL-BEARING params only: the
    # embedding tables are gathers with no matmul (no tied LM head in a
    # classification fine-tune), so they must not inflate MFU
    bc = BertConfig(**CONFIGS[name])
    h, i, L = bc.hidden_size, bc.intermediate_size, bc.num_hidden_layers
    n_matmul = L * (4 * h * h + 2 * h * i) + h * h  # blocks + pooler
    flops_seq = 6.0 * n_matmul * seq
    peak = _peak(dev, "bf16_flops")
    mfu = sps * flops_seq / peak
    return _emit({
        "metric": f"{name} fine-tune sequences/sec (seq={seq}, bs={batch}, "
                  f"{'bf16' if on_tpu else 'f32'})",
        "value": round(sps, 2),
        "unit": "sequences/sec/chip",
        "vs_baseline": round(mfu / 0.40, 4) if on_tpu else 0.0,
        "extra": {"mfu": round(mfu, 4), "loss": round(final_loss, 4),
                  "tokens_per_sec": round(sps * seq, 1),
                  "platform": dev.platform},
    })


def bench_ppyoloe(on_tpu, dev):
    """BASELINE config 3: PP-YOLOE-s-class anchor-free detector train step
    (COCO-shape synthetic), images/sec. Train FLOPs/img come from XLA's own
    cost analysis of the compiled forward (3x fwd for fwd+bwd), so the MFU
    is accounted against the model actually run, not a paper number."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.vision.models import ppyoloe_s

    batch = int(os.environ.get("BENCH_BATCH", "16" if on_tpu else "2"))
    steps = int(os.environ.get("BENCH_STEPS", "20" if on_tpu else "2"))
    size = 640 if on_tpu else 128
    max_boxes = 16
    # channels-last is the MXU-native conv layout (same lever as the
    # resnet config; NCHW<->NHWC loss parity is tested in-tree). CPU
    # smoke defaults NHWC too — ROADMAP item 1 lever (b), GC003-proven
    # transpose-free; BENCH_YOLO_FORMAT=NCHW measures the parity layout
    fmt = os.environ.get("BENCH_YOLO_FORMAT", "NHWC")

    def loss_fn(m, img, gb, gl, gm):
        return m.loss(img, gb, gl, gm)

    def make_engine():
        paddle.seed(0)
        model = ppyoloe_s(num_classes=80, max_boxes=max_boxes,
                          data_format=fmt)
        opt = paddle.optimizer.Momentum(learning_rate=0.01, momentum=0.9,
                                        parameters=model.parameters())
        mesh = dist.build_mesh(dp=-1, devices=jax.devices()[:1])
        return dist.parallelize(model, opt, loss_fn=loss_fn, mesh=mesh,
                                compute_dtype="bfloat16" if on_tpu else None)

    rng = np.random.RandomState(0)
    img_shape = (batch, 3, size, size) if fmt == "NCHW" \
        else (batch, size, size, 3)
    img = paddle.to_tensor(rng.randn(*img_shape).astype("float32"))
    # synthetic boxes: xyxy within the image, ~8 valid per sample
    x0 = rng.uniform(0, size * 0.6, (batch, max_boxes, 2))
    wh = rng.uniform(size * 0.05, size * 0.35, (batch, max_boxes, 2))
    gb = paddle.to_tensor(
        np.concatenate([x0, np.minimum(x0 + wh, size - 1)], -1)
        .astype("float32"))
    gl = paddle.to_tensor(rng.randint(0, 80, (batch, max_boxes))
                          .astype("int64"))
    gm = paddle.to_tensor(
        (np.arange(max_boxes)[None] < 8).repeat(batch, 0)
        .astype("float32"))

    k = _multistep_k(steps)
    if k > 1:
        final_loss, dt = _measure_multistep(
            make_engine, (img, gb, gl, gm), steps, k)
    else:
        final_loss, dt = _measure(make_engine, (img, gb, gl, gm), steps)
    ips = batch * steps / dt

    # forward FLOPs of the model actually benched, from XLA cost analysis
    flops_img = None
    try:
        from paddle_tpu.distributed.engine import functionalize
        paddle.seed(0)
        from paddle_tpu.vision.models import ppyoloe_s as _mk
        m2 = _mk(num_classes=80, max_boxes=max_boxes, data_format=fmt)
        apply_fn, params, buffers = functionalize(
            m2, method=lambda *b: loss_fn(m2, *b))
        import jax.numpy as jnp
        pv = {n: p._value.astype("bfloat16" if on_tpu else "float32")
              if jnp.issubdtype(p._value.dtype, jnp.floating) else p._value
              for n, p in params.items()}
        bv = {n: b._value for n, b in buffers.items()}
        from paddle_tpu.core.tensor import Tensor as _T

        def fwd(p, b, i, g1, g2, g3):
            out, _ = apply_fn(p, b, _T(i), _T(g1), _T(g2), _T(g3))
            return out

        lowered = jax.jit(fwd).lower(
            pv, bv, img._value.astype("bfloat16" if on_tpu else "float32"),
            gb._value, gl._value, gm._value)
        cost = lowered.compile().cost_analysis()
        if cost and cost.get("flops"):
            flops_img = 3.0 * float(cost["flops"]) / batch
    except Exception as e:
        print(f"ppyoloe: cost analysis unavailable ({e})", file=sys.stderr)

    peak = _peak(dev, "bf16_flops")
    mfu = (ips * flops_img / peak) if flops_img else 0.0
    payload = _emit({
        "metric": f"ppyoloe_s detector train images/sec ({size}px, "
                  f"bs={batch}, {fmt}, {'bf16' if on_tpu else 'f32'})",
        "value": round(ips, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(mfu / 0.40, 4) if (on_tpu and flops_img) else 0.0,
        "extra": {"mfu": round(mfu, 4), "loss": round(final_loss, 4),
                  "train_gflops_per_img": round(flops_img / 1e9, 2)
                  if flops_img else None,
                  "steps_per_dispatch": k,
                  "platform": dev.platform},
    })
    return payload if _conv_gate("ppyoloe", on_tpu, ips, mfu) else None


def bench_lora_decode(on_tpu, dev):
    """BASELINE config 5: LoRA-adapted LLM autoregressive decode tokens/sec.
    Decode is HBM-bandwidth-bound: the target is 40% of the
    bandwidth-implied ceiling (param_bytes/token over v5e's 819 GB/s)."""
    import jax
    import numpy as _np
    import paddle_tpu as paddle
    from paddle_tpu.models import gpt, generate, GenerationConfig
    from paddle_tpu.nn.lora import LoRAConfig, apply_lora

    name = os.environ.get("BENCH_MODEL",
                          "gpt3_1p3b" if on_tpu else "gpt_tiny")
    batch = int(os.environ.get("BENCH_BATCH", "8" if on_tpu else "2"))
    new_tokens = int(os.environ.get("BENCH_NEW_TOKENS",
                                    "128" if on_tpu else "8"))
    wdtype = os.environ.get("BENCH_WEIGHT_DTYPE", "")
    if wdtype and wdtype not in ("int8", "int4"):
        raise SystemExit(
            f"BENCH_WEIGHT_DTYPE={wdtype!r} unsupported (int8|int4)")
    kv_dtype = os.environ.get("BENCH_KV_DTYPE", "")
    if kv_dtype and kv_dtype != "int8":
        raise SystemExit(
            f"BENCH_KV_DTYPE={kv_dtype!r} unsupported (int8)")

    # Models whose f32 init exceeds HBM (llama2_7b: 27 GB on a 16 GB v5e)
    # must build + quantize on HOST, shipping only the quantized/bf16
    # buffers to the chip (the reference's deploy path likewise converts
    # offline and loads the quantized artifact).
    init_host = on_tpu and os.environ.get(
        "BENCH_INIT_HOST", "1" if name == "llama2_7b" else "0") == "1"
    import contextlib
    host_ctx = contextlib.nullcontext()
    if init_host:
        cpu0 = jax.local_devices(backend="cpu")[0]
        host_ctx = jax.default_device(cpu0)

    from paddle_tpu.nn.quant import quantize_for_inference, WeightOnlyLinear
    with host_ctx:
        paddle.seed(0)
        model = gpt(name)
        # adapters stay LIVE: the metric is LoRA-adapted decode (BASELINE
        # config 5), not base-model decode after a merge
        apply_lora(model, LoRAConfig(r=8))
        model.eval()
        if on_tpu:
            for _, p in model.named_parameters():
                p._value = p._value.astype("bfloat16")
        if kv_dtype:
            # int8 KV cache: halves the cache bytes (memory capability; the
            # measured throughput verdict is in docs/decode_perf.md)
            model.cache_quant = kv_dtype
        if wdtype:
            quantize_for_inference(model, weight_dtype=wdtype)
    if init_host:
        import jax.numpy as _jnp
        for _, p in model.named_parameters():
            v = p._value
            if _jnp.issubdtype(v.dtype, _jnp.floating):
                v = v.astype("bfloat16")
            p._value = jax.device_put(v, dev)
        for _, b in model.named_buffers():
            b._value = jax.device_put(b._value, dev)
    param_bytes = 0.0
    for _, sub in model.named_sublayers():
        if isinstance(sub, WeightOnlyLinear):
            param_bytes += float(_np.prod(sub.quant_weight.shape))  # 1B/el
    for n, p in model.named_parameters():
        param_bytes += float(_np.prod(p.shape)) * (2 if on_tpu else 4)

    rng = np.random.RandomState(0)
    prompt = paddle.to_tensor(rng.randint(0, 256, (batch, 16)).astype("int32"))
    cfg = GenerationConfig(max_new_tokens=new_tokens, do_sample=False,
                           use_cache=True)

    out = generate(model, prompt, cfg)  # warmup/compile
    np.asarray(out.numpy())  # fence: async dispatch otherwise leaks
    dt = float("inf")        # leftover work into the timed window
    for _ in range(3):
        t0 = time.perf_counter()
        out = generate(model, prompt, cfg)
        np.asarray(out.numpy())
        dt = min(dt, time.perf_counter() - t0)
    tps = batch * new_tokens / dt
    bw_frac = (tps * param_bytes / batch) / _peak(dev, "hbm_bandwidth")
    return _emit({
        "metric": f"{name}+LoRA decode tokens/sec (bs={batch}, "
                  f"{new_tokens} new tokens, KV cache"
                  + (f", weight-only {wdtype}" if wdtype else "")
                  + (f", {kv_dtype} KV" if kv_dtype else "") + ")",
        "value": round(tps, 2),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(bw_frac / 0.40, 4) if on_tpu else 0.0,
        "extra": {"bandwidth_frac": round(bw_frac, 4),
                  "platform": dev.platform},
    })


def bench_serving(on_tpu, dev):
    """BENCH_SERVING=1: dynamic-batching serving throughput. Requests/sec
    of a ServingPool over a small exported MLP at concurrency 1/8/32,
    batching off vs on (shape-bucketed AOT executables, docs/serving.md).
    Per-request outputs are checked bit-identical to sequential
    single-request execution; `vs_baseline` is the batched/unbatched
    speedup at the HIGHEST measured concurrency >= 8 (32 with the default
    sweep — where dispatch contention dominates and the win is stable;
    the acceptance gate is >= 1.5x). Every concurrency row is reported in
    `extra.rps`."""
    import concurrent.futures
    import itertools
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.inference import (
        BatchConfig, Config, ServingPool, create_predictor)

    n_req = int(os.environ.get("BENCH_SERVING_REQUESTS", "192"))
    conc = [int(c) for c in os.environ.get(
        "BENCH_SERVING_CONCURRENCY", "1,8,32").split(",")]
    pool_size = int(os.environ.get("BENCH_SERVING_POOL", "2"))
    wait_ms = float(os.environ.get("BENCH_SERVING_WAIT_MS", "3"))
    buckets = (1, 2, 4, 8, 16)

    with tempfile.TemporaryDirectory(prefix="bench-serving-") as workdir:
        paddle.seed(0)
        # dispatch-bound on purpose: serving overhead (one XLA dispatch +
        # host round-trip per request) is what batching removes; compute
        # stays small so the CPU smoke measures the dispatch amortization
        # a TPU would see at much larger models
        model = nn.Sequential(nn.Linear(32, 32), nn.ReLU(),
                              nn.Linear(32, 32))
        model.eval()
        path = os.path.join(workdir, "infer")
        paddle.jit.save(model, path, input_spec=[
            paddle.to_tensor(np.zeros((1, 32), np.float32))])

        rng = np.random.RandomState(0)
        inputs = [rng.rand(1, 32).astype(np.float32) for _ in range(32)]
        ref = create_predictor(Config(path))
        want = [ref.run([x])[0] for x in inputs]

        def drive(pool, c):
            feeds = list(itertools.islice(itertools.cycle(
                range(len(inputs))), n_req))
            mismatches = [0]

            def one(i):
                out, = pool.infer([inputs[i]], timeout=30.0)
                if out.shape != want[i].shape or not (out == want[i]).all():
                    mismatches[0] += 1

            with concurrent.futures.ThreadPoolExecutor(max_workers=c) as ex:
                t0 = time.perf_counter()
                list(ex.map(one, feeds))
                dt = time.perf_counter() - t0
            return n_req / dt, mismatches[0]

        rows = {}
        dispatches = {}
        for mode in ("unbatched", "batched"):
            batching = BatchConfig(buckets=buckets, max_wait_ms=wait_ms) \
                if mode == "batched" else None
            pool = ServingPool(predictor=create_predictor(Config(path)),
                               size=pool_size, max_queue_depth=max(conc) * 4,
                               default_timeout=60.0, batching=batching)
            try:
                if batching is not None:
                    pool.warmup()
                drive(pool, 4)  # warm every member / executable
                for c in conc:
                    rps, bad = drive(pool, c)
                    rows[f"{mode}@{c}"] = round(rps, 1)
                    if bad:
                        rows[f"{mode}@{c}_MISMATCHES"] = bad
                if batching is not None:
                    bs = pool.stats()["batch"]
                    dispatches = {
                        "executed_by_bucket": bs["executed_by_bucket"],
                        "occupancy": round(bs["occupancy"], 3),
                        "requests": bs["requests"],
                        "padded": bs["padded_examples"],
                        "compile": bs["compile"],
                    }
            finally:
                pool.shutdown(drain_timeout=10.0)

        # gate at the highest measured concurrency (>= 8): that is where
        # per-request dispatch contention dominates and the batching win
        # is stable; lower-concurrency rows stay in `extra.rps`
        gate = max(c for c in conc if c >= 8) if any(
            c >= 8 for c in conc) else conc[-1]
        speedup = rows[f"batched@{gate}"] / rows[f"unbatched@{gate}"]
        return _emit({
            "metric": f"batched serving requests/sec (concurrency={gate}, "
                      f"pool={pool_size}, buckets={list(buckets)}, "
                      f"32x32 MLP)",
            "value": rows[f"batched@{gate}"],
            "unit": "requests/sec",
            "vs_baseline": round(speedup, 3),
            "extra": {"rps": rows, "batch": dispatches,
                      "requests_per_config": n_req,
                      "platform": dev.platform},
        })


def bench_slo(on_tpu, dev):
    """BENCH_SLO=1: the perf-SLO regression gate (docs/observability.md).

    Drives the CPU serving smoke (batched ServingPool over a small
    exported MLP at concurrency 8) with the obs metrics registry
    attached and a live HTTP exporter scraped mid-run, plus a tiny
    training loop, then evaluates the objectives declared in
    paddle_tpu.obs.slo (p99 request latency, throughput floor,
    queue-depth ceiling, steps/sec floor) against the checked-in
    SLO_BASELINE.json ratchet — exit nonzero on any breach, exactly how
    .tpu_lint_baseline.json gates lint. Generations are also streamed
    through a two-replica ServingRouter over stub decode engines, so
    the router's streaming overhead (time-to-first-token p99) rides the
    same gate. BENCH_SLO_WRITE=1 re-measures and rewrites the whole
    baseline (for an intentional, explained perf change);
    BENCH_SLO_WRITE=stream re-ratchets only the router_stream.* rows,
    merging over the existing bounds (slo.write_baseline(merge=)). The
    scrape is also verified: the pool's conservation law (admitted ==
    completed + failed + timed_out + cancelled) and the router's stream
    ledger must hold in the Prometheus text exposition itself."""
    import concurrent.futures
    import itertools
    import re
    import tempfile
    import urllib.request

    import paddle_tpu as paddle
    from paddle_tpu import nn, obs
    from paddle_tpu.obs import slo as slo_mod
    from paddle_tpu.inference import (
        BatchConfig, Config, LocalReplica, RouterConfig, ServingPool,
        ServingRouter, create_predictor)

    class _NullPredictor:
        """Pool-compatible stand-in: the streaming stage exercises the
        router's decode path only, never predictor compute."""

        def clone(self):
            return _NullPredictor()

        def reset_handles(self):
            pass

        def run(self, feeds):
            return [np.asarray(f) for f in feeds]

    class _StubStream:
        """Pump-contract stream over a precomputed token list: every
        token is available the instant the stream is placed, so the
        measured TTFT is pure router overhead."""

        def __init__(self, sid, toks):
            self.id, self.deadline, self.status = sid, None, "active"
            self._toks, self._i, self._end = toks, 0, None

        @property
        def tokens(self):
            return self._toks[:self._i]

        def cancel(self):
            if self._end is None:
                self._end = ("end", "cancelled", None)
                self.status = "cancelled"

        def poll(self, timeout=None):
            if self._end is not None:
                return self._end
            if self._i < len(self._toks):
                self._i += 1
                return ("tok", self._toks[self._i - 1])
            self._end = ("end", "completed", None)
            self.status = "completed"
            return self._end

    class _StubEngine:
        """Engine-duck-typed deterministic token recurrence — no XLA
        anywhere in the streaming hot path."""

        def __init__(self, generation):
            self._gen = int(generation)
            self._n = itertools.count()

        def submit(self, prompt_ids, max_new_tokens, timeout=None,
                   resume_committed=None, sampling=None, adapter=None):
            seq = ([int(t) for t in prompt_ids]
                   + [int(t) for t in (resume_committed or [])])
            toks = []
            for _ in range(int(max_new_tokens)):
                t = (sum(seq) * 31 + len(seq) + 7 * self._gen) % 211
                seq.append(t)
                toks.append(t)
            return _StubStream(f"s{next(self._n)}", toks)

        def shutdown(self, drain_timeout=None):
            pass

        def stats(self):
            return {}

    n_req = int(os.environ.get("BENCH_SLO_REQUESTS", "160"))
    conc = int(os.environ.get("BENCH_SLO_CONCURRENCY", "8"))
    pool_size = 2
    baseline_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        slo_mod.BASELINE_FILENAME)
    values = {}

    with tempfile.TemporaryDirectory(prefix="bench-slo-") as workdir:
        paddle.seed(0)
        model = nn.Sequential(nn.Linear(32, 32), nn.ReLU(),
                              nn.Linear(32, 32))
        model.eval()
        path = os.path.join(workdir, "infer")
        paddle.jit.save(model, path, input_spec=[
            paddle.to_tensor(np.zeros((1, 32), np.float32))])

        rng = np.random.RandomState(0)
        inputs = [rng.rand(1, 32).astype(np.float32) for _ in range(32)]

        reg = obs.MetricsRegistry()
        pool = ServingPool(predictor=create_predictor(Config(path)),
                           size=pool_size, max_queue_depth=conc * 8,
                           default_timeout=60.0,
                           batching=BatchConfig(max_wait_ms=2.0),
                           metrics=reg, name="slo")
        router = None
        try:
            server = pool.serve_metrics()
            pool.warmup()
            feeds = list(itertools.islice(
                itertools.cycle(range(len(inputs))), n_req))
            hist = reg.histogram("serving.request_seconds")
            with concurrent.futures.ThreadPoolExecutor(conc) as ex:
                # warm every member/executable outside the measure
                list(ex.map(lambda i: pool.infer([inputs[i]],
                                                 timeout=30.0),
                            feeds[:conc * 2]))
                # window the histogram too: the p99 objective must see
                # only the measured traffic, not the cold-start samples
                # the warm-up just absorbed (counts-delta quantile)
                warm_counts = hist.counts()
                t0 = time.perf_counter()
                list(ex.map(lambda i: pool.infer([inputs[i]],
                                                 timeout=30.0), feeds))
                dt = time.perf_counter() - t0

            snap = reg.snapshot()
            st = snap["collectors"]["serving.pool.slo"]
            window = [a - b for a, b in zip(hist.counts(), warm_counts)]
            values["serving_smoke.p99_latency_s"] = \
                hist.quantile(0.99, window)
            values["serving_smoke.throughput_rps"] = n_req / dt
            values["serving_smoke.queue_depth_peak"] = \
                st["queue_depth_peak"]

            # streaming TTFT through the distributed tier (docs/
            # serving.md): a two-replica ServingRouter over stub decode
            # engines shares the SAME registry, so its stream ledger
            # lands in the scrape below. Every token is ready the
            # moment a stream is placed — the p99 TTFT bound gates
            # ROUTER overhead (affinity pick, admission, first-frame
            # pump delivery), and a stall slipped into the pump loop
            # trips the gate even though model compute never moved.
            n_streams = int(os.environ.get("BENCH_SLO_STREAMS", "48"))
            router = ServingRouter(
                lambda rid, mdir, gen: LocalReplica(
                    rid, lambda d: _NullPredictor(), mdir, gen,
                    decode_factory=_StubEngine,
                    pool_kwargs=dict(default_timeout=30.0)),
                size=2,
                config=RouterConfig(default_timeout=30.0,
                                    affinity_block_tokens=4,
                                    no_capacity_wait=10.0),
                metrics=reg, name="slo")

            def stream_one(i):
                t0 = time.perf_counter()
                rs = router.submit_generate([i % 7, 1, 4, 1], 8,
                                            timeout=30.0)
                it = iter(rs)
                next(it)                    # first token lands
                ttft = time.perf_counter() - t0
                for _ in it:                # drain to completion
                    pass
                return ttft

            with concurrent.futures.ThreadPoolExecutor(4) as ex:
                list(ex.map(stream_one, range(8)))   # warm the tier
                ttfts = list(ex.map(stream_one, range(n_streams)))
            values["router_stream.ttft_p99_s"] = float(
                np.percentile(np.asarray(ttfts), 99))

            # the SAME registry must be scrapeable as Prometheus text
            # from the live endpoint, conservation law intact
            text = urllib.request.urlopen(
                server.url + "/metrics", timeout=10).read().decode()
            healthz = urllib.request.urlopen(
                server.url + "/healthz", timeout=10).status

            def scraped(field):
                m = re.search(
                    rf"^serving_pool_slo_{field} (\d+)$", text, re.M)
                if m is None:
                    raise RuntimeError(
                        f"serving_pool_slo_{field} missing from the "
                        f"scraped exposition")
                return int(m.group(1))

            balance = (scraped("completed") + scraped("failed")
                       + scraped("timed_out") + scraped("cancelled"))
            if scraped("admitted") != balance or healthz != 200:
                print(f"bench_slo: scraped conservation broken "
                      f"(admitted={scraped('admitted')} vs {balance}, "
                      f"healthz={healthz})", file=sys.stderr)
                return None

            # ... and so must the router's streams ledger (admitted ==
            # completed + failed + timed_out + cancelled + in_flight)
            rprefix = "serving_router_slo_streams_"
            ledger = {}
            for ln in text.splitlines():
                if ln.startswith(rprefix):
                    k, _, v = ln.partition(" ")
                    ledger[k[len(rprefix):]] = int(float(v))
            rbal = (ledger.get("completed", 0) + ledger.get("failed", 0)
                    + ledger.get("timed_out", 0)
                    + ledger.get("cancelled", 0)
                    + ledger.get("in_flight", 0))
            if ledger.get("admitted") != rbal \
                    or ledger.get("admitted", 0) < n_streams:
                print(f"bench_slo: scraped stream ledger broken "
                      f"({ledger})", file=sys.stderr)
                return None
            if "router_ttft_seconds" not in text:
                print("bench_slo: router_ttft_seconds missing from the "
                      "scraped exposition", file=sys.stderr)
                return None
        finally:
            if router is not None:
                router.shutdown(drain_timeout=10.0)
            pool.shutdown(drain_timeout=10.0)

    # training-dispatch floor: a tiny Engine loop (compile excluded)
    import jax
    import paddle_tpu.distributed as dist

    paddle.seed(0)
    tmodel = nn.Sequential(nn.Linear(16, 16), nn.ReLU(), nn.Linear(16, 1))
    opt = paddle.optimizer.Momentum(learning_rate=0.01,
                                    parameters=tmodel.parameters())
    mesh = dist.build_mesh(dp=-1, devices=jax.devices()[:1])
    eng = dist.parallelize(
        tmodel, opt, mesh=mesh,
        loss_fn=lambda m, x, y: paddle.nn.functional.mse_loss(m(x), y))
    x = paddle.to_tensor(np.random.RandomState(0)
                         .rand(8, 16).astype("float32"))
    y = paddle.to_tensor(np.random.RandomState(1)
                         .rand(8, 1).astype("float32"))
    float(eng.train_batch(x, y).numpy())  # compile + fence
    steps = int(os.environ.get("BENCH_SLO_TRAIN_STEPS", "30"))
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = eng.train_batch(x, y)
    float(loss.numpy())                   # readback fences the chain
    values["train_smoke.steps_per_sec"] = steps / (time.perf_counter()
                                                   - t0)

    gate_objectives = slo_mod.SERVING_SMOKE + slo_mod.ROUTER_STREAM
    write = os.environ.get("BENCH_SLO_WRITE", "")
    if write in ("1", "stream"):
        # "1" re-ratchets every row; "stream" re-ratchets only the
        # router_stream.* rows, carrying the rest of the checked-in
        # bounds over untouched (slo.write_baseline merge semantics)
        ratchet = gate_objectives if write == "1" \
            else slo_mod.ROUTER_STREAM
        try:
            merge = slo_mod.load_baseline(baseline_path)
        except FileNotFoundError:
            merge = None
        written = slo_mod.write_baseline(
            baseline_path, values, ratchet,
            note="CPU serving+stream+train smoke bounds; re-ratchet "
                 "with BENCH_SLO_WRITE=1 (all) or =stream "
                 "(router_stream.* only) for an intentional perf "
                 "change", merge=merge)
        print(f"bench_slo: wrote {len(written)} baseline bounds "
              f"({len(ratchet)} re-ratcheted) -> {baseline_path}",
              file=sys.stderr)

    baseline = slo_mod.load_baseline(baseline_path)
    report = slo_mod.evaluate(values, baseline, gate_objectives)
    print(slo_mod.format_report(report), file=sys.stderr)
    payload = _emit({
        "metric": f"SLO gate ({len(report['results'])} objectives, "
                  f"serving c={conc} n={n_req} + {n_streams} routed "
                  f"streams + {steps}-step train smoke)",
        "value": len(report["results"]) - len(report["breaches"]),
        "unit": "objectives passed",
        "vs_baseline": 1.0 if report["ok"] else 0.0,
        "extra": {"values": {k: round(v, 6) for k, v in values.items()},
                  "results": report["results"],
                  "platform": dev.platform},
    })
    return payload if report["ok"] else None


POD_BASELINE_FILENAME = "POD_BASELINE.json"


def _pod_objectives(on_tpu):
    """Declared objectives for the BENCH_POD gate. The CPU smoke mixes
    two DETERMINISTIC gates (dispatch count per step, per-chip param+opt
    state shrink — pure placement math, slack ~1) with a generous-slack
    throughput floor; TPU rows ratchet tokens/sec on the first hardware
    round, like the conv gate."""
    from paddle_tpu.obs.slo import Objective

    if on_tpu:
        return [Objective(
            "pod_smoke.tpu_fsdp_tokens_per_sec", "min",
            description="tokens/sec/chip of the fsdp-sharded GPT train "
                        "step on the real device mesh",
            unit="tok/s", slack=2.0)]
    return [
        Objective("pod_smoke.fsdp_tokens_per_sec", "min",
                  description="tokens/sec of the fsdp=8 GPT CPU-mesh "
                              "smoke (8 virtual devices, multi-step "
                              "scan path)",
                  unit="tok/s", slack=5.0),
        Objective("pod_smoke.dispatches_per_step", "max",
                  description="compiled-program dispatches per optimizer "
                              "step of the measured fsdp loop "
                              "(train_batches k-step scan: 1/k; "
                              "deterministic engine counter, not "
                              "wall-clock)",
                  unit="dispatches/step", slack=1.0),
        Objective("pod_smoke.fsdp_state_shrink", "min",
                  description="per-chip param+optimizer-state bytes, "
                              "dp-replicated / fsdp-sharded — the "
                              "fsdp-fits-where-dp-OOMs lever; "
                              "deterministic placement math "
                              "(graphcheck params_bytes_per_chip)",
                  unit="x", slack=1.1),
    ]


def bench_pod(on_tpu, dev):
    """BENCH_POD=1: pod-scale training defaults gate (ROADMAP item 3).

    Trains the GPT flagship config (gpt_tiny CPU smoke) through
    `MeshConfig(dp=8)` and `MeshConfig(fsdp=8)` engines on the
    8-virtual-device mesh and gates, via the checked-in POD_BASELINE.json
    ratchet (slo machinery, BENCH_POD_WRITE=1 re-ratchets):

    * loss parity dp vs fsdp <= 1e-5 at every step (hard gate — the
      in-graph gather/reduce-scatter must be semantically invisible);
    * dispatches/step of the measured loop (deterministic engine
      counter: the fsdp path must stay on the k-step scan hot path —
      dispatch/collective overlap is bought at dispatch granularity);
    * per-chip param+opt-state shrink dp/fsdp ~ N (deterministic
      placement math — the memory lever that makes 7B+ fit); the run
      also reports the "fits where dp OOMs" budget bracket;
    * fsdp tokens/sec floor (generous slack: CPU timing).
    """
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.analysis.graphcheck import params_bytes_per_chip
    from paddle_tpu.distributed import topology as topo_mod
    from paddle_tpu.models import gpt
    from paddle_tpu.obs import slo as slo_mod
    from paddle_tpu.sharding import MeshConfig

    n_dev = len(jax.devices())
    ways = int(os.environ.get("BENCH_POD_WAYS", "8"))
    if n_dev < ways:
        if on_tpu and n_dev >= 2:
            ways = n_dev
        else:
            print(f"bench_pod: needs {ways} devices, have {n_dev} "
                  f"(CPU smokes force 8 virtual devices via main()); "
                  f"gate skipped", file=sys.stderr)
            return {"metric": "pod gate (skipped: too few devices)",
                    "value": 0, "unit": "objectives passed",
                    "vs_baseline": 1.0, "extra": {"devices": n_dev}}

    name = "gpt_tiny" if not on_tpu else os.environ.get(
        "BENCH_MODEL", "gpt_base")
    seq = int(os.environ.get("BENCH_SEQLEN", "64" if not on_tpu else "1024"))
    batch = int(os.environ.get("BENCH_BATCH", str(ways)))
    steps = int(os.environ.get("BENCH_POD_STEPS", "8"))
    k = _multistep_k(steps)

    rng = np.random.RandomState(0)
    from paddle_tpu.models.gpt import CONFIGS

    vocab = CONFIGS[name]["vocab_size"]
    ids = paddle.to_tensor(
        rng.randint(0, vocab, (batch, seq)).astype("int32"))

    def make_engine(cfg):
        topo_mod.set_hybrid_communicate_group(None)
        paddle.seed(0)
        model = gpt(name, max_position_embeddings=max(
            seq, CONFIGS[name].get("max_position_embeddings", seq)))
        opt = paddle.optimizer.AdamW(
            learning_rate=1e-4, parameters=model.parameters(),
            grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
        return dist.parallelize(
            model, opt, mesh=cfg,
            compute_dtype="bfloat16" if on_tpu else None)

    def run(cfg):
        eng = make_engine(cfg)
        lv = eng.train_batches([(ids,)] * k)       # warmup/compile
        float(lv.numpy()[-1])
        d0, s0 = eng.stats["dispatches"], eng.stats["steps"]
        losses = []
        t0 = time.perf_counter()
        for _ in range(steps // k):
            lv = eng.train_batches([(ids,)] * k)
            losses.extend(float(x) for x in np.asarray(lv.numpy()))
        dt = time.perf_counter() - t0
        return (eng, losses, dt,
                eng.stats["dispatches"] - d0, eng.stats["steps"] - s0)

    def state_bytes(eng):
        # the same declared param+opt-state set the graphcheck
        # <site>::params watermark audits — one enumeration, one gate
        return params_bytes_per_chip(*eng.declared_state(), eng.mesh)

    fs_eng, fs_losses, fs_dt, fs_disp, fs_steps = run(MeshConfig(fsdp=ways))
    dp_eng, dp_losses, dp_dt, _d, _s = run(MeshConfig(dp=ways))

    # hard gate: the fsdp placement must be semantically invisible
    parity = max(abs(a - b) for a, b in zip(dp_losses, fs_losses))
    if parity > 1e-5:
        print(f"bench_pod: dp-vs-fsdp loss parity broken "
              f"(max |diff| {parity:.3e} > 1e-5)\n  dp   {dp_losses}\n"
              f"  fsdp {fs_losses}", file=sys.stderr)
        return None

    dp_bytes, fs_bytes = state_bytes(dp_eng), state_bytes(fs_eng)
    shrink = dp_bytes / max(fs_bytes, 1)
    # the fits-where-dp-OOMs bracket: any per-chip budget between the two
    # residencies admits the fsdp placement and rejects dp-replicated
    budget = (dp_bytes + fs_bytes) // 2
    tok_s = batch * seq * steps / fs_dt

    values = {}
    if on_tpu:
        values["pod_smoke.tpu_fsdp_tokens_per_sec"] = tok_s
    else:
        values["pod_smoke.fsdp_tokens_per_sec"] = tok_s
        values["pod_smoke.dispatches_per_step"] = fs_disp / max(fs_steps, 1)
        values["pod_smoke.fsdp_state_shrink"] = shrink

    objectives = _pod_objectives(on_tpu)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        POD_BASELINE_FILENAME)
    try:
        entries = slo_mod.load_baseline(path)
    except FileNotFoundError:
        entries = {}
    if os.environ.get("BENCH_POD_WRITE") == "1":
        entries = slo_mod.write_baseline(
            path, values, objectives,
            note="pod-scale fsdp training gate (ROADMAP item 3): CPU "
                 "deterministic dispatch/memory gates + throughput "
                 "floor; TPU rows ratchet on the first hardware round "
                 "with BENCH_POD_WRITE=1",
            merge=entries)
        print(f"bench_pod: ratcheted {sorted(values)} -> {path}",
              file=sys.stderr)

    missing = [o.name for o in objectives if o.name not in entries]
    extra = {
        "loss_parity_max_diff": parity,
        "dp_state_bytes_per_chip": int(dp_bytes),
        "fsdp_state_bytes_per_chip": int(fs_bytes),
        "fits_budget_bytes": int(budget),
        "dp_fits": bool(dp_bytes <= budget),
        "fsdp_fits": bool(fs_bytes <= budget),
        "steps_per_dispatch": k,
        "dp_tokens_per_sec": round(batch * seq * steps / dp_dt, 2),
        "mesh_ways": ways, "model": name, "seq": seq, "batch": batch,
        "platform": dev.platform,
    }
    if missing:
        print(f"bench_pod: no ratcheted bound yet for {missing} on this "
              f"platform — BENCH_POD_WRITE=1 ratchets; gate skipped",
              file=sys.stderr)
        report = {"ok": True, "results": [], "breaches": []}
    else:
        report = slo_mod.evaluate(values, entries, objectives)
        print(slo_mod.format_report(report), file=sys.stderr)
    payload = _emit({
        "metric": f"POD gate ({len(report['results'])} objectives, "
                  f"{name} dp vs fsdp x{ways}, {steps} steps)",
        "value": round(tok_s, 2),
        "unit": "tokens/sec (fsdp)",
        "vs_baseline": 1.0 if report["ok"] else 0.0,
        "extra": dict(extra,
                      values={n: round(v, 6) for n, v in values.items()},
                      results=report["results"]),
    })
    return payload if report["ok"] else None


def _bench_decode_shared_prefix(model, on_tpu):
    """BENCH_DECODE sub-row: copy-on-write prefix sharing. N sequences
    extend ONE system prompt; the sharing engine holds a single physical
    copy of the shared KV blocks (refcounts) and skips their prefill,
    multiplying admission headroom at a FIXED pool size. Outputs are
    checked bit-equal against unshared (prefix_cache=False) decode; the
    CPU-smoke gate is >= 1.5x admission headroom (peak blocks,
    deterministic block math) or >= 1.3x useful-tokens/sec."""
    import concurrent.futures

    from paddle_tpu.inference import DecodeEngine

    n_seq = int(os.environ.get("BENCH_DECODE_SHARED_SEQS", "8"))
    sys_len, sfx_len, max_new = 24, 8, 8
    vocab = model.cfg.vocab_size
    rng = np.random.RandomState(3)
    system = rng.randint(0, vocab, (sys_len,)).astype(np.int32)
    prompts = [np.concatenate([
        system, rng.randint(0, vocab, (sfx_len,)).astype(np.int32)])
        for _ in range(n_seq)]

    # a DELIBERATELY tight pool (15 allocatable blocks): each private
    # sequence reserves 5 worst-case blocks, so unshared decode can hold
    # ~3 residents — sharing cuts the FRESH reservation to 2 (the prefix
    # blocks exist once), so the same pool admits ~2x the residents.
    # That resident multiplier IS the admission headroom the gate
    # measures; with block math, it is deterministic on CPU.
    rows = {}
    outs = {}
    for mode, share in (("shared", True), ("unshared", False)):
        eng = DecodeEngine(
            model, max_length=48, block_size=8,
            decode_buckets=(1, 2, 4, 8), prefill_buckets=(8, 16, 32),
            prefill_chunk=8, prefix_cache=share, num_blocks=16,
            default_timeout=600.0)
        try:
            eng.warmup()
            eng.generate(system, 1)      # canary: seeds (or not) the cache
            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(n_seq) as ex:
                outs[mode] = list(ex.map(
                    lambda p: eng.generate(p, max_new), prompts))
            dt = time.perf_counter() - t0
            st = eng.stats()
            rows[mode] = {
                "useful_tokens_per_sec": round(n_seq * max_new / dt, 1),
                "peak_resident_seqs": st["peak_resident"],
                "peak_blocks": st["blocks"]["peak_allocated"],
                "prompt_tokens_reused": st["prefix_cache"]["tokens_reused"],
                "prefill_chunks": st["prefill_chunks"],
                "cow_copies": st["cow_copies"],
            }
        finally:
            eng.shutdown(drain_timeout=30.0)

    mismatches = sum(1 for a, b in zip(outs["shared"], outs["unshared"])
                     if a != b)
    total_prompt = n_seq * (sys_len + sfx_len) + sys_len
    headroom = rows["shared"]["peak_resident_seqs"] \
        / max(1, rows["unshared"]["peak_resident_seqs"])
    tps_ratio = (rows["shared"]["useful_tokens_per_sec"]
                 / max(1e-9, rows["unshared"]["useful_tokens_per_sec"]))
    return {
        "modes": rows,
        "sequences": n_seq,
        "mismatches": mismatches,
        "admission_headroom": round(headroom, 3),
        "tokens_per_sec_ratio": round(tps_ratio, 3),
        "prefill_frac_avoided": round(
            rows["shared"]["prompt_tokens_reused"] / total_prompt, 3),
    }


def _bench_decode_chunked_ttft(model, on_tpu):
    """BENCH_DECODE sub-row: chunked prefill vs monolithic on a
    long-prompt mixed workload. A 96-token prompt lands in a live engine
    followed immediately by short prompts: monolithic prefill stalls
    them for one giant dispatch; chunking (+ shortest-remaining-first
    prefill scheduling) lets the shorts' prefills and the running
    batch's decode steps interleave between chunks. Gate: measured
    TTFT-p99 improvement for the short sequences."""
    import concurrent.futures

    from paddle_tpu.inference import DecodeEngine

    n_short = int(os.environ.get("BENCH_DECODE_TTFT_SHORTS", "6"))
    long_len, short_len = 192, 6
    vocab = model.cfg.vocab_size
    rng = np.random.RandomState(5)
    long_prompt = rng.randint(0, vocab, (long_len,)).astype(np.int32)
    shorts = [rng.randint(0, vocab, (short_len,)).astype(np.int32)
              for _ in range(n_short)]

    rows = {}
    outs = {}
    for mode, chunk in (("chunked", 16), ("monolithic", False)):
        eng = DecodeEngine(
            model, max_length=256, block_size=8,
            decode_buckets=(1, 2, 4, 8), prefill_buckets=(8, 16, 192),
            prefill_chunk=chunk, prefix_cache=False, num_blocks=65,
            default_timeout=600.0)
        ttfts = []
        try:
            eng.warmup()
            # a running batch the long prefill would stall
            bg = [eng.submit(shorts[0], 32), eng.submit(shorts[1], 32)]
            for s in bg:
                next(iter(s))

            def one_short(p):
                t0 = time.perf_counter()
                s = eng.submit(p, 4)
                first = next(iter(s))
                ttfts.append(time.perf_counter() - t0)
                return [first] + [t for t in s]

            long_s = eng.submit(long_prompt, 4)
            # land the shorts while the long prefill is IN FLIGHT (the
            # head-of-line scenario): wait for its admission, then one
            # beat for the scheduler to dispatch its (first or only)
            # prefill
            deadline = time.perf_counter() + 5.0
            while (eng.stats()["prefilling"] < 1
                   and time.perf_counter() < deadline):
                time.sleep(0.001)
            time.sleep(0.004)
            with concurrent.futures.ThreadPoolExecutor(n_short) as ex:
                outs[mode] = list(ex.map(one_short, shorts[2:]
                                         + shorts[:2]))
            outs[mode].append(long_s.result())
            for s in bg:
                s.result()
            rows[mode] = {
                "ttft_p50_ms": round(
                    float(np.percentile(ttfts, 50)) * 1e3, 1),
                "ttft_p99_ms": round(
                    float(np.percentile(ttfts, 99)) * 1e3, 1),
                "prefill_chunks": eng.stats()["prefill_chunks"],
            }
        finally:
            eng.shutdown(drain_timeout=30.0)

    mismatches = sum(1 for a, b in zip(outs["chunked"], outs["monolithic"])
                     if a != b)
    return {
        "modes": rows,
        "mismatches": mismatches,
        "ttft_p99_improvement": round(
            rows["monolithic"]["ttft_p99_ms"]
            / max(1e-9, rows["chunked"]["ttft_p99_ms"]), 3),
    }


def _bench_decode_speculative(on_tpu):
    """BENCH_DECODE sub-row: speculative decoding (draft-proposed,
    one-dispatch verified, docs/llm_serving.md). The workload is the
    real speculative setting built by construction instead of
    distillation (a bench cannot train a draft): the draft is a 2-layer
    model, the target is the SAME two layers plus extra residual blocks
    whose output projections are scaled near zero — so the draft
    approximates the target closely (high acceptance, like a distilled
    draft would) while the target costs ~4x the draft per forward. The
    measured delta is the speculative machinery alone: K+1 tokens
    committed per target dispatch instead of 1. Outputs are checked
    bit-equal to `speculate_k=0` greedy decode — the acceptance
    criterion — and the CPU-smoke gate is >= 1.3x tokens/sec (each mode
    timed best-of-2; the TPU row is not measured yet)."""
    import concurrent.futures

    import paddle_tpu as paddle
    from paddle_tpu.inference import DecodeEngine
    from paddle_tpu.models import gpt

    n_seq = int(os.environ.get("BENCH_DECODE_SPEC_SEQS", "6"))
    k = int(os.environ.get("BENCH_DECODE_SPEC_K", "8"))
    n_layers = 8
    tiny = dict(vocab_size=97, hidden_size=48, num_heads=4,
                num_kv_heads=2, rope=True, swiglu=True, rms_norm=True,
                max_position_embeddings=64, tie_word_embeddings=False)
    paddle.seed(7)
    target = gpt("gpt_tiny", num_layers=n_layers, **tiny)
    paddle.seed(7)
    draft = gpt("gpt_tiny", num_layers=2, **tiny)
    target.eval()
    draft.eval()
    tp = dict(target.named_parameters())
    for name, p in draft.named_parameters():
        p._value = tp[name]._value     # shared early stack + emb + head
    for name, p in target.named_parameters():
        if any(f"layers.{i}." in name for i in range(2, n_layers)) \
                and ("out_proj" in name or "down_proj" in name):
            p._value = p._value * 0.05  # extra blocks ~ identity

    lens = [24, 32, 40, 32]
    want = [lens[i % len(lens)] for i in range(n_seq)]
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, 97, (8,)).astype(np.int32)
               for _ in range(n_seq)]

    rows, outs = {}, {}
    for mode in ("speculative", "greedy"):
        eng = DecodeEngine(
            target, max_length=64, block_size=8,
            decode_buckets=(1, 2, 4), prefill_buckets=(8,),
            prefix_cache=False, default_timeout=600.0,
            draft_model=draft if mode == "speculative" else None,
            speculate_k=k if mode == "speculative" else 0)
        try:
            eng.warmup()
            best, out, st0 = float("inf"), None, None
            for i in range(2):         # best-of-2: CPU timing variance
                # counters below are reported as deltas over the FINAL
                # run (each run commits the identical greedy tokens, so
                # per-run dispatch counts are deterministic) while
                # tokens/sec uses the best run's time — without the
                # snapshot the published dispatch/rollback counts would
                # be two-run totals, 2x the workload's
                if i == 1:
                    st0 = eng.stats()
                t0 = time.perf_counter()
                with concurrent.futures.ThreadPoolExecutor(n_seq) as ex:
                    out = list(ex.map(
                        lambda i: eng.generate(prompts[i], want[i]),
                        range(n_seq)))
                best = min(best, time.perf_counter() - t0)
            outs[mode] = out
            st = eng.stats()
            sp, sp0 = st["speculative"], st0["speculative"]
            proposed = sp["proposed"] - sp0["proposed"]
            accepted = sp["accepted"] - sp0["accepted"]
            committed = sp["committed"] - sp0["committed"]
            verifies = sp["verify_dispatches"] - sp0["verify_dispatches"]
            rows[mode] = {
                "tokens_per_sec": round(sum(want) / best, 1),
                "target_dispatches": (st["steps"] - st0["steps"])
                + verifies + (st["prefills"] - st0["prefills"]),
                "acceptance_rate": round(accepted / proposed, 3)
                if proposed else 0.0,
                "accepted_per_dispatch": round(committed / verifies, 2)
                if verifies else 0.0,
                "rolled_back": sp["rejected"] - sp0["rejected"],
                "fallback_rounds": sp["fallbacks"] - sp0["fallbacks"],
            }
        finally:
            eng.shutdown(drain_timeout=30.0)

    mismatches = sum(1 for a, b in zip(outs["speculative"],
                                       outs["greedy"]) if a != b)
    ratio = (rows["speculative"]["tokens_per_sec"]
             / max(1e-9, rows["greedy"]["tokens_per_sec"]))
    return {
        "modes": rows,
        "k": k,
        "sequences": n_seq,
        "target_layers": n_layers,
        "draft_layers": 2,
        "mismatches": mismatches,
        "tokens_per_sec_ratio": round(ratio, 3),
    }


def _bench_decode_multi_tenant(model, on_tpu):
    """BENCH_DECODE sub-row: multi-tenant LoRA decode (S-LoRA/Punica
    shape, docs/llm_serving.md). One resident base model serves many
    adapters; the batched mode decodes a MIXED-adapter batch through the
    one bucketed step executable (per-sequence adapter ids gather the
    slot-stacked A/B pages in-graph), while the baseline emulates
    single-tenant serving: one adapter's requests at a time, sequential
    waves. Both modes run the identical engine machinery and adapters,
    so the measured delta is adapter multiplexing alone. Per-request
    outputs are checked bit-identical across modes (greedy decode); the
    CPU-smoke gate is >= 1.5x tokens/sec at concurrency 8."""
    import concurrent.futures

    from paddle_tpu.inference import AdapterPool, DecodeEngine

    conc = int(os.environ.get("BENCH_DECODE_MT_SEQS", "8"))
    n_adapters = int(os.environ.get("BENCH_DECODE_MT_ADAPTERS", "8"))
    max_new = 16
    vocab = model.cfg.vocab_size
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, vocab, (6,)).astype(np.int32)
               for _ in range(conc)]
    names = [f"tenant-{i}" for i in range(n_adapters)]
    who = [names[i % n_adapters] for i in range(conc)]

    pool = AdapterPool(model, rank=4, slots=n_adapters + 1)
    weights = {}
    for i, nm in enumerate(names):
        w = {}
        for lname, (a, b) in pool.stacks().items():
            r = np.random.RandomState(100 + i)
            w[lname] = (r.normal(0, 0.05, a.shape[1:]).astype(np.float32),
                        r.normal(0, 0.05, b.shape[1:]).astype(np.float32))
        weights[nm] = w
    for nm in names:
        pool.load(nm, weights[nm])

    eng = DecodeEngine(
        model, max_length=32, block_size=8,
        decode_buckets=tuple(sorted({1, 2, 4, conc})),
        prefill_buckets=(8,), prefix_cache=False,
        default_timeout=600.0, adapters=pool,
        num_blocks=1 + 2 * conc * 4)
    rows, outs = {}, {}
    try:
        eng.warmup()
        for mode in ("sequential", "batched"):
            best, out = float("inf"), None
            for _ in range(2):        # best-of-2: CPU timing variance
                out = [None] * conc
                st0 = eng.stats()
                t0 = time.perf_counter()

                def one(i):
                    out[i] = eng.generate(prompts[i], max_new,
                                          adapter=who[i])

                if mode == "batched":
                    with concurrent.futures.ThreadPoolExecutor(conc) as ex:
                        list(ex.map(one, range(conc)))
                else:
                    # single-tenant emulation: swap the tenant's adapter
                    # in, serve its requests, next tenant — what a
                    # one-adapter-at-a-time deployment actually does
                    for nm in names:
                        pool.load(nm, weights[nm])
                        gang = [i for i in range(conc) if who[i] == nm]
                        with concurrent.futures.ThreadPoolExecutor(
                                len(gang)) as ex:
                            list(ex.map(one, gang))
                best = min(best, time.perf_counter() - t0)
                st = eng.stats()
            outs[mode] = out
            rows[mode] = {
                "tokens_per_sec": round(conc * max_new / best, 1),
                "steps": st["steps"] - st0["steps"],
            }
        astats = eng.stats()["adapters"]
        lookups = astats["hits"] + astats["misses"]
        rows["occupancy"] = round(astats["occupancy"], 3)
        rows["hit_rate"] = round(astats["hits"] / lookups, 3) \
            if lookups else 0.0
        rows["per_adapter"] = {nm: a["refs"]
                               for nm, a in astats["adapters"].items()}
    finally:
        eng.shutdown(drain_timeout=30.0)
    mismatches = sum(1 for a, b in zip(outs["batched"],
                                       outs["sequential"]) if a != b)
    ratio = (rows["batched"]["tokens_per_sec"]
             / max(1e-9, rows["sequential"]["tokens_per_sec"]))
    return {
        "modes": rows,
        "adapters": n_adapters,
        "sequences": conc,
        "mismatches": mismatches,
        "tokens_per_sec_ratio": round(ratio, 3),
    }


def _bench_decode_sampling_parity(model):
    """BENCH_DECODE sub-row: per-request sampling rides the batch as
    VALUES (inference/sampling.py), so a mixed-sampling workload must
    dispatch exactly like the all-greedy one — same step/prefill counts
    at every bucket, zero post-warmup compiles. This row asserts that
    dispatch-count parity instead of a speed gate (identical dispatches
    IS the perf claim: sampling adds no scheduler rounds and no
    retraces)."""
    import concurrent.futures

    from paddle_tpu.inference import DecodeEngine, SamplingParams

    conc = 8
    max_new = 12
    vocab = model.cfg.vocab_size
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, vocab, (6,)).astype(np.int32)
               for _ in range(conc)]
    mixes = [None,
             SamplingParams(temperature=0.8, seed=1),
             SamplingParams(temperature=1.2, top_k=8, seed=2),
             SamplingParams(temperature=0.7, top_p=0.9, seed=3),
             SamplingParams(temperature=0.0),
             SamplingParams(temperature=0.9, repetition_penalty=1.3,
                            seed=4),
             SamplingParams(temperature=1.0, top_k=4, top_p=0.95, seed=5),
             None]
    eng = DecodeEngine(
        model, max_length=32, block_size=8,
        decode_buckets=tuple(sorted({1, 2, 4, conc})),
        prefill_buckets=(8,), prefix_cache=False,
        default_timeout=600.0, num_blocks=1 + 2 * conc * 4)
    try:
        eng.warmup()
        counts = {}
        for mode in ("greedy", "mixed"):
            st0 = eng.stats()

            def one(i):
                sp = mixes[i] if mode == "mixed" else None
                return eng.generate(prompts[i], max_new, sampling=sp)

            with concurrent.futures.ThreadPoolExecutor(conc) as ex:
                list(ex.map(one, range(conc)))
            st = eng.stats()
            counts[mode] = {
                "steps": st["steps"] - st0["steps"],
                "prefills": st["prefills"] - st0["prefills"],
                "compiles": (st["compiles"]["built"]
                             - st0["compiles"]["built"]),
            }
    finally:
        eng.shutdown(drain_timeout=30.0)
    return {
        "modes": counts,
        "dispatch_parity": counts["greedy"]["steps"]
        == counts["mixed"]["steps"]
        and counts["greedy"]["prefills"] == counts["mixed"]["prefills"],
        "post_warmup_compiles": counts["mixed"]["compiles"]
        + counts["greedy"]["compiles"],
    }


def bench_decode(on_tpu, dev):
    """BENCH_DECODE=1: continuous-batching LLM decode — tokens/sec and
    p50/p99 time-to-first-token of the iteration-level `DecodeEngine`
    (inference/decode, docs/llm_serving.md) vs REQUEST-level batching on
    mixed-length generations.

    The baseline emulates what `DynamicBatcher` semantics give a
    generation workload: a formed batch decodes until its LONGEST member
    finishes (a batched program cannot stop per-row, so finished
    sequences keep occupying their slots doing padded work) and the next
    batch waits for the whole gang to drain. Both modes run the SAME
    paged, bucketed AOT step executables, so the measured delta is the
    scheduling policy alone — iteration-level join/leave vs
    head-of-line blocking. Only useful (per-request) tokens count toward
    tokens/sec; per-request outputs are checked identical across modes
    (greedy decode is deterministic). `vs_baseline` is the
    continuous/request-level tokens/sec ratio; the acceptance gate is
    >= 1.5x at concurrency >= 8. The CPU smoke runs a tiny varied-output
    GPT (rope + GQA + swiglu); the real-model rows are not measured on a
    TPU yet."""
    import concurrent.futures

    import paddle_tpu as paddle
    from paddle_tpu.inference import DecodeEngine
    from paddle_tpu.models import gpt

    conc = int(os.environ.get("BENCH_DECODE_CONCURRENCY", "8"))
    lens = [int(x) for x in os.environ.get(
        "BENCH_DECODE_LENS", "3,4,6,8,10,12,16,20").split(",")]
    gangs = int(os.environ.get("BENCH_DECODE_GANGS", "6"))
    n_req = gangs * conc
    prompt_len = 6
    max_len = prompt_len + max(lens) + prompt_len  # headroom for prefill pad

    paddle.seed(7)
    name = os.environ.get("BENCH_MODEL", "gpt_base" if on_tpu else "")
    if name:
        model = gpt(name, max_position_embeddings=max(max_len, 64))
    else:
        model = gpt("gpt_tiny", vocab_size=97, hidden_size=48,
                    num_heads=4, num_kv_heads=2, num_layers=2,
                    rope=True, swiglu=True, rms_norm=True,
                    max_position_embeddings=64,
                    tie_word_embeddings=False)
    model.eval()
    vocab = model.cfg.vocab_size
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, vocab, (prompt_len,)).astype(np.int32)
               for _ in range(conc)]
    want = [lens[i % len(lens)] for i in range(n_req)]

    def make_engine():
        return DecodeEngine(
            model, max_length=max_len, block_size=8,
            decode_buckets=tuple(sorted({1, 2, 4, conc})),
            prefill_buckets=(8,), default_timeout=600.0,
            num_blocks=1 + 2 * conc * -(-max_len // 8))

    def percentiles(ts):
        return {"p50_ms": round(float(np.percentile(ts, 50)) * 1e3, 1),
                "p99_ms": round(float(np.percentile(ts, 99)) * 1e3, 1)}

    results = {}
    for mode in ("request_level", "continuous"):
        eng = make_engine()
        try:
            eng.warmup()          # compiles excluded from the measure
            ttft = [0.0] * n_req
            outs = [None] * n_req
            t0 = time.perf_counter()

            def one(i, max_new):
                s = eng.submit(prompts[i % conc], max_new)
                toks = []
                for tok in s:
                    if not toks:
                        ttft[i] = time.perf_counter() - t0
                    toks.append(tok)
                outs[i] = toks

            if mode == "continuous":
                # open admission: sequences join the running batch the
                # moment a client thread frees up
                with concurrent.futures.ThreadPoolExecutor(conc) as ex:
                    list(ex.map(one, range(n_req), want))
            else:
                # request granularity: every gang member decodes to the
                # gang max (the batched program can't stop per-row) and
                # the next gang waits for a full drain
                for g in range(0, n_req, conc):
                    gang = list(range(g, g + conc))
                    gmax = max(want[i] for i in gang)
                    with concurrent.futures.ThreadPoolExecutor(
                            conc) as ex:
                        list(ex.map(one, gang, [gmax] * conc))
            dt = time.perf_counter() - t0
            useful = sum(want)
            results[mode] = {
                "tokens_per_sec": round(useful / dt, 1),
                "ttft": percentiles(ttft),
                "occupancy": round(eng.stats()["occupancy"], 3),
                "steps": eng.stats()["steps"],
            }
            # useful tokens only: truncate gang overruns before compare
            results[mode]["outs"] = [o[:want[i]]
                                     for i, o in enumerate(outs)]
        finally:
            eng.shutdown(drain_timeout=30.0)

    mismatches = sum(
        1 for a, b in zip(results["continuous"].pop("outs"),
                          results["request_level"].pop("outs"))
        if a != b)
    speedup = (results["continuous"]["tokens_per_sec"]
               / results["request_level"]["tokens_per_sec"])

    # Decode speed 2.0 rows: copy-on-write prefix sharing, chunked
    # prefill, and speculative decoding — each bit-equality-checked
    # against its private/monolithic/greedy twin and CPU-smoke
    # gated below
    shared = _bench_decode_shared_prefix(model, on_tpu)
    ttft = _bench_decode_chunked_ttft(model, on_tpu)
    spec = _bench_decode_speculative(on_tpu)
    mt = _bench_decode_multi_tenant(model, on_tpu)
    samp = _bench_decode_sampling_parity(model)

    payload = _emit({
        "metric": f"continuous-batching decode tokens/sec "
                  f"(concurrency={conc}, mixed max_new "
                  f"{min(lens)}..{max(lens)}, "
                  f"{name or 'tiny gpt'})",
        "value": results["continuous"]["tokens_per_sec"],
        "unit": "tokens/sec",
        "vs_baseline": round(speedup, 3),
        "extra": {"modes": results, "requests": n_req,
                  "mismatches": mismatches,
                  "shared_prefix": shared,
                  "chunked_prefill": ttft,
                  "speculative": spec,
                  "multi_tenant": mt,
                  "sampling_parity": samp,
                  "platform": dev.platform},
    })
    if mismatches:
        print(f"bench_decode: {mismatches} request(s) diverged between "
              f"modes", file=sys.stderr)
        return None
    if conc >= 8 and speedup < 1.5:
        print(f"bench_decode: speedup {speedup:.2f}x below the 1.5x "
              f"gate at concurrency {conc}", file=sys.stderr)
        return None
    if shared["mismatches"]:
        print(f"bench_decode: {shared['mismatches']} shared-prefix "
              f"request(s) diverged from unshared decode",
              file=sys.stderr)
        return None
    if shared["admission_headroom"] < 1.5 \
            and shared["tokens_per_sec_ratio"] < 1.3:
        print(f"bench_decode: prefix sharing gate failed — headroom "
              f"{shared['admission_headroom']:.2f}x < 1.5x AND "
              f"tokens/sec {shared['tokens_per_sec_ratio']:.2f}x "
              f"< 1.3x", file=sys.stderr)
        return None
    if ttft["mismatches"]:
        print(f"bench_decode: {ttft['mismatches']} chunked-prefill "
              f"request(s) diverged from monolithic decode",
              file=sys.stderr)
        return None
    if ttft["ttft_p99_improvement"] < 1.1:
        print(f"bench_decode: chunked prefill gate failed — TTFT p99 "
              f"improvement {ttft['ttft_p99_improvement']:.2f}x "
              f"< 1.1x on the long-prompt mixed workload",
              file=sys.stderr)
        return None
    if spec["mismatches"]:
        print(f"bench_decode: {spec['mismatches']} speculative "
              f"request(s) diverged from speculate_k=0 greedy decode",
              file=sys.stderr)
        return None
    if spec["tokens_per_sec_ratio"] < 1.3:
        print(f"bench_decode: speculative gate failed — "
              f"{spec['tokens_per_sec_ratio']:.2f}x tokens/sec "
              f"< 1.3x vs speculate_k=0 (acceptance "
              f"{spec['modes']['speculative']['acceptance_rate']})",
              file=sys.stderr)
        return None
    if mt["mismatches"]:
        print(f"bench_decode: {mt['mismatches']} multi-tenant "
              f"request(s) diverged between batched mixed-adapter "
              f"decode and sequential per-adapter serving",
              file=sys.stderr)
        return None
    if mt["tokens_per_sec_ratio"] < 1.5:
        print(f"bench_decode: multi-tenant gate failed — "
              f"{mt['tokens_per_sec_ratio']:.2f}x tokens/sec < 1.5x "
              f"vs sequential per-adapter serving at concurrency "
              f"{mt['sequences']}", file=sys.stderr)
        return None
    if not samp["dispatch_parity"] or samp["post_warmup_compiles"]:
        print(f"bench_decode: sampling parity gate failed — mixed-"
              f"sampling dispatch counts {samp['modes']['mixed']} vs "
              f"greedy {samp['modes']['greedy']} "
              f"({samp['post_warmup_compiles']} post-warmup "
              f"compiles)", file=sys.stderr)
        return None
    return payload


def bench_gpt(on_tpu, dev):
    """Flagship (BASELINE north star): GPT/ERNIE-base-class pretrain step."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models import gpt
    from paddle_tpu.models.gpt import GPTConfig, CONFIGS, flops_per_token

    name = os.environ.get("BENCH_MODEL", "gpt_base")
    seq_len = int(os.environ.get("BENCH_SEQLEN", "1024"))
    batch = int(os.environ.get("BENCH_BATCH", "16" if on_tpu else "2"))
    steps = int(os.environ.get("BENCH_STEPS", "20" if on_tpu else "3"))
    if not on_tpu:  # CPU smoke: shrink
        name = os.environ.get("BENCH_MODEL", "gpt_tiny")
        seq_len = min(seq_len, 128)

    cfg = GPTConfig(**{**CONFIGS[name],
                       "max_position_embeddings": max(
                           seq_len,
                           CONFIGS[name].get("max_position_embeddings",
                                             seq_len))})

    def make_engine():
        paddle.seed(0)
        model = gpt(name,
                    max_position_embeddings=cfg.max_position_embeddings)
        opt = paddle.optimizer.AdamW(
            learning_rate=1e-4, parameters=model.parameters(),
            grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
        mesh = dist.build_mesh(dp=-1, devices=jax.devices()[:1])
        return dist.parallelize(model, opt, mesh=mesh,
                                compute_dtype="bfloat16" if on_tpu else None)

    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq_len)).astype("int32"))

    # BENCH_MULTISTEP=k (default 5) drives the pipelined hot path: k
    # optimizer steps per dispatch through Engine.train_batches' fused
    # lax.scan variant — no host work between micro-steps
    # (docs/performance.md). BENCH_MULTISTEP=1 restores one dispatch/step.
    k = _multistep_k(steps)
    if k > 1:
        final_loss, dt = _measure_multistep(make_engine, (ids,), steps, k)
    else:
        final_loss, dt = _measure(make_engine, (ids,), steps)

    if os.environ.get("BENCH_PROFILE") == "1":
        _export_profile(make_engine, (ids,))

    tokens = batch * seq_len * steps
    tps = tokens / dt

    flops_tok = flops_per_token(cfg, seq_len)
    # v5e peak bf16: 197 TFLOP/s; CPU has no meaningful peak — report 0 MFU
    peak = _peak(dev, "bf16_flops")
    mfu = tps * flops_tok / peak
    vs_baseline = mfu / 0.40 if on_tpu else 0.0

    return {
        "metric": f"{name} pretrain tokens/sec/chip (seq={seq_len}, "
                  f"bs={batch}, {'bf16' if on_tpu else 'f32'})",
        "value": round(tps, 2),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(vs_baseline, 4),
        "extra": {"mfu": round(mfu, 4), "loss": round(final_loss, 4),
                  "steps": steps, "steps_per_dispatch": k,
                  "platform": dev.platform},
    }


LONGCTX_BASELINE_FILENAME = "LONGCTX_BASELINE.json"


def _longctx_objectives(on_tpu):
    """Declared ratchet objectives for the long-context serving row:
    chunked-prefill TTFT must not grow, decode tokens/sec must not drop.
    CPU smoke bounds are generous (machine variance); TPU rows ratchet
    independently under their own names."""
    from paddle_tpu.obs.slo import Objective

    pre = "tpu" if on_tpu else "cpu"
    return [
        Objective(f"longctx.{pre}_ttft_ms", "max",
                  description="long-prompt CP chunked-prefill time to "
                              "first token",
                  unit="ms", slack=3.0),
        Objective(f"longctx.{pre}_tokens_per_sec", "min",
                  description="decode tokens/sec after a long-prompt CP "
                              "chunked prefill",
                  unit="tok/s", slack=3.0),
    ]


def _longctx_gate(on_tpu, ttft_ms, tps):
    """vs_baseline ratchet for BENCH_LONGCTX (mirrors the conv gate):
    evaluated against the checked-in LONGCTX_BASELINE.json bounds; a
    breach beyond the slack fails the bench like a correctness bug
    (e.g. the prefill chunks falling off the cp-sharded executable and
    recompiling, or the ring schedule degenerating to a serial gather).
    BENCH_LONGCTX_WRITE=1 re-ratchets this platform's rows (merging)."""
    from paddle_tpu.obs import slo as slo_mod

    objectives = _longctx_objectives(on_tpu)
    values = {objectives[0].name: ttft_ms, objectives[1].name: tps}
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        LONGCTX_BASELINE_FILENAME)
    try:
        entries = slo_mod.load_baseline(path)
    except FileNotFoundError:
        entries = {}

    if os.environ.get("BENCH_LONGCTX_WRITE") == "1":
        entries = slo_mod.write_baseline(
            path, values, objectives,
            note="long-context serving ratchet bounds (ISSUE 19); "
                 "re-ratchet with BENCH_LONGCTX_WRITE=1 only for an "
                 "intentional, explained perf change",
            merge=entries)
        print(f"longctx gate: ratcheted {[o.name for o in objectives]} "
              f"-> {path}", file=sys.stderr)

    missing = [o.name for o in objectives if o.name not in entries]
    if missing:
        print(f"longctx gate: no ratcheted bound yet for {missing} on "
              f"this platform — BENCH_LONGCTX_WRITE=1 ratchets; gate "
              f"skipped", file=sys.stderr)
        return True
    report = slo_mod.evaluate(values, entries, objectives)
    print(slo_mod.format_report(report), file=sys.stderr)
    return report["ok"]


def bench_longctx(on_tpu, dev):
    """BENCH_LONGCTX=1: long-context serving row — TTFT and decode
    tokens/sec at long prompt lengths through the DecodeEngine's
    context-parallel chunked prefill (prefill token buffer sequence-
    sharded along the mesh `cp` axis; each absolute-boundary chunk is
    one ring-scheduled unit, docs/long_context.md). The CPU smoke runs
    the tiny rope/GQA/swiglu GPT on the 8-virtual-device mesh with
    MeshConfig(cp=4) and cross-checks the cp output bit-identical to
    the single-device engine; TPU rows ratchet under their own
    objective names. Gated against LONGCTX_BASELINE.json."""

    import jax
    import paddle_tpu as paddle
    from paddle_tpu.inference import DecodeEngine
    from paddle_tpu.models import gpt
    from paddle_tpu.sharding import MeshConfig

    prompt_len = int(os.environ.get("BENCH_PROMPT_LEN",
                                    "3072" if on_tpu else "96"))
    new_tokens = int(os.environ.get("BENCH_NEW_TOKENS",
                                    "64" if on_tpu else "8"))
    cp = int(os.environ.get("BENCH_CP", "4"))
    chunk = int(os.environ.get("BENCH_PREFILL_CHUNK",
                               "512" if on_tpu else "32"))
    max_len = prompt_len + new_tokens + 8

    if jax.device_count() < cp:
        # an unsharded run under the cp row's name would be another
        # workload's number: set BENCH_CP to what the host has
        raise SystemExit(f"bench_longctx: {jax.device_count()} device(s) "
                         f"< cp={cp}")
    mesh = MeshConfig(cp=cp).build() if cp > 1 else None


    def build_model():
        paddle.seed(7)
        name = os.environ.get("BENCH_MODEL",
                              "gpt_base" if on_tpu else "")
        if name:
            m = gpt(name, max_position_embeddings=max(max_len, 64))
        else:
            m = gpt("gpt_tiny", vocab_size=97, hidden_size=48,
                    num_heads=4, num_kv_heads=2, num_layers=2,
                    rope=True, swiglu=True, rms_norm=True,
                    max_position_embeddings=max_len,
                    tie_word_embeddings=False)
        m.eval()
        return m

    model = build_model()
    vocab = model.cfg.vocab_size
    prompt = np.random.RandomState(0).randint(
        1, vocab - 1, (prompt_len,)).astype(np.int32)
    # the largest bucket admits the full prompt (max_prompt is bucket-
    # capped even when chunking); the chunk bucket does the work —
    # every dispatched chunk is `chunk` long, cp | chunk
    geo = dict(max_length=max_len, block_size=8,
               decode_buckets=(1,),
               prefill_buckets=tuple(sorted({chunk, prompt_len})),
               prefill_chunk=chunk, default_timeout=600.0)

    bit_identical = None
    if not on_tpu:
        ref_eng = DecodeEngine(build_model(), **geo)
        try:
            ref_toks = ref_eng.generate(prompt, new_tokens,
                                        timeout=600.0)
        finally:
            ref_eng.shutdown()

    eng = DecodeEngine(model, **geo, mesh=mesh)
    try:
        eng.warmup()
        toks = eng.generate(prompt, new_tokens, timeout=600.0)
        if not on_tpu:
            bit_identical = (toks == ref_toks)

        def best_of(n, fn):
            best = float("inf")
            for _ in range(n):
                t0 = time.perf_counter()
                fn()
                best = min(best, time.perf_counter() - t0)
            return best

        ttft_s = best_of(3, lambda: eng.generate(
            prompt, 1, timeout=600.0))
        full_s = best_of(3, lambda: eng.generate(
            prompt, new_tokens, timeout=600.0))
    finally:
        eng.shutdown()

    ttft_ms = ttft_s * 1e3
    tps = new_tokens / full_s
    ok = _longctx_gate(on_tpu, ttft_ms, tps)
    if bit_identical is False:
        print("bench_longctx: CP output DIVERGED from single-device "
              "engine", file=sys.stderr)
        ok = False
    payload = _emit({
        "metric": f"long-context decode tokens/sec (prompt={prompt_len}, "
                  f"cp={cp}, chunked prefill x{-(-prompt_len // chunk)})",
        "value": round(tps, 2),
        "unit": "tokens/sec",
        "vs_baseline": 0.0,
        "extra": {"ttft_ms": round(ttft_ms, 1), "prompt_len": prompt_len,
                  "new_tokens": new_tokens, "cp": cp,
                  "prefill_chunk": chunk,
                  "bit_identical_vs_single_device": bit_identical,
                  "platform": dev.platform},
    })
    return payload if ok else None


def main():
    if (os.environ.get("BENCH_POD") == "1"
            or os.environ.get("BENCH_LONGCTX") == "1") and \
            "tpu" not in os.environ.get("JAX_PLATFORMS", "").lower():
        # the pod gate's CPU smoke needs the 8-virtual-device mesh, and
        # the flag must land BEFORE jax initializes its backend
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()

    import jax
    from paddle_tpu.jit.aot import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and "cpu" not in os.environ.get(
            "JAX_PLATFORMS", "").lower().split(","):
        # jax falls back to the CPU without a word when it finds no chip;
        # the toy-size CPU smoke runs only when asked for by name, so a
        # device metric is never printed from a machine without the device
        print(f"bench: first device is {dev.platform} "
              f"({dev.device_kind}), not a TPU; set JAX_PLATFORMS=cpu for "
              f"the explicit CPU smoke", file=sys.stderr)
        return 1

    if os.environ.get("BENCH_POD") == "1":
        # pod-scale training defaults gate: dp-vs-fsdp on the (virtual)
        # pod mesh against the checked-in POD_BASELINE.json ratchet
        return 0 if bench_pod(on_tpu, dev) else 1

    if os.environ.get("BENCH_SLO") == "1":
        # perf-SLO regression gate: declared objectives vs the checked-in
        # SLO_BASELINE.json ratchet; nonzero exit on breach
        return 0 if bench_slo(on_tpu, dev) else 1

    if os.environ.get("BENCH_SERVING") == "1":
        # serving-throughput mode: its own one-line JSON (requests/sec,
        # batched-vs-unbatched) instead of the flagship train metric
        return 0 if bench_serving(on_tpu, dev) else 1

    if os.environ.get("BENCH_DECODE") == "1":
        # continuous-batching decode mode: tokens/sec + TTFT, iteration-
        # level engine vs request-level batching (gate >= 1.5x at c >= 8)
        return 0 if bench_decode(on_tpu, dev) else 1

    if os.environ.get("BENCH_LONGCTX") == "1":
        # long-context serving: TTFT + tokens/sec at long prompt lengths
        # through the cp-sharded chunked prefill, ratcheted against the
        # checked-in LONGCTX_BASELINE.json
        return 0 if bench_longctx(on_tpu, dev) else 1

    if "--model" in sys.argv:
        i = sys.argv.index("--model")
        if i + 1 >= len(sys.argv):
            print("usage: bench.py [--model gpt_base|resnet50|bert|"
                  "lora_decode] (BENCH_ALL=1 runs every config and writes "
                  "BENCH_ALL.json)", file=sys.stderr)
            sys.exit(2)
        os.environ["BENCH_MODEL"] = sys.argv[i + 1]

    if os.environ.get("BENCH_ALL") == "1":
        # all measured configs -> BENCH_ALL.json artifact (VERDICT r2 weak
        # #2: every README perf claim must trace to a driver-captured or
        # in-repo artifact); flagship line alone on stdout
        os.environ.pop("BENCH_MODEL", None)   # each config picks defaults
        # shell-exported quant knobs must not leak into the bf16 rows —
        # the quantized-variant loop below re-sets them per row
        os.environ.pop("BENCH_WEIGHT_DTYPE", None)
        os.environ.pop("BENCH_KV_DTYPE", None)
        payloads = [_emit(bench_gpt(on_tpu, dev))]
        gate_failed = False
        for fn in (bench_resnet50, bench_bert_finetune, bench_ppyoloe,
                   bench_lora_decode):
            os.environ.pop("BENCH_MODEL", None)
            p = fn(on_tpu, dev)
            if p is None:
                # a ratchet gate breached (conv vs_baseline rows): keep
                # measuring the rest, fail the run at the end — a perf
                # regression fails like a correctness bug
                gate_failed = True
            else:
                payloads.append(p)
        for wdtype, kv in (("int8", ""), ("int4", ""), ("int8", "int8")):
            # weight-only decode variants + the fully-quantized row; both
            # env knobs are forced per row so shell-exported values cannot
            # leak into the matrix
            os.environ["BENCH_WEIGHT_DTYPE"] = wdtype
            os.environ["BENCH_KV_DTYPE"] = kv
            try:
                payloads.append(bench_lora_decode(on_tpu, dev))
            finally:
                os.environ.pop("BENCH_WEIGHT_DTYPE", None)
                os.environ.pop("BENCH_KV_DTYPE", None)
        if on_tpu:
            # weight-dominated decode row (VERDICT r4 item 6): llama2-7B
            # int8 at bs=1 — here the frac metric measures the kernels
            # rather than the KV/LoRA/latency floor (docs/decode_perf.md)
            os.environ.update(BENCH_MODEL="llama2_7b",
                              BENCH_WEIGHT_DTYPE="int8", BENCH_BATCH="1",
                              BENCH_NEW_TOKENS="128")
            try:
                payloads.append(bench_lora_decode(on_tpu, dev))
            finally:
                for k in ("BENCH_MODEL", "BENCH_WEIGHT_DTYPE",
                          "BENCH_BATCH", "BENCH_NEW_TOKENS"):
                    os.environ.pop(k, None)
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_ALL.json"), "w") as f:
            json.dump(payloads, f, indent=1)
        print(json.dumps(payloads[0]))
        return 1 if gate_failed else 0

    mode = os.environ.get("BENCH_MODEL", "")
    if mode.startswith("resnet"):
        return 0 if bench_resnet50(on_tpu, dev) else 1
    if mode.startswith("bert"):
        return 0 if bench_bert_finetune(on_tpu, dev) else 1
    if "yolo" in mode:
        return 0 if bench_ppyoloe(on_tpu, dev) else 1
    if "lora" in mode or mode == "decode":
        return 0 if bench_lora_decode(on_tpu, dev) else 1
    print(json.dumps(_stamp(bench_gpt(on_tpu, dev))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
