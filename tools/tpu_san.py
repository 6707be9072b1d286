#!/usr/bin/env python
"""tpu_san — CLI for the paddle_tpu runtime sanitizer (tpu-san).

Where ``tools/tpu_lint.py`` ratchets what the AST can prove, this tool
ratchets what only a *live* process can: it runs the framework's own hot
paths with ``paddle_tpu.analysis.runtime_san`` enabled — the training
engine (retrace sentinel, donation guard, non-finite sweep, hot-region
probes around dispatch) and a serving pool (hot-region probes around
execute) — then compares the recorded findings against the checked-in
baseline.

Usage:

    python tools/tpu_san.py                       # ratcheted smoke run
    python tools/tpu_san.py --smoke engine        # engine hot path only
    python tools/tpu_san.py --format json
    python tools/tpu_san.py --write-baseline

Exit codes (stable contract, asserted by tests/test_runtime_san.py):

    0   clean — no findings beyond the baseline
    1   new findings beyond the baseline
    2   usage error (bad smoke name, unreadable baseline, bad args)

The baseline (default: <repo>/.tpu_san_baseline.json) freezes existing
findings by ``site::detector`` count — line-number-free and
instance-free, like the tracelint ratchet, so it never churns when code
moves. The framework is expected to hold the baseline at ZERO findings;
the deep end-to-end dogfood (every serving/decode/router fault phase
with the sanitizer live) runs in ``tools/serving_fault_injector.py``.

Unlike tpu_lint this tool MUST import and execute the framework — a
runtime sanitizer has nothing to analyze until the program runs. It
pins JAX_PLATFORMS=cpu so CI boxes without an accelerator behave
identically.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

DEFAULT_BASELINE = os.path.join(REPO, ".tpu_san_baseline.json")
SMOKES = ("engine", "serving", "decode")

USAGE_ERROR, NEW_FINDINGS, CLEAN = 2, 1, 0


def _smoke_engine():
    """Training hot path: build, warm, then steady-state steps — every
    detector live (retrace sentinel on the step/multi/eval entrypoints,
    hot region around dispatch, donation notes on the carried state,
    non-finite sweep over loss/grads/params per dispatch)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.analysis import runtime_san
    from paddle_tpu.distributed.engine import parallelize

    paddle.seed(0)
    model = nn.Linear(8, 4)
    opt = optimizer.SGD(learning_rate=0.1,
                        parameters=model.parameters())
    eng = parallelize(model, opt,
                      loss_fn=lambda m, x, y: ((m(x) - y) ** 2).mean())
    rng = np.random.RandomState(0)
    # batch dim 8: divisible by any dp the host mesh exposes (incl. the
    # 8-virtual-device CPU test mesh), and fine on a single device
    x = paddle.to_tensor(rng.rand(8, 8).astype(np.float32))
    y = paddle.to_tensor(rng.rand(8, 4).astype(np.float32))
    eng.train_batch(x, y)                     # cold: trace + compile
    eng.train_batches([(x, y)] * 3)           # cold multi-step pipeline
    eng.eval_batch(x, y)
    runtime_san.mark_warm()
    for _ in range(3):                        # steady state: must not
        eng.train_batch(x, y)                 # trace or sync again
    eng.train_batches([(x, y)] * 3)
    eng.eval_batch(x, y)


def _smoke_serving():
    """Serving hot path on a stub predictor (no export, no XLA compile —
    the real-model end-to-end dogfood is the fault injector): proves the
    serving.execute hot-region probes run clean under concurrency."""
    import numpy as np

    from paddle_tpu.analysis import runtime_san
    from paddle_tpu.inference import Predictor, ServingPool

    class _Out:
        def __init__(self, a):
            self._a = a

        def numpy(self):
            return self._a

    class _StubLayer:
        input_spec = [{"shape": [2], "dtype": "float32"}]
        num_outputs = 1

        def __call__(self, x):
            return _Out(np.asarray(x) * 2.0)

    pool = ServingPool(predictor=Predictor(None, _shared_layer=_StubLayer()),
                       size=2, max_queue_depth=64, default_timeout=10.0)
    try:
        pool.infer([np.ones(2, np.float32)])
        runtime_san.mark_warm()
        for i in range(16):
            out, = pool.infer([np.full(2, i, np.float32)])
            assert out[0] == 2.0 * i
    finally:
        pool.shutdown(drain_timeout=5.0)


def _smoke_decode():
    """Multi-tenant decode hot path: warm every bucket, arm the retrace
    sentinel, then sweep a MIXED-adapter + MIXED-sampling warm batch
    through the one set of compiled step executables — adapter ids and
    sampling params are per-sequence VALUES, so no mix may ever trace
    again (the zero-post-warmup-retraces contract of
    docs/llm_serving.md)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.analysis import runtime_san
    from paddle_tpu.inference import (AdapterPool, DecodeEngine,
                                      SamplingParams)
    from paddle_tpu.models import gpt

    paddle.seed(7)
    model = gpt("gpt_tiny", vocab_size=97, hidden_size=32, num_heads=4,
                num_kv_heads=2, num_layers=1, rope=True, swiglu=True,
                rms_norm=True, max_position_embeddings=64,
                tie_word_embeddings=False)
    model.eval()
    pool = AdapterPool(model, rank=2, slots=3)
    rng = np.random.RandomState(0)
    for nm in ("a", "b"):
        pool.load(nm, {ln: (rng.normal(0, 0.05, a.shape[1:])
                            .astype(np.float32),
                            rng.normal(0, 0.05, b.shape[1:])
                            .astype(np.float32))
                       for ln, (a, b) in pool.stacks().items()})
    eng = DecodeEngine(model, max_length=24, block_size=8,
                       decode_buckets=(1, 2, 4), prefill_buckets=(8,),
                       prefix_cache=False, default_timeout=30.0,
                       adapters=pool)
    try:
        eng.warmup()
        runtime_san.mark_warm()
        prompts = [rng.randint(0, 97, (5,)).astype(np.int32)
                   for _ in range(4)]
        mixes = [(None, None),
                 ("a", None),
                 ("b", SamplingParams(temperature=0.8, top_k=8, seed=1)),
                 ("a", SamplingParams(temperature=1.1, top_p=0.9,
                                      repetition_penalty=1.2, seed=2))]
        import concurrent.futures
        with concurrent.futures.ThreadPoolExecutor(4) as ex:
            list(ex.map(
                lambda i: eng.generate(prompts[i], 6,
                                       adapter=mixes[i][0],
                                       sampling=mixes[i][1]),
                range(4)))
        # a CHANGED mix over the same buckets: values only, no retrace
        for i in range(4):
            eng.generate(prompts[i], 4, adapter=mixes[3 - i][0],
                         sampling=mixes[3 - i][1])
    finally:
        eng.shutdown(drain_timeout=5.0)


def run_smokes(names):
    """Run the selected workloads with the sanitizer live; returns the
    (counts, report) pair recorded across them."""
    from paddle_tpu.analysis import runtime_san

    runtime_san.enable()
    runtime_san.reset()
    for name in names:
        {"engine": _smoke_engine, "serving": _smoke_serving,
         "decode": _smoke_decode}[name]()
    return runtime_san.counts_by_key(), runtime_san.report()


def _render_text(counts, fresh, report, baseline_used, out):
    by_key = {}
    for f in report["findings"]:
        by_key.setdefault(f"{f['site']}::{f['detector']}", []).append(f)
    for key, (n, base) in fresh.items():
        print(f"{key}: {n} finding(s) (baseline {base})", file=out)
        for f in by_key.get(key, ())[:3]:
            print(f"  {f['message']}", file=out)
    kept = sum(counts.values()) - sum(n for n, _ in fresh.values())
    tail = f" ({kept} baselined finding(s) suppressed)" \
        if baseline_used and kept else ""
    c = report["counters"]
    print(f"tpu_san: {sum(n for n, _ in fresh.values())} new finding(s), "
          f"{sum(counts.values())} total{tail} "
          f"[traces={c['traces']} hot_regions={c['hot_regions']} "
          f"donations={c['donations']} finite_checks={c['finite_checks']}]",
          file=out)


def _render_json(counts, fresh, report, baseline_used, out):
    payload = {
        "tool": "tpu_san",
        "new": {k: {"count": n, "baseline": b}
                for k, (n, b) in fresh.items()},
        "new_count": sum(n for n, _ in fresh.values()),
        "total_count": sum(counts.values()),
        "counts": counts,
        "counters": report["counters"],
        "baseline_used": bool(baseline_used),
        "findings": report["findings"],
    }
    json.dump(payload, out, indent=2, sort_keys=True)
    out.write("\n")


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="tpu_san", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--smoke", default=",".join(SMOKES),
                    help=f"comma-separated workloads to run "
                         f"(default: {','.join(SMOKES)})")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help=f"baseline file (default {DEFAULT_BASELINE})")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline: report every finding")
    ap.add_argument("--write-baseline", action="store_true",
                    help="regenerate the baseline from this run's "
                         "findings (sorted keys) and exit 0")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    try:
        args = ap.parse_args(argv)
    except SystemExit as e:
        raise SystemExit(USAGE_ERROR if e.code else 0)

    smokes = [s.strip() for s in args.smoke.split(",") if s.strip()]
    bad = [s for s in smokes if s not in SMOKES]
    if bad or not smokes:
        print(f"tpu_san: unknown smoke(s) {bad or args.smoke!r} "
              f"(choose from {', '.join(SMOKES)})", file=sys.stderr)
        return USAGE_ERROR

    baseline_counts, baseline_used = {}, False
    if not args.no_baseline and not args.write_baseline:
        if os.path.exists(args.baseline):
            from paddle_tpu.analysis import runtime_san
            try:
                baseline_counts = runtime_san.load_baseline(args.baseline)
            except (ValueError, OSError, json.JSONDecodeError) as e:
                print(f"tpu_san: unreadable baseline {args.baseline}: {e}",
                      file=sys.stderr)
                return USAGE_ERROR
            baseline_used = True
        elif args.baseline != DEFAULT_BASELINE:
            print(f"tpu_san: baseline not found: {args.baseline}",
                  file=sys.stderr)
            return USAGE_ERROR

    # hermetic AOT cache: every run sees the same cold start
    from paddle_tpu.jit.aot import hermetic_cache

    with hermetic_cache(prefix="tpu-san-"):
        counts, report = run_smokes(smokes)

    from paddle_tpu.analysis import runtime_san

    if args.write_baseline:
        runtime_san.write_baseline(args.baseline, counts)
        print(f"tpu_san: wrote {sum(counts.values())} finding(s) across "
              f"{len(counts)} key(s) to {args.baseline}", file=sys.stderr)
        return CLEAN

    fresh = runtime_san.new_counts(counts, baseline_counts)
    render = _render_json if args.format == "json" else _render_text
    render(counts, fresh, report, baseline_used, sys.stdout)
    return NEW_FINDINGS if fresh else CLEAN


if __name__ == "__main__":
    sys.exit(main())
