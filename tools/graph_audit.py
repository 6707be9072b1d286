#!/usr/bin/env python
"""graph_audit — CLI for the paddle_tpu graph auditor (graphcheck).

``tools/tpu_lint.py`` ratchets what the AST can prove and
``tools/tpu_san.py`` what a live run can observe; this tool ratchets
what XLA actually **compiled**. It runs the framework's own entrypoints
with ``paddle_tpu.analysis.graphcheck`` enabled — the training engine
(train/eval/multi-step programs, incl. an NHWC conv stack for the
layout rule), the decode engine (every prefill/decode bucket
executable) and the export path (`TranslatedLayer` call + batched AOT
bucket) — then compares the recorded findings AND the per-entrypoint
live-memory watermarks against the checked-in baseline.

Usage:

    python tools/graph_audit.py                    # ratcheted smoke run
    python tools/graph_audit.py --smoke engine     # one smoke only
    python tools/graph_audit.py --format json
    python tools/graph_audit.py --write-baseline

Exit codes (stable contract, asserted by tests/test_graphcheck.py):

    0   clean — no findings / watermark regressions beyond the baseline
    1   new findings (or a watermark regression past the slack)
    2   usage error (bad smoke name, unreadable baseline, bad args)

The baseline (default: <repo>/.graphcheck_baseline.json) freezes
findings by ``site::rule`` count — line-number-free, like the tracelint
and tpu-san ratchets — plus an estimated live-memory watermark per
audited site (GC006 fails the run when a site regresses past
``PADDLE_TPU_GRAPHCHECK_MEM_SLACK``, default 25%). The framework is
expected to hold the baseline at ZERO findings.

Like tpu_san (and unlike tpu_lint) this tool imports and executes the
framework: the auditor reads jaxprs and compiled HLO, which only exist
in a live process. JAX_PLATFORMS=cpu is pinned, and the host platform
is forced to 8 virtual devices so placement-sensitive rules (GC001/
GC002) audit real multi-device programs on accelerator-less CI boxes.
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
# 8 virtual devices BEFORE jax imports: the audited engine programs then
# carry a real dp mesh (same trick as tests/conftest.py — appending is
# idempotent when the flag is already forced)
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

DEFAULT_BASELINE = os.path.join(REPO, ".graphcheck_baseline.json")
SMOKES = ("engine", "decode", "export", "longctx")

USAGE_ERROR, NEW_FINDINGS, CLEAN = 2, 1, 0


def _smoke_engine():
    """Training entrypoints: a dense model and an NHWC conv stack through
    train_batch / train_batches / eval_batch — audits engine.step,
    engine.multi and engine.eval (donation aliasing, collectives vs the
    dp specs, conv-region layout, watermark)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed import topology as topo_mod
    from paddle_tpu.distributed.engine import parallelize

    paddle.seed(0)
    rng = np.random.RandomState(0)

    # explicit dp mesh: the audited specs (and so the baseline) must not
    # depend on whatever hybrid topology an earlier in-process caller
    # (the tier-1 test imports this module) happened to leave behind
    mesh = topo_mod.build_mesh(dp=-1)
    model = nn.Linear(8, 4)
    opt = optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
    eng = parallelize(model, opt, mesh=mesh,
                      loss_fn=lambda m, x, y: ((m(x) - y) ** 2).mean())
    x = paddle.to_tensor(rng.rand(8, 8).astype(np.float32))
    y = paddle.to_tensor(rng.rand(8, 4).astype(np.float32))
    eng.train_batch(x, y)
    eng.train_batches([(x, y)] * 3)
    eng.eval_batch(x, y)

    # NHWC conv stack: the layout rule (GC003) audits a REAL conv train
    # step — clean because nothing transposes inside the stack
    conv = nn.Sequential(
        nn.Conv2D(3, 4, 3, padding=1, data_format="NHWC"),
        nn.ReLU(),
        nn.Flatten(),
        nn.Linear(4 * 8 * 8, 4),
    )
    copt = optimizer.SGD(learning_rate=0.1, parameters=conv.parameters())
    ceng = parallelize(conv, copt, mesh=mesh,
                       loss_fn=lambda m, x, y: ((m(x) - y) ** 2).mean())
    cx = paddle.to_tensor(rng.rand(8, 8, 8, 3).astype(np.float32))
    cy = paddle.to_tensor(rng.rand(8, 4).astype(np.float32))
    ceng.train_batch(cx, cy)
    ceng.eval_batch(cx, cy)


def _smoke_decode():
    """Decode entrypoints: warmup compiles EVERY decode/prefill bucket
    executable (each one audited at its aot.decode-* site), then one
    streamed generation proves the audited programs run."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference import DecodeEngine
    from paddle_tpu.models import gpt

    paddle.seed(7)
    m = gpt("gpt_tiny", vocab_size=97, hidden_size=48, num_heads=4,
            num_kv_heads=2, num_layers=2, rope=True, swiglu=True,
            rms_norm=True, max_position_embeddings=64,
            tie_word_embeddings=False)
    m.eval()
    eng = DecodeEngine(m, max_length=32, block_size=8,
                       decode_buckets=(1, 2), prefill_buckets=(8,),
                       default_timeout=120.0)
    try:
        eng.warmup()
        list(eng.generate(np.array([3, 5, 7], np.int32), max_new_tokens=4))
    finally:
        eng.shutdown(drain_timeout=30.0)


def _smoke_longctx():
    """Context-parallel ring attention entrypoints: a GPT train step on
    the MeshConfig(cp=4) mesh (ring KV rotation inside the audited
    engine.step — the `cp`-declared batch spec legitimizes the
    ppermutes; a ring that accidentally all-gathered full KV on a
    replicated placement would fire GC001) and the decode engine's
    cp-sharded chunked prefill executables."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.distributed.engine import parallelize
    from paddle_tpu.inference import DecodeEngine
    from paddle_tpu.models import gpt
    from paddle_tpu.sharding import MeshConfig

    paddle.seed(0)
    model = gpt("gpt_tiny", num_layers=2, num_heads=4, hidden_size=64,
                dropout=0.0)
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    eng = parallelize(model, opt, mesh=MeshConfig(cp=4).build(),
                      context_parallel="ring")
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 256, (4, 32)).astype("int32"))
    eng.train_batch(ids)
    eng.eval_batch(ids)

    paddle.seed(7)
    m = gpt("gpt_tiny", vocab_size=97, hidden_size=48, num_heads=4,
            num_kv_heads=2, num_layers=2, rope=True, swiglu=True,
            rms_norm=True, max_position_embeddings=64,
            tie_word_embeddings=False)
    m.eval()
    deng = DecodeEngine(m, max_length=48, block_size=8,
                        decode_buckets=(1,), prefill_buckets=(8, 16, 24),
                        prefill_chunk=8, default_timeout=120.0,
                        mesh=MeshConfig(cp=4).build())
    try:
        deng.warmup()
        list(deng.generate(
            np.random.RandomState(1).randint(1, 96, 19).astype(np.int32),
            max_new_tokens=4))
    finally:
        deng.shutdown(drain_timeout=30.0)


def _smoke_export(workdir):
    """Export entrypoints: jit.save → load → direct call (aot.layer_call)
    and a batched AOT bucket executable (aot.batched)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn

    paddle.seed(0)
    m = nn.Linear(6, 3)
    m.eval()
    x = paddle.to_tensor(np.random.RandomState(0).rand(2, 6)
                         .astype(np.float32))
    path = os.path.join(workdir, "graph_audit_model")
    paddle.jit.save(m, path, input_spec=[x])
    loaded = paddle.jit.load(path)
    loaded(x)
    fn = loaded.batched_call(2)
    fn(np.stack([x.numpy(), x.numpy()]))


def run_smokes(names, workdir):
    """Run the selected workloads with the auditor live; returns the
    (counts, watermarks, report) triple recorded across them."""
    from paddle_tpu.analysis import graphcheck

    graphcheck.enable()
    graphcheck.reset()
    for name in names:
        if name == "export":
            _smoke_export(workdir)
        else:
            {"engine": _smoke_engine, "decode": _smoke_decode,
             "longctx": _smoke_longctx}[name]()
    return (graphcheck.counts_by_key(), graphcheck.watermarks(),
            graphcheck.report())


def _render_text(counts, fresh, wm_fresh, report, baseline_used, out):
    by_key = {}
    for f in report["findings"]:
        by_key.setdefault(f"{f['site']}::{f['rule']}", []).append(f)
    for key, (n, base) in fresh.items():
        print(f"{key}: {n} finding(s) (baseline {base})", file=out)
        for f in by_key.get(key, ())[:3]:
            print(f"  {f['message']}", file=out)
    for site, (cur, base) in wm_fresh.items():
        print(f"{site}::GC006: estimated watermark {cur} bytes regressed "
              f"past baseline {base}", file=out)
    kept = sum(counts.values()) - sum(n for n, _ in fresh.values())
    tail = f" ({kept} baselined finding(s) suppressed)" \
        if baseline_used and kept else ""
    c = report["counters"]
    print(f"graph_audit: {sum(n for n, _ in fresh.values())} new "
          f"finding(s), {len(wm_fresh)} watermark regression(s), "
          f"{sum(counts.values())} total{tail} "
          f"[audits={c['audits']} collectives={c['collectives_seen']} "
          f"sites={len(report['watermarks'])}]", file=out)


def _render_json(counts, fresh, wm_fresh, report, baseline_used, out):
    payload = {
        "tool": "graph_audit",
        "new": {k: {"count": n, "baseline": b}
                for k, (n, b) in fresh.items()},
        "new_count": sum(n for n, _ in fresh.values()),
        "watermark_regressions": {
            s: {"bytes": c, "baseline": b}
            for s, (c, b) in wm_fresh.items()},
        "total_count": sum(counts.values()),
        "counts": counts,
        "watermarks": report["watermarks"],
        "counters": report["counters"],
        "baseline_used": bool(baseline_used),
        "findings": report["findings"],
    }
    json.dump(payload, out, indent=2, sort_keys=True)
    out.write("\n")


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="graph_audit", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--smoke", default=",".join(SMOKES),
                    help=f"comma-separated workloads to run "
                         f"(default: {','.join(SMOKES)})")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help=f"baseline file (default {DEFAULT_BASELINE})")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline: report every finding")
    ap.add_argument("--write-baseline", action="store_true",
                    help="regenerate the baseline (counts + watermarks, "
                         "sorted keys) from this run and exit 0")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    try:
        args = ap.parse_args(argv)
    except SystemExit as e:
        raise SystemExit(USAGE_ERROR if e.code else 0)

    smokes = [s.strip() for s in args.smoke.split(",") if s.strip()]
    bad = [s for s in smokes if s not in SMOKES]
    if bad or not smokes:
        print(f"graph_audit: unknown smoke(s) {bad or args.smoke!r} "
              f"(choose from {', '.join(SMOKES)})", file=sys.stderr)
        return USAGE_ERROR

    baseline_counts, baseline_wm, baseline_used = {}, {}, False
    if not args.no_baseline and not args.write_baseline:
        if os.path.exists(args.baseline):
            from paddle_tpu.analysis import graphcheck
            try:
                data = graphcheck.load_baseline(args.baseline)
            except (ValueError, OSError, json.JSONDecodeError) as e:
                print(f"graph_audit: unreadable baseline "
                      f"{args.baseline}: {e}", file=sys.stderr)
                return USAGE_ERROR
            baseline_counts = data["counts"]
            baseline_wm = data.get("watermarks", {})
            baseline_used = True
        elif args.baseline != DEFAULT_BASELINE:
            print(f"graph_audit: baseline not found: {args.baseline}",
                  file=sys.stderr)
            return USAGE_ERROR

    # hermetic AOT cache (same contract as tpu_san): every smoke then
    # COMPILES — disk hits would skip the audit hooks
    from paddle_tpu.jit.aot import hermetic_cache

    with hermetic_cache(prefix="graph-audit-") as tmp:
        counts, wm, report = run_smokes(smokes, tmp)

    from paddle_tpu.analysis import graphcheck

    if args.write_baseline:
        graphcheck.write_baseline(args.baseline, counts, wm)
        print(f"graph_audit: wrote {sum(counts.values())} finding(s) "
              f"across {len(counts)} key(s) + {len(wm)} watermark(s) to "
              f"{args.baseline}", file=sys.stderr)
        return CLEAN

    fresh = graphcheck.new_counts(counts, baseline_counts)
    # watermark ratchet only applies against a real baseline: an ad-hoc
    # --no-baseline run reports findings, not regressions
    wm_fresh = graphcheck.new_watermarks(wm, baseline_wm) \
        if baseline_used else {}
    render = _render_json if args.format == "json" else _render_text
    render(counts, fresh, wm_fresh, report, baseline_used, sys.stdout)
    return NEW_FINDINGS if (fresh or wm_fresh) else CLEAN


if __name__ == "__main__":
    sys.exit(main())
