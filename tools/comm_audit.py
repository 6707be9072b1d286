#!/usr/bin/env python
"""comm_audit — CLI for the paddle_tpu collective-schedule auditor
(commcheck).

``tools/graph_audit.py`` ratchets what XLA compiled *per program*; this
tool ratchets what the pod must *agree on*: the ordered collective
schedule — kind, mesh axes, operand shape/dtype, replica groups, reduce
op — of every framework entrypoint. It runs the framework's own
entrypoints with ``paddle_tpu.analysis.commcheck`` enabled — the
training engine on a dense dp mesh, an fsdp-sharded GPT step (in-graph
param all-gathers), a context-parallel ring-attention step (explicit
shard_map ppermutes) and the decode engine's bucket executables — then
compares every recorded ``site::program`` schedule against the
checked-in baseline. A PR that silently adds an all-gather or reorders
a reduce-scatter fails with the FIRST divergent collective named, until
the baseline is deliberately re-ratcheted.

Usage:

    python tools/comm_audit.py                     # ratcheted smoke run
    python tools/comm_audit.py --smoke engine,cp   # selected smokes
    python tools/comm_audit.py --changed-only      # only smokes whose
                                                   # modules changed vs
                                                   # the merge-base
    python tools/comm_audit.py --format json
    python tools/comm_audit.py --write-baseline

Exit codes (stable contract, asserted by tests/test_commcheck.py):

    0   clean — every recorded schedule matches the baseline
    1   schedule divergence / unbaselined program / extraction error
    2   usage error (bad smoke name, unreadable baseline, bad args)

The baseline (default: <repo>/.commcheck_baseline.json) freezes the
FULL canonical schedule per ``site::program`` — not just a count — so a
regression names the exact divergent collective tuple and its position.

Like graph_audit this tool imports and executes the framework: the
schedules only exist in a live process. JAX_PLATFORMS=cpu is pinned,
and the host platform is forced to 8 virtual devices so the audited
programs carry real multi-device collectives on accelerator-less CI
boxes.
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
# 8 virtual devices BEFORE jax imports (same trick as graph_audit /
# tests/conftest.py): the audited schedules must contain real
# multi-device collectives, not single-device no-ops
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

DEFAULT_BASELINE = os.path.join(REPO, ".commcheck_baseline.json")
SMOKES = ("engine", "fsdp", "cp", "decode")

USAGE_ERROR, NEW_FINDINGS, CLEAN = 2, 1, 0

#: module prefixes (repo-relative) whose changes implicate each smoke —
#: the --changed-only selector; a change under _ALWAYS reruns everything
_SMOKE_PATHS = {
    "engine": ("paddle_tpu/distributed/", "paddle_tpu/nn/",
               "paddle_tpu/optimizer/", "paddle_tpu/core/"),
    "fsdp": ("paddle_tpu/distributed/", "paddle_tpu/sharding/",
             "paddle_tpu/models/", "paddle_tpu/nn/"),
    "cp": ("paddle_tpu/distributed/", "paddle_tpu/sharding/",
           "paddle_tpu/models/", "paddle_tpu/nn/"),
    "decode": ("paddle_tpu/inference/", "paddle_tpu/jit/",
               "paddle_tpu/models/", "paddle_tpu/sharding/"),
}
_ALWAYS_PATHS = ("paddle_tpu/analysis/", "tools/")


def _smoke_engine():
    """Dense training entrypoints on an explicit dp mesh: train_batch /
    train_batches / eval_batch record the engine.step, engine.multi and
    engine.eval schedules (the dp gradient all-reduces)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed import topology as topo_mod
    from paddle_tpu.distributed.engine import parallelize

    paddle.seed(0)
    rng = np.random.RandomState(0)
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


def _smoke_fsdp():
    """fsdp-sharded GPT train/eval step: the in-graph param all-gathers
    and grad reduce-scatters GSPMD derives from the fsdp specs are the
    schedule MOST at risk from a sharding-rule change."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.distributed import topology as topo_mod
    from paddle_tpu.distributed.engine import parallelize
    from paddle_tpu.models import gpt
    from paddle_tpu.sharding import MeshConfig

    topo_mod.set_hybrid_communicate_group(None)
    paddle.seed(11)
    model = gpt("gpt_tiny", vocab_size=64, hidden_size=32, num_heads=2,
                num_layers=1, max_position_embeddings=32)
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    eng = parallelize(model, opt, mesh=MeshConfig(fsdp=8).build())
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 64, (8, 16)).astype("int32"))
    eng.train_batch(ids)
    eng.eval_batch(ids)


def _smoke_cp():
    """Context-parallel ring attention: the MeshConfig(cp=4) train step's
    EXPLICIT collectives (the shard_map ppermute ring rotating KV) plus
    whatever GSPMD adds around them — the ordered mix commcheck exists
    to freeze."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.distributed import topology as topo_mod
    from paddle_tpu.distributed.engine import parallelize
    from paddle_tpu.models import gpt
    from paddle_tpu.sharding import MeshConfig

    topo_mod.set_hybrid_communicate_group(None)
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


def _smoke_decode():
    """Decode entrypoints: warmup compiles every decode/prefill bucket
    executable (each recorded at its aot.decode-* site), then one
    generation proves the recorded programs run."""
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


_SMOKE_FNS = {"engine": _smoke_engine, "fsdp": _smoke_fsdp,
              "cp": _smoke_cp, "decode": _smoke_decode}


def run_smokes(names):
    """Run the selected workloads with the auditor live; returns the
    (schedules, errors, report) triple recorded across them."""
    from paddle_tpu.analysis import commcheck

    commcheck.enable()
    commcheck.reset()
    for name in names:
        _SMOKE_FNS[name]()
    return (commcheck.schedules(), commcheck.errors(), commcheck.report())


def select_changed_smokes(smokes):
    """The subset of `smokes` implicated by files changed vs the
    merge-base (tpu_lint's machinery); falls back to ALL smokes when git
    can't resolve — the pre-commit loop must fail safe toward auditing,
    never toward skipping."""
    from tools.tpu_lint import _changed_files

    got = _changed_files(REPO)
    if got is None:
        return list(smokes), None
    _, rels = got
    if any(rel.startswith(_ALWAYS_PATHS) for rel in rels):
        return list(smokes), rels
    keep = [s for s in smokes
            if any(rel.startswith(_SMOKE_PATHS[s]) for rel in rels)]
    return keep, rels


def _render_text(schedules, fresh, errors, report, out):
    for key, msgs in sorted(fresh.items()):
        for m in msgs:
            print(f"{key}: {m}", file=out)
    for site, msg in sorted(errors.items()):
        print(f"{site}::commcheck: {msg}", file=out)
    c = report["counters"]
    n_colls = sum(len(v["collectives"]) for v in schedules.values())
    print(f"comm_audit: {sum(len(m) for m in fresh.values())} schedule "
          f"divergence(s), {len(errors)} extraction error(s), "
          f"{len(schedules)} program(s) / {n_colls} collective(s) "
          f"recorded [programs={c['programs']} "
          f"collectives={c['collectives_seen']}]", file=out)


def _render_json(schedules, fresh, errors, report, out):
    payload = {
        "tool": "comm_audit",
        "new": {k: list(v) for k, v in fresh.items()},
        "new_count": sum(len(v) for v in fresh.values()),
        "errors": errors,
        "schedules": schedules,
        "counters": report["counters"],
    }
    json.dump(payload, out, indent=2, sort_keys=True)
    out.write("\n")


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="comm_audit", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--smoke", default=",".join(SMOKES),
                    help=f"comma-separated workloads to run "
                         f"(default: {','.join(SMOKES)})")
    ap.add_argument("--changed-only", action="store_true",
                    help="audit only smokes whose modules changed vs the "
                         "merge-base (git); no changes -> exit 0 without "
                         "running anything")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help=f"baseline file (default {DEFAULT_BASELINE})")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline: report recorded schedules "
                         "without ratcheting")
    ap.add_argument("--write-baseline", action="store_true",
                    help="regenerate the baseline (full schedules, sorted "
                         "keys) from this run and exit 0")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    try:
        args = ap.parse_args(argv)
    except SystemExit as e:
        raise SystemExit(USAGE_ERROR if e.code else 0)

    smokes = [s.strip() for s in args.smoke.split(",") if s.strip()]
    bad = [s for s in smokes if s not in SMOKES]
    if bad or not smokes:
        print(f"comm_audit: unknown smoke(s) {bad or args.smoke!r} "
              f"(choose from {', '.join(SMOKES)})", file=sys.stderr)
        return USAGE_ERROR

    if args.changed_only:
        smokes, rels = select_changed_smokes(smokes)
        if not smokes:
            print("comm_audit: no audited modules changed vs merge-base "
                  f"({0 if rels is None else len(rels)} changed file(s)) "
                  "— nothing to do", file=sys.stderr)
            return CLEAN
        print(f"comm_audit: changed-only -> {','.join(smokes)}",
              file=sys.stderr)

    baseline_schedules, baseline_used = {}, False
    if not args.no_baseline and not args.write_baseline:
        if os.path.exists(args.baseline):
            from paddle_tpu.analysis import commcheck
            try:
                data = commcheck.load_baseline(args.baseline)
            except (ValueError, OSError, json.JSONDecodeError) as e:
                print(f"comm_audit: unreadable baseline "
                      f"{args.baseline}: {e}", file=sys.stderr)
                return USAGE_ERROR
            baseline_schedules = data["schedules"]
            baseline_used = True
        elif args.baseline != DEFAULT_BASELINE:
            print(f"comm_audit: baseline not found: {args.baseline}",
                  file=sys.stderr)
            return USAGE_ERROR

    # hermetic AOT cache (same contract as graph_audit): every smoke then
    # COMPILES — disk hits would skip the record hooks
    from paddle_tpu.jit.aot import hermetic_cache

    with hermetic_cache(prefix="comm-audit-"):
        schedules, errors, report = run_smokes(smokes)

    from paddle_tpu.analysis import commcheck

    if args.write_baseline:
        commcheck.write_baseline(args.baseline, schedules)
        n_colls = sum(len(v["collectives"]) for v in schedules.values())
        print(f"comm_audit: wrote {len(schedules)} program schedule(s) "
              f"({n_colls} collective(s)) to {args.baseline}",
              file=sys.stderr)
        return CLEAN

    # extraction errors are never silently baselined: an entrypoint the
    # auditor cannot read is an entrypoint the pod cannot verify
    fresh = commcheck.new_schedules(schedules, baseline_schedules) \
        if (baseline_used or not args.no_baseline) else {}
    render = _render_json if args.format == "json" else _render_text
    render(schedules, fresh, errors, report, sys.stdout)
    return NEW_FINDINGS if (fresh or errors) else CLEAN


if __name__ == "__main__":
    sys.exit(main())
