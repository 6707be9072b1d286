"""Readings that the limits of `correct` are set from, on the chip at the
cell's own size (PERF.md section 2 holds them).

    python benchmarks/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control 1] [--faults 1] [--seconds 15]

For each seed: the program's numbers against the plain reference (the lower
reading over the seeds), with `--control` the reference computed in float8
put in the program's place (the upper reading), and with `--faults` the
reference with half of the batch left out (training). Each side goes through
the harness's own comparison with the limits the cell has committed
(`limits/<cell>.json`) and its verdict is printed: the program has to come
out correct, the control and the fault not. A cell that has no limits yet is
only read. The last line gives the two readings of every number over the
seeds. Benchmark runs never call this. One JSON line a seed, also appended
to `chiprun_out/calibrate/<cell>.jsonl`.
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks import harness  # noqa: E402


def judged(cell, fill):
    """The numbers that `fill(checks)` compares, with the verdict of the
    harness's comparison under the cell's committed limits."""
    checks = harness.Checks(cell["limits"] or collections.defaultdict(
        lambda: float("inf")))
    notes = fill(checks) or {}
    out = {r["name"]: r["value"] for r in checks.rows}
    out.update(notes, correct=checks.correct if cell["limits"] else None,
               failed=[r["name"] for r in checks.rows if not r["ok"]])
    return out


def numbers(cell, train, got, ref):
    return judged(cell, lambda checks: train.compare(got, ref, checks))


def two_readings(rows, sides=("control_fp8", "fault_half_batch")):
    """Per number compared: the largest that the program read over the
    seeds (the lower reading) and the smallest that each other side read,
    with how many seeds each side failed."""
    out = {}
    for name in (k for k, v in rows[0]["program"].items()
                 if k.endswith("_gap") and isinstance(v, float)):
        out[name] = {"lower": max(r["program"][name] for r in rows)}
        for side in sides:
            if side in rows[0]:
                out[name][side + "_least"] = min(r[side][name] for r in rows)
    verdicts = {side: f"{sum(r[side]['correct'] is False for r in rows)} "
                      f"of {len(rows)} seeds not correct"
                for side in ("program",) + tuple(sides) if side in rows[0]}
    return {"seeds": len(rows), "readings": out, "verdicts": verdicts}


def calibrate_train(cell, seeds, control, faults):
    import jax

    from benchmarks import weights
    from benchmarks.traffic import generate

    train = harness.load_module(
        os.path.join(harness.HERE, "drivers", "train.py"), "driver_train")
    model, mix = cell["model"], cell["mix"]
    for seed in seeds:
        t0 = time.perf_counter()
        batches = generate.token_batches(
            {**mix, "distinct_dispatches": 1}, model["vocab_size"], seed)[0]
        eng = train.build_engine(cell, jax.devices()[:cell["chips"]],
                                 weights.make(model, seed, "float32"))
        first = eng.train_batches([(b,) for b in batches])
        got = train.first_dispatch_readings(eng, first._value, model, seed)
        del eng, first
        gc.collect()
        ref = train.reference_readings(model, mix, batches, seed)
        row = {"seed": seed, "program": numbers(cell, train, got, ref),
               "losses": got["losses"], "ref_losses": ref["losses"],
               "gnorms": got["gnorms"], "ref_gnorms": ref["gnorms"]}
        if control:
            ctl = train.reference_readings(model, mix, batches, seed,
                                           quantized=True)
            row["control_fp8"] = numbers(cell, train, ctl, ref)
        if faults:
            half = train.reference_readings(
                model, mix, batches[:, :mix["batch"] // 2], seed)
            row["fault_half_batch"] = numbers(cell, train, half, ref)
        row["seconds"] = time.perf_counter() - t0
        # every leaf's norms, for a look at numbers other than the worst leaf
        row["leaves"] = {
            side: {k: r[k] for k in ("moment1", "delta")}
            for side, r in (("program", got), ("reference", ref))}
        if control:
            row["leaves"]["control_fp8"] = {k: ctl[k]
                                            for k in ("moment1", "delta")}
        yield row


def calibrate_serve(cell, seeds, control, seconds):
    from benchmarks import weights
    from benchmarks.traffic import generate

    serve = harness.load_module(
        os.path.join(harness.HERE, "drivers", "serve.py"), "driver_serve")
    model, mix = cell["model"], cell["mix"]
    tools = {"counter": harness.CompileCounter(), "spans": harness.Spans(),
             "tracer": harness.TraceWindow(False, 0, 0),
             "window_opened": lambda t: None}
    dtype = mix["weights_dtype"]
    eng, pool = serve.build_server(cell, weights.make(model, seeds[0], dtype))
    eng.warmup()
    serve.warm_traffic(cell, eng, pool, seeds[0], tools["spans"])
    samples = {}
    for seed in seeds:
        # the running engine reads its parameters' values at every dispatch,
        # so one engine serves every seed's weights; the prefix cache is
        # keyed by token ids, which differ from seed to seed
        w = weights.make(model, seed, dtype)
        for n, p in eng.model.named_parameters():
            p._value = w[n]
        del w
        reqs = generate.requests(mix, model["vocab_size"], seed)
        win = serve.window(cell, eng, pool, reqs, seconds, tools)
        done = [r for r in win["records"] if r["status"] == "completed"]
        samples[seed] = (serve.pick_sample(done, mix["check_requests"], seed),
                         len(win["records"]), len(done), win["builds"])
    pool.shutdown()
    eng.shutdown()
    del eng, pool
    gc.collect()
    pad = mix["engine"]["max_length"]
    for seed in seeds:
        sample, sent, done, builds = samples[seed]
        def side(quantized):
            g = serve.token_gaps(model, seed, dtype, sample, pad,
                                 quantized=quantized)
            out = judged(cell, lambda checks: checks.add(
                "token_gap", float(g.max())))
            out.update(not_first=int((g > 0).sum()), tokens=len(g),
                       p99=float(np.percentile(g, 99)))
            return out

        row = {"seed": seed, "sent": sent, "finished": done,
               "builds": builds, "requests": len(sample),
               "program": side(False)}
        if control:
            row["control_fp8"] = side(True)
        yield row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=1)
    ap.add_argument("--faults", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    cell = harness.resolve_cell(args.workload, rehearsal)
    seeds = [int(s) for s in args.seeds.split(",")]

    from paddle_tpu.jit.aot import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    rows = calibrate_train(cell, seeds, args.control, args.faults) \
        if cell["mix"]["driver"] == "train" \
        else calibrate_serve(cell, seeds, args.control, args.seconds)
    # a rehearsal's numbers are no device numbers: they are kept nowhere
    out_dir = os.path.join(ROOT, "chiprun_out", "calibrate")
    os.makedirs(out_dir, exist_ok=True)
    kept = []
    with open(os.devnull if rehearsal else os.path.join(
            out_dir, cell["name"] + ".jsonl"), "a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
            row.pop("leaves", None)
            kept.append(row)
            print(json.dumps(row), flush=True)
    print(json.dumps({"cell": cell["name"], "limits": cell["limits"],
                      **two_readings(kept)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
