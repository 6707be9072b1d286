"""Readings that the limits of the Ling-flash serving cell are set from, on
the chip at the cell's own size (`calibrate_lin.py`'s twin for the
`serve_kda` driver; PERF.md section 2 holds the readings).

    python benchmarks/calibrate_kda.py --workload <cell> --seeds 1,2,3 \
        [--controls float8] [--seconds 20] [--fault mean_decay|no_bias|no_groups]

One engine serves every seed's weights and traffic for `--seconds` each; once
it is shut down and freed, each seed's finished requests go through the
driver's own comparison under the committed limits (the lower reading), and
each of `--controls` (float8: the upper reading, it has to come out not
correct; mean_decay, no_bias, no_groups: the reference's planted faults) is
put in the program's place from the same contexts. `--fault` plants a fault
in the PROGRAM instead, the two things a tolerance could hide:
`mean_decay` hands the delta rule a head's decay as its mean over the key
channels (the scalar rule under the new name); `no_bias` / `no_groups`
leave the selection bias / the group limit out of the router. One JSON line
a seed, also appended to `chiprun_out/calibrate/<cell>.jsonl`; the last line
gives the readings of every side. Benchmark runs never call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, weights_ling  # noqa: E402
from benchmarks.traffic import generate  # noqa: E402

FAULTS = ("mean_decay", "no_bias", "no_groups")


def plant_fault(fault):
    """Break the program as `fault` says, where its traced functions look
    their helpers up. What this process traced of the sound layers before
    is dropped, so that no earlier trace serves the faulty program (a
    caller that goes on to run the sound program drops the faulty traces
    likewise: `jax.clear_caches()`)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import linear_attention as la
    from paddle_tpu.models import moe

    if fault == "mean_decay":
        sound = la._split

        def split(*args, **kw):
            q, k, v, g, beta = sound(*args, **kw)
            if g.ndim == k.ndim:
                g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
            return q, k, v, g, beta

        la._split = split
    else:
        sound = moe.route
        drop = {"no_bias": {"bias": None},
                "no_groups": {"n_group": 0, "topk_group": 0}}[fault]

        def route(*args, **kw):
            return sound(*args, **{**kw, **drop})

        moe.route = route
    jax.clear_caches()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="float8")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fault", choices=("",) + FAULTS, default="")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    cell = harness.resolve_cell(args.workload, rehearsal)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = [c for c in args.controls.split(",") if c]

    from paddle_tpu.jit.aot import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    kda = harness.load_module(
        os.path.join(harness.HERE, "drivers", "serve_kda.py"),
        "driver_serve_kda")
    model, mix = cell["model"], cell["mix"]
    tools = {"counter": harness.CompileCounter(), "spans": harness.Spans(),
             "tracer": harness.TraceWindow(False, 0, 0),
             "window_opened": lambda t: None}
    dtype = mix["weights_dtype"]
    served_by = "fault_" + args.fault if args.fault else "program"
    if args.fault:
        # a faulty program is a program of its own: its executables may
        # neither be served from the sound program's cache nor be left there
        import shutil

        from paddle_tpu.jit.aot import CompileCache

        plant_fault(args.fault)
        fresh = os.path.join(ROOT, ".bench_tmp", "calibrate_fault_cache")
        shutil.rmtree(fresh, ignore_errors=True)
        mix["engine"] = {**mix["engine"],
                         "compile_cache": CompileCache(fresh)}

    def note(what):
        print(f"[calibrate_kda] {time.perf_counter() - t_start:7.1f} s  "
              f"{what}", flush=True)

    eng, pool = kda.lin.build_server(cell, weights_ling.make(
        model, seeds[0], dtype))
    eng.warmup()
    kda.serve.warm_traffic(cell, eng, pool, seeds[0], tools["spans"])
    note("engine warm")
    served = {}
    for seed in seeds:
        # the running engine reads its parameters' values at every
        # dispatch, so one engine serves every seed's weights (the old
        # values go first: two sets do not fit the chip), once the last
        # window's cancelled sequences have left it
        while sum(eng.stats()[k] for k in ("active", "prefilling",
                                            "waiting")):
            time.sleep(0.05)
        for _, p in eng.model.named_parameters():
            p._value.delete()
        w = weights_ling.make(model, seed, dtype)
        for n, p in eng.model.named_parameters():
            p._value = w[n]
        del w
        win = kda.serve.window(
            cell, eng, pool,
            generate.requests(mix, model["vocab_size"], seed),
            args.seconds, tools, seed)
        served[seed] = ([r for r in win["records"]
                         if r["status"] == "completed"],
                        len(win["records"]), win["builds"])
        note(f"seed {seed} served: {len(served[seed][0])} finished")
    kda.lin.free_server(eng, pool)
    del eng, pool
    note("engine freed; the reference follows")

    def side(seed, w, control):
        finished, _, builds = served[seed]
        checks, _ = kda.check(cell, w, seed, finished, builds, control)
        out = {r["name"]: r["value"] for r in checks.rows}
        out.update(correct=checks.correct,
                   failed=[r["name"] for r in checks.rows if not r["ok"]])
        return out

    out_dir = os.path.join(ROOT, "chiprun_out", "calibrate")
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    with open(os.devnull if rehearsal else os.path.join(
            out_dir, cell["name"] + ".jsonl"), "a") as f:
        for seed in seeds:
            w = weights_ling.make(model, seed, dtype)
            row = {"seed": seed, "sent": served[seed][1],
                   "finished": len(served[seed][0]),
                   served_by: side(seed, w, None)}
            for control in controls:
                row["control_" + control] = side(seed, w, control)
            del w
            rows.append(row)
            f.write(json.dumps(row) + "\n")
            print(json.dumps(row), flush=True)
    sides = (served_by,) + tuple("control_" + c for c in controls)
    print(json.dumps({
        "cell": cell["name"], "limits": cell["limits"], "seeds": len(rows),
        "readings": {name: {
            s: [min(r[s][name] for r in rows),
                max(r[s][name] for r in rows)] for s in sides}
            for name in ("token_gap", "token_gap_mean")},
        "verdicts": {s: f"{sum(not r[s]['correct'] for r in rows)} of "
                        f"{len(rows)} seeds not correct" for s in sides}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
