"""Readings that the limits of a block-diffusion serving cell are set from,
on the chip at the cell's own size (`calibrate.py`'s twin for the `serve_bd`
driver; PERF.md section 2 holds the readings).

    python benchmarks/calibrate_bd.py --workload <cell> --seeds 1,2,3 \
        [--control 1] [--seconds 20] [--fault pick]

One engine serves every seed's weights and traffic for `--seconds` each; once
it is shut down and freed, each seed's finished requests go through the
driver's own comparison under the committed limits (the lower reading), and
with `--control` the float8 reference is put in the program's place (the
upper reading: it has to come out not correct). `--fault pick` plants the
fault that `pick_gap` is there for, in the program's place: every denoising
pass fixes its LEAST confident positions (each token still its position's
own arg-max, so nothing else tells). One JSON line a seed, also
appended to `chiprun_out/calibrate/<cell>.jsonl`; the last line gives both
readings of every number. Benchmark runs never call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, weights_sdar  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fault", choices=("", "pick"), default="")
    args = ap.parse_args(argv)
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    cell = harness.resolve_cell(args.workload, rehearsal)
    seeds = [int(s) for s in args.seeds.split(",")]

    from paddle_tpu.jit.aot import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    bd = harness.load_module(
        os.path.join(harness.HERE, "drivers", "serve_bd.py"),
        "driver_serve_bd")
    model, mix = cell["model"], cell["mix"]
    tools = {"counter": harness.CompileCounter(), "spans": harness.Spans(),
             "tracer": harness.TraceWindow(False, 0, 0),
             "window_opened": lambda t: None}
    dtype = mix["weights_dtype"]
    served_by = "fault_" + args.fault if args.fault else "program"
    if args.fault == "pick":
        from paddle_tpu.inference.decode.engine import DecodeEngine

        sound = DecodeEngine._bd_advance
        DecodeEngine._bd_advance = lambda self, seq, committed, best, conf: \
            sound(self, seq, committed, best, -conf)
    eng, pool = bd.build_server(cell, weights_sdar.make(model, seeds[0],
                                                        dtype))
    eng.warmup()
    bd.serve.warm_traffic(cell, eng, pool, seeds[0], tools["spans"])
    served = {}
    for seed in seeds:
        # the running engine reads its parameters' values at every
        # dispatch, so one engine serves every seed's weights; prompts
        # differ from seed to seed, so the prefix cache shares nothing
        # (the old values go first: two sets do not fit the chip)
        for _, p in eng.model.named_parameters():
            p._value.delete()
        w = weights_sdar.make(model, seed, dtype)
        for n, p in eng.model.named_parameters():
            p._value = w[n]
        del w
        win = bd.serve.window(cell, eng, pool,
                              bd.requests(mix, model["vocab_size"], seed),
                              args.seconds, tools)
        served[seed] = ([r for r in win["records"]
                         if r["status"] == "completed"],
                        len(win["records"]), win["builds"])
    bd.free_server(eng, pool)
    del eng, pool

    def side(seed, quantized):
        finished, _, builds = served[seed]
        checks = bd.check(cell, seed, finished, builds, quantized)
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
            row = {"seed": seed, "sent": served[seed][1],
                   "finished": len(served[seed][0]),
                   served_by: side(seed, False)}
            if args.control:
                row["control_fp8"] = side(seed, True)
            rows.append(row)
            f.write(json.dumps(row) + "\n")
            print(json.dumps(row), flush=True)
    sides = (served_by,) + (("control_fp8",) if args.control else ())
    print(json.dumps({
        "cell": cell["name"], "limits": cell["limits"], "seeds": len(rows),
        "readings": {name: {s: [min(r[s][name] for r in rows),
                                max(r[s][name] for r in rows)]
                            for s in sides}
                     for name in ("token_gap", "pick_gap")},
        "verdicts": {s: f"{sum(not r[s]['correct'] for r in rows)} of "
                        f"{len(rows)} seeds not correct" for s in sides}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
