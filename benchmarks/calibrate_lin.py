"""Readings that the limits of a recurrent-state serving cell are set from,
on the chip at the cell's own size (`calibrate.py`'s twin for the
`serve_lin` driver; PERF.md section 2 holds the readings).

    python benchmarks/calibrate_lin.py --workload <cell> --seeds 1,2,3 \
        [--control 1] [--seconds 20] [--fault carry]

One engine serves every seed's weights and traffic for `--seconds` each; once
it is shut down and freed, each seed's finished requests go through the
driver's own comparison under the committed limits (the lower reading), and
with `--control` two controls are put in the program's place from the same
contexts: the float8 reference (the upper reading: it has to come out not
correct) and the reference that rounds the recurrent state to bfloat16 at
every position. `--fault carry` plants the fault the comparison is there
for, in the program: a prompt chunk after the first starts from a zeroed
state, as if the slot had not been handed from chunk to chunk. One JSON line
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

from benchmarks import harness, weights_olmo_hybrid  # noqa: E402
from benchmarks.traffic import generate  # noqa: E402


def plant_carry_fault():
    """Every prompt chunk after a sequence's first starts from a zeroed
    slot: the state (and the convolution's window) is not carried across
    the chunk boundary."""
    from paddle_tpu.inference.decode.engine import DecodeEngine

    sound = DecodeEngine._prefill_chunk

    def faulty(self, seq):
        if seq.prefill_pos > 0:
            self._zero_slot(seq.slot)
        return sound(self, seq)

    DecodeEngine._prefill_chunk = faulty


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fault", choices=("", "carry"), default="")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    cell = harness.resolve_cell(args.workload, rehearsal)
    seeds = [int(s) for s in args.seeds.split(",")]

    from paddle_tpu.jit.aot import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    lin = harness.load_module(
        os.path.join(harness.HERE, "drivers", "serve_lin.py"),
        "driver_serve_lin")
    model, mix = cell["model"], cell["mix"]
    tools = {"counter": harness.CompileCounter(), "spans": harness.Spans(),
             "tracer": harness.TraceWindow(False, 0, 0),
             "window_opened": lambda t: None}
    dtype = mix["weights_dtype"]
    served_by = "fault_" + args.fault if args.fault else "program"
    if args.fault == "carry":
        plant_carry_fault()
    def note(what):
        print(f"[calibrate_lin] {time.perf_counter() - t_start:7.1f} s  "
              f"{what}", flush=True)

    eng, pool = lin.build_server(cell, weights_olmo_hybrid.make(
        model, seeds[0], dtype))
    eng.warmup()
    lin.serve.warm_traffic(cell, eng, pool, seeds[0], tools["spans"])
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
        w = weights_olmo_hybrid.make(model, seed, dtype)
        for n, p in eng.model.named_parameters():
            p._value = w[n]
        del w
        win = lin.serve.window(
            cell, eng, pool,
            generate.requests(mix, model["vocab_size"], seed),
            args.seconds, tools, seed)
        served[seed] = ([r for r in win["records"]
                         if r["status"] == "completed"],
                        len(win["records"]), win["builds"])
        note(f"seed {seed} served: {len(served[seed][0])} finished")
    lin.free_server(eng, pool)
    del eng, pool
    note("engine freed; the reference follows")

    def side(seed, w, control):
        finished, _, builds = served[seed]
        checks = lin.check(cell, w, seed, finished, builds, control)
        out = {r["name"]: r["value"] for r in checks.rows}
        out.update(correct=checks.correct,
                   failed=[r["name"] for r in checks.rows if not r["ok"]])
        return out

    out_dir = os.path.join(ROOT, "chiprun_out", "calibrate")
    os.makedirs(out_dir, exist_ok=True)
    controls = {"control_fp8": "float8",
                "control_state_bf16": "state_bfloat16"} \
        if args.control else {}
    rows = []
    with open(os.devnull if rehearsal else os.path.join(
            out_dir, cell["name"] + ".jsonl"), "a") as f:
        for seed in seeds:
            w = weights_olmo_hybrid.make(model, seed, dtype)
            row = {"seed": seed, "sent": served[seed][1],
                   "finished": len(served[seed][0]),
                   served_by: side(seed, w, None)}
            for name, control in controls.items():
                row[name] = side(seed, w, control)
            del w
            rows.append(row)
            f.write(json.dumps(row) + "\n")
            print(json.dumps(row), flush=True)
    sides = (served_by,) + tuple(controls)
    print(json.dumps({
        "cell": cell["name"], "limits": cell["limits"], "seeds": len(rows),
        "readings": {"token_gap": {
            s: [min(r[s]["token_gap"] for r in rows),
                max(r[s]["token_gap"] for r in rows)] for s in sides}},
        "verdicts": {s: f"{sum(not r[s]['correct'] for r in rows)} of "
                        f"{len(rows)} seeds not correct" for s in sides}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
