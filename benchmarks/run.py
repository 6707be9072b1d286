"""One process, one cell, one run.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell by its name in BENCHMARK.json, refuses a device that is
not in `peaks.json`, keeps the compile cache at `<checkout>/.jax_cache`,
builds the program's entry point, warms the cell's own shapes, measures for
`--seconds`, checks what the timed path produced against the plain
reference, and prints one JSON object as its last line.

With `JAX_PLATFORMS=cpu` set by the caller it rehearses the same control flow
at the `rehearsal` sizes of the cell's files and names `cpu` as the device:
no number of such a run is a device number.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def read_layers(cell, ctx):
    """Every per-layer metric of the cell through its own reader,
    `layers/<name>.py`; a reader that finds nothing returns None and the
    metric is left out."""
    from benchmarks import harness

    out = {}
    for m in harness.metrics_for(cell, "per_layer"):
        reader = harness.load_module(
            os.path.join(harness.HERE, "layers", m["name"] + ".py"),
            "layer_" + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None):
    args = parse(argv)
    from benchmarks import harness

    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    cell = harness.resolve_cell(args.workload, rehearsal)
    if args.seconds is None:
        args.seconds = float(cell["bench"]["run_seconds"])

    import jax

    devices = jax.devices()
    peaks_table = harness.load_json(harness.HERE, "peaks.json")
    kind = devices[0].device_kind
    if rehearsal:
        peaks = None
        print(f"REHEARSAL on {devices[0].platform}: sizes "
              f"{cell['model']}; no number below is a device number",
              file=sys.stderr)
    elif kind not in peaks_table:
        print(f"run.py: device {devices[0].platform!r} / {kind!r} is not "
              f"in peaks.json; this benchmark measures only a chip it has "
              f"the peaks of", file=sys.stderr)
        return 3
    else:
        peaks = peaks_table[kind]
    if len(devices) < cell["chips"]:
        print(f"run.py: cell {cell['name']} needs {cell['chips']} chip(s), "
              f"jax reports {len(devices)}", file=sys.stderr)
        return 3

    from paddle_tpu.jit.aot import enable_compile_cache

    cache_root = enable_compile_cache()
    # the benchmark's own small programs (weights, norms, the reference)
    # are kept too, so that a warm run compiles nothing at all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"device {devices[0].platform} / {kind} x{len(devices)}; compile "
          f"cache {cache_root}", flush=True)

    mix = cell["mix"]
    window = {}
    tools = {
        "counter": harness.CompileCounter(),
        "spans": harness.Spans(),
        "tracer": harness.TraceWindow(
            bool(args.trace), start_after_s=min(2.0, args.seconds / 4),
            seconds=min(mix.get("trace_seconds", 4), args.seconds / 2)),
        "window_opened": lambda t: window.setdefault("t_open", t),
        "phase": lambda name: print(
            f"[setup] {time.perf_counter() - _T_PROCESS:7.2f} s  {name}",
            flush=True),
    }
    tools["phase"]("imports done, cell resolved")
    driver = harness.load_module(
        os.path.join(harness.HERE, "drivers", mix["driver"] + ".py"),
        "driver_" + mix["driver"])
    res = driver.run(cell, args, tools)

    setup_s = window["t_open"] - _T_PROCESS
    values = dict(res["end_to_end"], setup_s=setup_s)
    device = res["device"]
    checks = res["checks"]
    out = {"correct": checks.correct, "attempted": res["attempted"],
           "failed": res["failed"]}
    if args.trace:
        trace = tools["tracer"].reduced()
        ctx = {"cell": cell, "model": cell["model"], "mix": mix,
               "peaks": peaks, "trace": trace, "spans": tools["spans"],
               "counters": res["counters"], "device": device,
               "tracer": tools["tracer"]}
        scope = driver.trace_scope(ctx) if trace else {}
        ctx["scope"] = scope
        print("[trace] " + json.dumps({
            k: v for k, v in scope.items()
            if k not in ("device_ops", "idle_gaps", "decode_positions",
                         "prompts_finished")}), flush=True)
        out["metrics"] = read_layers(cell, ctx)
        if scope:
            device["busy_s"] = scope["busy_s"]
            device["window_s"] = scope["window_s"]
            out["breakdown"] = {"device_ops": scope["device_ops"],
                                "idle_gaps": scope["idle_gaps"]}
        out["end_to_end_in_traced_run"] = values
    else:
        out["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in harness.metrics_for(cell, "end_to_end")}
    out["device"] = device
    out["compared"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                       for r in checks.rows}
    for r in checks.rows:
        print(f"compared {r['name']}: {r['value']:.6g} (limit "
              f"{r['limit']:.6g}) {'ok' if r['ok'] else 'NOT OK'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
