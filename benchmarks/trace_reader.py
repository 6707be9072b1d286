"""From a profiler trace (`.xplane.pb`) to busy and idle time, per-operation
time and the host span that covers each idle gap.

`load` needs only jax (`jax.profiler.ProfileData`). Everything below it works
on plain lists of `(name, start_ns, duration_ns)` and is tested on hand-made
intervals. A trace is reduced to

    {"devices": [{"name", "ops": [...], "modules": [...]}, ...],
     "host_spans": [(name, start_ns, duration_ns), ...]}

Device planes are `/device:TPU:<n>`; their line "XLA Ops" holds one event an
operation (nested where an operation, such as a loop, contains others) and
"XLA Modules" one event a launched program. The trace names an operation by
its whole HLO text; it is cut here to `<%name> <opcode>`, as in
`%fusion.12 fusion` or `%jvp_jit__unknown___.3 custom-call` (a Mosaic
kernel), and a module to the name before its fingerprint (`jit_multi`).
Host spans are the benchmark's own `jax.profiler.TraceAnnotation`s (names
starting with `bench::`), which the profiler records on the same clock.
"""
from __future__ import annotations

import glob
import gzip
import os
import re

SPAN_PREFIX = "bench::"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def op_name(text: str) -> str:
    """`%name opcode` from an operation's HLO text."""
    name, _, rhs = text.partition(" = ")
    found = _OPCODE.search(rhs)
    return f"{name} {found.group(1)}" if found else name


def module_name(text: str) -> str:
    return text.split("(", 1)[0]


def load(path: str) -> dict:
    """Reduce a trace file (`.xplane.pb`, or the same gzipped)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, host_spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    cut = op_name if key == "ops" else module_name
                    dev[key] = [(cut(e.name), int(e.start_ns),
                                 int(e.duration_ns)) for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans += [
                    (e.name[len(SPAN_PREFIX):], int(e.start_ns),
                     int(e.duration_ns)) for e in line.events
                    if e.name.startswith(SPAN_PREFIX)]
    devices.sort(key=lambda d: d["name"])
    return {"devices": devices, "host_spans": sorted(
        host_spans, key=lambda s: s[1])}


def merge(events) -> list:
    """Disjoint sorted [start, end) intervals covering the events."""
    out = []
    for start, end in sorted((s, s + d) for _, s, d in events if d > 0):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def covered(merged, lo, hi) -> int:
    """Nanoseconds of [lo, hi) that the merged intervals cover."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)


def gaps(merged, lo, hi) -> list:
    """The idle [start, end) stretches of [lo, hi)."""
    out, at = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return out


def self_times(events) -> list:
    """(name, self_ns) per event of one line: its duration less what the
    events nested inside it cover, so that a loop does not count its body
    twice."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    selfs = [e[2] for e in events]
    stack = []
    for i in order:
        _, s, d = events[i]
        while stack and s >= events[stack[-1]][1] + events[stack[-1]][2]:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= d
        stack.append(i)
    return [(events[i][0], max(0, selfs[i])) for i in range(len(events))]


def totals(events, self_time=True) -> dict:
    """name -> summed nanoseconds (self time unless told otherwise)."""
    pairs = self_times(events) if self_time else \
        [(n, d) for n, _, d in events]
    out = {}
    for n, d in pairs:
        out[n] = out.get(n, 0) + d
    return out


_SUFFIX = re.compile(r"\.\d+(?= |$)")


def family(name: str) -> str:
    """An operation's name without the number the compiler gave it:
    `%fusion.12 fusion` and `%fusion.7 fusion` are both `fusion`,
    `%jvp_jit_f.3 custom-call` is `jvp_jit_f custom-call`. The numbers
    change with every compilation; the families do not."""
    stem, _, opcode = _SUFFIX.sub("", name.lstrip("%")).partition(" ")
    return stem if opcode in ("", stem) else f"{stem} {opcode}"


def within(events, lo, hi) -> list:
    """Events that lie wholly inside [lo, hi)."""
    return [e for e in events if e[1] >= lo and e[1] + e[2] <= hi]


def attribute(gap, host_spans, ignore=(), default="engine_internal") -> str:
    """The host span that covers most of the gap, or `default` where none
    of the benchmark's spans (other than `ignore`) overlaps it."""
    lo, hi = gap
    best, best_ns = default, 0
    for name, s, d in host_spans:
        if name in ignore:
            continue
        ov = min(hi, s + d) - max(lo, s)
        if ov > best_ns:
            best, best_ns = name, ov
    return best


def reduce_window(trace: dict, lo=None, hi=None, ignore=()) -> dict:
    """Busy and idle of every device over [lo, hi) (default: from the first
    operation's start to the last one's end), averaged over the devices,
    with the launched programs and the families of operations that took
    most time (self time, so a loop does not count its body) and the idle
    gaps by the host span that covers them."""
    devs = [d for d in trace["devices"] if d["ops"]]
    if not devs:
        return {}
    lo = min(e[1] for d in devs for e in d["ops"]) if lo is None else lo
    hi = max(e[1] + e[2] for d in devs for e in d["ops"]) if hi is None \
        else hi
    busy, op_ns, mod_ns, gap_ns = [], {}, {}, {}
    for d in devs:
        merged = merge(d["ops"])
        busy.append(covered(merged, lo, hi))
        for n, ns in totals(within(d["ops"], lo, hi)).items():
            n = family(n)
            op_ns[n] = op_ns.get(n, 0) + ns / len(devs)
        for n, ns in totals(within(d["modules"], lo, hi),
                            self_time=False).items():
            mod_ns["program " + n] = mod_ns.get("program " + n, 0) \
                + ns / len(devs)
        for g in gaps(merged, lo, hi):
            n = attribute(g, trace["host_spans"], ignore)
            gap_ns[n] = gap_ns.get(n, 0) + (g[1] - g[0]) / len(devs)
    top = lambda d, k: [[n, ns / 1e9] for n, ns in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:k]]
    programs = top(mod_ns, 3)
    return {"lo": lo, "hi": hi, "window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy) / len(busy) / 1e9,
            "device_ops": programs + top(op_ns, 10 - len(programs)),
            "idle_gaps": top(gap_ns, 10)}


def main_module_window(trace: dict) -> dict:
    """The launched program that took most device time, and the stretch from
    the start of its first launch to the end of its last: whole launches
    only, so that work and time cover the same steps."""
    dev = next((d for d in trace["devices"] if d["modules"]), None)
    if dev is None:
        return {}
    by_name = totals(dev["modules"], self_time=False)
    name = max(by_name, key=by_name.get)
    runs = sorted((e for e in dev["modules"] if e[0] == name),
                  key=lambda e: e[1])
    # the trace cuts the launch that was running when it started, and may
    # cut the one running when it stopped: leave the outermost two out
    if len(runs) >= 4:
        runs = runs[1:-1]
    return {"module": name, "launches": len(runs), "lo": runs[0][1],
            "hi": runs[-1][1] + runs[-1][2]}
