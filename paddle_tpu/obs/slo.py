"""paddle_tpu.obs.slo — declared service-level objectives + their gate.

An objective is a named number with a direction; a baseline mapping (or
a file written by `write_baseline`) holds the bound each one is held
to; `evaluate` says which are breached. `ServingRouter`'s autoscaler
evaluates its windowed p99s against the configured ceilings through
this module. Where a baseline file is checked in, an intentional change
re-writes it (`write_baseline`) in the same PR that explains it.

An `Objective` names ONE number and its direction:

* ``kind="max"`` — the measured value must stay **at or under** the
  baseline bound (latency p99, queue-depth ceiling);
* ``kind="min"`` — the value must stay **at or over** it (throughput,
  steps/sec floor).

Bounds are written from a measurement with per-objective `slack` (a
max-kind bound is ``value * slack``, a min-kind bound ``value / slack``)
so machine-to-machine timing variance doesn't trip the gate while an
order-of-magnitude regression still does. A declared objective that is
missing from the measured values — or from the baseline — is a breach
(silent rot is the failure mode ratchets exist to kill).
"""
from __future__ import annotations

import json
import os

__all__ = ["Objective", "evaluate", "load_baseline", "write_baseline",
           "format_report"]


class Objective:
    """One named SLO: a measured value, a direction, and ratchet slack."""

    KINDS = ("max", "min")

    def __init__(self, name, kind, description="", unit="", slack=2.0):
        if kind not in self.KINDS:
            raise ValueError(f"kind must be one of {self.KINDS}, "
                             f"got {kind!r}")
        if slack < 1.0:
            raise ValueError(f"slack must be >= 1.0, got {slack}")
        self.name = str(name)
        self.kind = kind
        self.description = str(description)
        self.unit = str(unit)
        self.slack = float(slack)

    def bound_from(self, value):
        """The checked-in bound a measurement of `value` ratchets to."""
        v = float(value)
        return v * self.slack if self.kind == "max" else v / self.slack

    def ok(self, value, bound):
        return (value <= bound) if self.kind == "max" else (value >= bound)

    def __repr__(self):
        return (f"Objective({self.name!r}, {self.kind!r}, "
                f"unit={self.unit!r}, slack={self.slack})")


def load_baseline(path):
    """Read a baseline file -> {objective_name: {"kind", "bound", ...}}.
    Raises FileNotFoundError naming the function that writes one."""
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"SLO baseline {path!r} not found — measure the objectives, "
            f"call write_baseline() to write one, then check it in")
    with open(path) as f:
        data = json.load(f)
    return data.get("objectives", {})


def write_baseline(path, values, objectives, note="", merge=None):
    """Ratchet: freeze bounds from `values` (objective name -> measured
    float) with each objective's slack applied. Returns the written
    mapping. `merge` (a mapping from `load_baseline`) carries over
    existing rows for objectives not being re-ratcheted."""
    objs = dict(merge) if merge else {}
    for obj in objectives:
        if obj.name not in values:
            raise KeyError(f"no measured value for objective {obj.name!r}")
        objs[obj.name] = {
            "kind": obj.kind,
            "bound": round(obj.bound_from(values[obj.name]), 6),
            "measured": round(float(values[obj.name]), 6),
            "slack": obj.slack,
            "unit": obj.unit,
            "description": obj.description,
        }
    payload = {"version": 1, "note": note, "objectives": objs}
    from .._atomic_io import atomic_write

    body = json.dumps(payload, indent=1, sort_keys=True).encode() + b"\n"
    atomic_write(path, lambda f: f.write(body))
    return objs


def evaluate(values, baseline, objectives):
    """Gate `values` (objective name -> measured float) against the
    `baseline` mapping from `load_baseline`. Every declared objective
    must have BOTH a measurement and a baseline bound; a missing side is
    a breach. Returns::

        {"ok": bool, "results": [{name, kind, value, bound, ok,
                                  reason?}, ...], "breaches": [name...]}
    """
    results = []
    for obj in objectives:
        entry = baseline.get(obj.name)
        value = values.get(obj.name)
        row = {"name": obj.name, "kind": obj.kind, "unit": obj.unit,
               "value": value,
               "bound": None if entry is None else entry.get("bound")}
        if value is None:
            row.update(ok=False,
                       reason="objective declared but not measured")
        elif entry is None or entry.get("bound") is None:
            row.update(ok=False,
                       reason="no baseline bound (write_baseline() "
                              "ratchets one)")
        elif entry.get("kind", obj.kind) != obj.kind:
            row.update(ok=False,
                       reason=f"baseline kind {entry.get('kind')!r} != "
                              f"declared {obj.kind!r}")
        else:
            row["ok"] = obj.ok(float(value), float(entry["bound"]))
            if not row["ok"]:
                cmp = "over" if obj.kind == "max" else "under"
                row["reason"] = (f"{value:.6g} {obj.unit} is {cmp} the "
                                 f"baseline bound {entry['bound']:.6g}")
        results.append(row)
    breaches = [r["name"] for r in results if not r["ok"]]
    return {"ok": not breaches, "results": results, "breaches": breaches}


def format_report(report):
    """Human-readable one-line-per-objective rendering."""
    lines = []
    for r in report["results"]:
        mark = "PASS" if r["ok"] else "FAIL"
        op = "<=" if r["kind"] == "max" else ">="
        val = "unmeasured" if r["value"] is None else f"{r['value']:.6g}"
        bound = "unset" if r["bound"] is None else f"{r['bound']:.6g}"
        line = (f"  {mark} {r['name']}: {val} {op} {bound} "
                f"{r['unit']}".rstrip())
        if not r["ok"] and r.get("reason"):
            line += f"  ({r['reason']})"
        lines.append(line)
    verdict = "SLO gate: PASS" if report["ok"] else \
        f"SLO gate: FAIL ({len(report['breaches'])} breach(es))"
    return "\n".join(lines + [verdict])
