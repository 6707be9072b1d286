"""BENCHMARK.json against the files it names, the counts of operations and
bytes against hand-worked values, and a CPU rehearsal of every cell."""
import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks import flops, harness

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_every_cell_resolves_to_its_files_by_name():
    for w in BENCH["workloads"]:
        cell = harness.resolve_cell(w["name"], rehearsal=False)
        assert cell["model"]["hidden_size"] > 0
        assert os.path.exists(os.path.join(
            harness.HERE, "drivers", cell["mix"]["driver"] + ".py"))
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert w["name"] == f"{w['config']}.{w['traffic']}"
    for c in BENCH["configs"]:
        conf = harness.load_json(harness.ROOT, c["file"])
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        assert not any(k.endswith(("_size", "_dim", "_rank", "_heads"))
                       for k in c["reduced"])


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_has_limits_of_its_own(cell):
    """Each measured limit sits in the cell's own file, above its lower
    reading and below its upper one, with more room above the lower."""
    doc = harness.load_json(harness.HERE, "limits", cell + ".json")
    numbers = doc["numbers"]
    assert numbers and harness.resolve_cell(cell, False)["limits"] == {
        k: v["limit"] for k, v in numbers.items()}
    for name, n in list(numbers.items()) + list(
            doc.get("rehearsal", {}).items()):
        assert n["upper"] >= 3 * n["lower"], name
        assert n["lower"] < n["limit"] < n["upper"], name
        assert n["upper_is"]


def test_a_number_with_no_limit_of_the_cells_own_stops_the_run():
    checks = harness.Checks({"token_gap": 0.1})
    checks.add("token_gap", 0.05)
    checks.add("compiles_in_window", 0, 0)
    assert checks.correct
    with pytest.raises(SystemExit, match="no limit for 'delta_gap'"):
        checks.add("delta_gap", 0.001)


def test_names_and_units_use_only_the_allowed_characters():
    names = [m["name"] for g in ("end_to_end", "per_layer")
             for m in BENCH[g]]
    names += CELLS + [c["name"] for c in BENCH["configs"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    for g in ("end_to_end", "per_layer"):
        for m in BENCH[g]:
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
    for root, _, files in os.walk(harness.HERE):
        if "__pycache__" in root:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), (root, f)


def test_metrics_have_readers_bounds_and_cells():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    reports = {c: {m["name"] for m in BENCH["end_to_end"]
                   if c in m.get("workloads", CELLS)} for c in CELLS}
    for m in BENCH["per_layer"]:
        assert os.path.exists(os.path.join(
            harness.HERE, "layers", m["name"] + ".py")), m["name"]
        assert "bound" not in m and m["moves"] in e2e
        for c in m["workloads"]:
            assert m["moves"] in reports[c], (m["name"], c)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for c in CELLS:
        assert len(reports[c]) >= 2
        assert any(c in m["workloads"] for m in BENCH["per_layer"])
    per_cell_time = BENCH["run_seconds"] + 60
    assert (2 + 14 * 24) * per_cell_time + 24 * 180 + 1200 <= 43200


def test_counts_agree_with_hand_worked_values_gpt_base_16x1024():
    m = harness.load_json(harness.HERE, "configs", "gpt_base.json")["model"]
    # 12 layers of 4*768^2 + 2*768*3072 = 7,077,888 matmul weights, a tied
    # head of 50304*768 over 1023 positions a row, 6 flops a weight a token
    dense = 6 * (12 * 7_077_888 * 16 * 1024 + 38_633_472 * 16 * 1023)
    # 1024*1025/2 causal pairs, 2*768 flops a pair a matmul, 6 matmuls
    flash = 6 * 2 * 768 * 524_800 * 16 * 12
    assert flops.flash_train_flops(m, 16, 1024) == flash == 928_618_905_600
    assert flops.train_flops_per_step(m, 16, 1024) == dense + flash \
        == 13_072_151_347_200
    assert flops.flash_train_bytes(m, 16, 1024) == 3_623_878_656
    peaks = harness.load_json(harness.HERE, "peaks.json")["TPU v5 lite"]
    least, bound = flops.roofline_seconds(flash, 3_623_878_656, peaks)
    assert bound == "compute" and least == pytest.approx(flash / 197e12)


def test_counts_agree_with_hand_worked_values_gpt3_1p3b_decode_step():
    m = harness.load_json(harness.HERE, "configs", "gpt3_1p3b.json")["model"]
    # 24 * (4*2048^2 + 2*2048*8192) + 50304*2048 weights, two bytes each
    assert flops.weight_bytes(m) == 2 * (24 * 50_331_648 + 103_022_592) \
        == 2_621_964_288
    assert flops.kv_bytes_per_token(m) == 196_608
    # one step of 8 sequences with 1000 tokens present each
    assert flops.decode_bytes(m, 1, 8000) == 4_194_828_288
    # one decoded token at position 999: 2 flops a weight, 1000 keys
    assert flops.serve_flops(m, [999], 1) == \
        2 * (24 * 50_331_648 + 103_022_592) + 4 * 2048 * 1000 * 24


def test_traffic_has_the_same_sizes_for_every_seed():
    from benchmarks.traffic import generate

    mix = harness.load_json(harness.HERE, "traffic", "serve_closed8.json")
    a = generate.requests(mix, 50304, 1)
    b = generate.requests(mix, 50304, 2 ** 31 + 17)
    sizes = lambda rs, k: sorted(  # noqa: E731
        len(r[k]) if k == "prompt" else r[k] for r in rs)
    assert sizes(a, "prompt") == sizes(b, "prompt")
    assert sizes(a, "max_new") == sizes(b, "max_new")
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert len(a) == 4 * mix["lengths_pool"]
    pool = mix["lengths_pool"]
    assert sizes(a[:pool], "prompt") == sizes(a[pool:2 * pool], "prompt")
    assert len({r["prompt"].tobytes() for r in a}) == len(a)
    assert min(sizes(a, "prompt")) >= 64 and max(sizes(a, "prompt")) <= 1536
    assert min(sizes(a, "max_new")) >= 8 and max(sizes(a, "max_new")) <= 256
    due = generate.open_loop_schedule(
        {"kind": "poisson", "rate_per_s": 5.0}, 20.0, 3)
    assert 60 < len(due) < 140 and due[-1] < 20.0


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cpu_rehearsal_ends_in_the_contract_line(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         cell, "--seed", str(2 ** 31 + 5), "--seconds", "3", "--trace",
         str(trace)], cwd=harness.ROOT, env=env, capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    keys = list(line)
    assert set(keys) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert keys[-1] == "compared"
    assert set(keys) <= {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "compared",
                         "end_to_end_in_traced_run"}
    assert "memory_peak_bytes" in line["device"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    group = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in BENCH[group]
               if cell in m.get("workloads", [cell])}
    assert line["metrics"] and set(line["metrics"]) <= set(allowed)
    if not trace:
        assert set(line["metrics"]) == set(allowed)
    for name, m in line["metrics"].items():
        assert m["unit"] == allowed[name] and m["value"] > 0
    tail = proc.stderr.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") for t in tail)


def test_no_chip_no_result():
    # the CPU, but not as a rehearsal that the caller declared
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["JAX_PLATFORM_NAME"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{")
    assert "not in peaks.json" in proc.stderr
