"""The readers of the program's own spans: the clock offset from hand-made
pairs, self time and gap attribution on hand-made intervals, and each new
per-layer reader under a CPU rehearsal of its cell."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks import harness, program_spans as ps

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
NEW = {"gpt3_1p3b.serve_closed8": [
    "serve.host_ms_per_step", "serve_itl.step_ms_p95",
    "serve_itl.chunk_ms_p95", "serve.round_ms_max"],
    "gpt_base.pretrain_b16s1024": ["train.engine_host_ms_per_step"]}


def row(name, t0, t1, id, parent=None, trace=1, thread="s", attrs=None):
    return ps.Row(name, t0, t1, id, parent, trace, thread, attrs or {})


# ---------------------------------------------------------------------------
# the two clocks
# ---------------------------------------------------------------------------

def pairs(offset_ns, jitter_ns=()):
    """Six spans of two names on both clocks; the profiler's copies start
    `jitter_ns[i]` late."""
    records, host = [], []
    for i in range(6):
        name = "submit" if i % 2 else "dispatch"
        t0, dur = 100.0 + 0.7 * i, 0.004 + 0.0011 * i
        records.append((name, t0, t0 + dur))
        late = jitter_ns[i] if i < len(jitter_ns) else 0
        host.append((name, int(t0 * 1e9 + offset_ns + late),
                     int(dur * 1e9)))
    return records, host


@pytest.mark.parametrize("offset", [0.0, -6.5e13, 1.79e18])
def test_offset_and_residual_from_hand_made_pairs(offset):
    records, host = pairs(offset, jitter_ns=(0, 2000, -2000, 500, 0, 0))
    got, residual, n = ps.clock_offset(records, host)
    assert n == 6
    assert abs(got - offset) <= 2000 + 512          # float64 at 1.8e18
    assert 1500 <= residual <= 2600


def test_offset_survives_spans_the_trace_did_not_catch():
    records, host = pairs(5e9)
    # the trace holds only the middle four; the records hold others too
    got, residual, n = ps.clock_offset(
        records + [("submit", 50.0, 50.005), ("wait", 101.0, 101.3)],
        host[1:5])
    assert n == 4 and abs(got - 5e9) < 10 and residual < 10


def test_a_serving_trace_with_one_benchmark_span_is_levelled_by_it():
    import types

    # 40 decode steps; each wait ends 2.1-2.4 ms after its launch has
    # ended; the trace holds launches 10..29 and one `bench::submit`
    offset, t, waits, ends = 4.2e9, 20.0, [], []
    for i in range(40):
        step, lag = 0.150 + 0.001 * (i % 7), 0.0021 + 0.00001 * (i % 30)
        waits.append((t, t + step + lag))
        ends.append((t + step) * 1e9 + offset)
        t += step + lag + 0.007
    rows = [row("decode.round.decode.fetch", a, b, i)
            for i, (a, b) in enumerate(waits)]
    modules = [("jit_step", e - 150e6, 150e6) for e in ends[9:31]]
    ctx = {"program_spans": rows, "counters": {},
           "mix": {"trace_names": {"decode": ["jit_step"]}},
           "tracer": types.SimpleNamespace(t_start=21.0, t_stop=25.5),
           "spans": types.SimpleNamespace(records=[
               ("submit", 22.0, 22.0005), ("submit", 19.0, 19.0004)]),
           "trace": {"host_spans": [("submit", int(22.0002e9 + offset),
                                     300000)],
                     "devices": [{"ops": [], "modules": modules}]}}
    got, residual, pairs, source = ps.offset_of(ctx)
    assert source == "device_launches+bench_spans" and pairs == 21
    assert abs(got - (offset + 0.2e6)) < 1e3        # the span's level
    assert residual < 0.3e6
    # without the span: the launches alone, short by the least lag
    ctx.pop("clock_offset")
    ctx["trace"]["host_spans"] = []
    got, residual, pairs, source = ps.offset_of(ctx)
    assert source == "device_launches" and pairs == 20
    assert abs(offset - got - 2.2e6) < 1e3      # launch 10 lagged least


def test_offset_is_none_with_too_few_pairs_or_too_wide_a_residual():
    records, host = pairs(1e9)
    assert ps.clock_offset(records, host[:2]) == (None, 0.0, 2)
    records, host = pairs(1e9, jitter_ns=(0, 0, 0, 0, 0, 3_000_000))
    got, residual, n = ps.clock_offset(records, host)
    # the far-off span finds no partner within the limit: five pairs agree
    assert n == 5 and abs(got - 1e9) < 10
    # no three spans agree on one offset within the limit: no offset
    records, host = pairs(1e9, jitter_ns=(0, 0, 3e6, 6e6, 9e6, 12e6))
    assert ps.clock_offset(records, host) == (None, 0.0, 2)
    # the pairs lie within the limit of the guess, not of their median
    records, host = pairs(1e9, jitter_ns=(0, 9e5, 9e5, 18e5, 18e5, 18e5))
    got, residual, n = ps.clock_offset(records, host)
    assert got is None and n == 6 and residual == 13.5e5
    assert ps.clock_offset([], []) == (None, None, 0)


def test_offset_from_the_devices_launches_and_the_hosts_waits():
    import numpy as np

    rng = np.random.default_rng(3)
    offset = -7.25e9
    # 60 steps of 140-165 ms, a prompt chunk of 70 ms before every fourth;
    # each wait ends 40-300 us after its launch has ended
    t, waits, ends = 50.0, [], []
    for i in range(60):
        t += (0.07 if i % 4 == 0 else 0.0) + 0.008
        step = 0.140 + 0.025 * rng.random()
        lag = 40e-6 + 260e-6 * rng.random()
        waits.append((t + 0.001, t + step + lag))
        ends.append((t + step) * 1e9 + offset)
        t += step + lag
    # the trace caught launches 20..44 of them
    launches = [(e - 150e6, e) for e in ends[20:45]]
    got, residual, n = ps.offset_from_launches(launches, waits)
    assert n == 25
    assert 0 <= offset - got <= 60e3 + 512    # short by the least lag only
    assert 50e3 < residual < 200e3            # the median lag less the least
    # too few launches, or more launches than waits: no offset
    assert ps.offset_from_launches(launches[:2], waits)[0] is None
    assert ps.offset_from_launches(launches, waits[:10])[0] is None
    # waits of which most lag by milliseconds are no clock
    slow = [(a, b + 0.004 * (i % 3 > 0)) for i, (a, b) in enumerate(waits)]
    got, residual, n = ps.offset_from_launches(launches, slow)
    assert got is None and residual > 1e6


# ---------------------------------------------------------------------------
# self time, and idle gaps by phase
# ---------------------------------------------------------------------------

def a_round():
    """One scheduler round of 100 ms, with a worker-side pair under the
    hand-off and 4 ms that no phase covers."""
    return [
        row("decode.round", 10.000, 10.100, 1),
        row("decode.round.admit", 10.000, 10.002, 2, parent=1),
        row("decode.round.decode", 10.004, 10.098, 3, parent=1),
        row("decode.round.decode.pack", 10.004, 10.010, 4, parent=3),
        row("decode.round.decode.handoff", 10.010, 10.090, 5, parent=3),
        row("decode.round.decode.enqueue", 10.012, 10.020, 6, parent=5,
            thread="w"),
        row("decode.round.decode.fetch", 10.020, 10.088, 7, parent=5,
            thread="w"),
        row("decode.round.decode.deliver", 10.090, 10.098, 8, parent=3),
    ]


def test_self_time_is_duration_less_what_children_cover():
    got = ps.self_times(a_round())
    want = {"decode.round": 0.004, "decode.round.admit": 0.002,
            "decode.round.decode": 0.0, "decode.round.decode.pack": 0.006,
            "decode.round.decode.handoff": 0.004,
            "decode.round.decode.enqueue": 0.008,
            "decode.round.decode.fetch": 0.068,
            "decode.round.decode.deliver": 0.008}
    assert set(got) | {"decode.round.decode"} == set(want)
    for name, s in got.items():
        assert abs(s - want[name]) < 1e-6, name
    assert abs(sum(got.values()) - 0.100) < 1e-6
    # a child that overlaps its sibling, or hangs over its parent's end,
    # is not counted twice
    rows = [row("p", 0.0, 1.0, 1), row("a", 0.1, 0.6, 2, parent=1),
            row("b", 0.4, 1.2, 3, parent=1)]
    assert abs(ps.self_times(rows)["p"] - 0.1) < 1e-9


def test_idle_gaps_go_to_the_phase_that_covers_most_of_each():
    offset = 7_000_000_000.0               # profiler = perf + 7 s
    ns = lambda t: int(t * 1e9 + offset)  # noqa: E731
    # the device is busy during `.fetch` and idle around it
    devices = [{"ops": [("%fusion.1 fusion", ns(10.020), int(0.068e9))],
                "modules": []},
               {"ops": [], "modules": []}]
    idle, split = ps.idle_by_phase(devices, ps.self_segments(a_round()),
                                   ns(10.000), ns(10.110), offset)
    # before the step: admit 2 ms, the round's own 2, pack 6, handoff 2,
    # enqueue 8: the gap goes whole to the widest cover; after it: handoff
    # 2, deliver 8, the round's own 2 and 10 ms past its end uncovered
    assert set(idle) == {"decode.round.decode.enqueue",
                         "decode.round.decode.deliver"}
    assert abs(idle["decode.round.decode.enqueue"] - 0.020) < 1e-6
    assert abs(idle["decode.round.decode.deliver"] - 0.022) < 1e-6
    want = {"decode.round.admit": 0.002, "decode.round": 0.004,
            "decode.round.decode.pack": 0.006,
            "decode.round.decode.handoff": 0.004,
            "decode.round.decode.enqueue": 0.008,
            "decode.round.decode.deliver": 0.008, ps.UNNAMED: 0.010}
    assert set(split) == set(want)
    for name, sec in want.items():
        assert abs(split[name] - sec) < 1e-6, name
    # with nothing of the program there, every gap is unnamed
    idle, split = ps.idle_by_phase(devices, [], ns(10.000), ns(10.110),
                                   offset)
    assert list(idle) == list(split) == [ps.UNNAMED]
    assert abs(idle[ps.UNNAMED] - 0.042) < 1e-6
    # the reader: the round's own time and what nothing covers, of it all
    reader = harness.load_module(os.path.join(
        harness.HERE, "layers", "serve.idle_unnamed_share.py"), "r")
    ctx = {"idle_by_phase": (None, want)}
    assert abs(reader.read(ctx) - 100.0 * 0.014 / 0.042) < 1e-6


def test_rows_are_chosen_by_window_name_root_and_trace():
    rows = a_round() + [row("decode.round", 10.2, 10.3, 9, trace=2),
                        row("decode.round.admit", 10.2, 10.21, 10, parent=9,
                            trace=2)]
    inside = ps.inside(rows, 9.9, 10.15, "decode.round", roots=True)
    assert [r.id for r in inside] == [1]
    assert [r.id for r in ps.in_traces(rows, inside)] == list(range(1, 9))
    assert ps.p95_ms([]) is None
    assert abs(ps.p95_ms(rows[:1]) - 100.0) < 1e-6


def test_a_program_without_the_store_reads_as_nothing(monkeypatch):
    from paddle_tpu.obs import flight

    ctx = {"counters": {"t_open": 0.0, "t_close": 1.0}}
    monkeypatch.delattr(flight.FlightRecorder, "spans_between")
    assert ps.window_spans(0.0, 1.0) is None
    assert ps.of_window(ctx) is None
    assert ps.rounds_of(ctx) == (None, None)
    assert ps.idle_of(ctx) is None
    for cell, names in NEW.items():
        for name in names + ["serve.idle_unnamed_share"]:
            reader = harness.load_module(os.path.join(
                harness.HERE, "layers", name + ".py"), "r")
            assert reader.read(dict(ctx)) is None


def test_a_wrapped_ring_reads_as_nothing(monkeypatch):
    from paddle_tpu.obs import flight

    monkeypatch.setattr(flight.FlightRecorder, "spans_between",
                        lambda self, t0, t1, prefix=None: ([], True))
    assert ps.window_spans(0.0, 1.0) is None


# ---------------------------------------------------------------------------
# the readers under a rehearsal of their cells
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", sorted(NEW))
def test_new_readers_report_in_a_cpu_rehearsal(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         cell, "--seed", str(2 ** 31 + 11), "--seconds", "3", "--trace",
         "1"], cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    for name in NEW[cell]:
        assert line["metrics"][name]["value"] > 0, name
    # no device trace on the CPU: the metric that needs one is left out
    assert "serve.idle_unnamed_share" not in line["metrics"]
    spans = [json.loads(t[len("[spans] "):]) for t in lines
             if t.startswith("[spans] ")]
    assert len(spans) == 1
    assert spans[0]["spans_read"] > 0 and spans[0]["wrapped"] is False
    assert spans[0]["clock_pairs"] >= 3
    assert spans[0]["clock_source"] == "bench_spans"
    assert spans[0]["clock_residual_us"] < 1000
    assert spans[0]["window_self_s"]
    if cell.startswith("gpt3"):
        assert spans[0]["queue_wait_ms"]["n"] > 0
        # a step is most of a round, a round at most the longest one
        m = line["metrics"]
        assert m["serve_itl.step_ms_p95"]["value"] \
            <= m["serve.round_ms_max"]["value"]


def test_new_metrics_are_declared_with_their_layers_and_cells():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for cell, names in NEW.items():
        for name in names + (["serve.idle_unnamed_share"]
                             if cell.startswith("gpt3") else []):
            m = by_name[name]
            assert m["source"] == "program_span" and m["workloads"] == [cell]
            assert m["better"] == "lower"
            assert os.path.exists(os.path.join(
                harness.HERE, "layers", name + ".py"))
    assert by_name["serve.idle_unnamed_share"]["layer"] == "device"
    assert by_name["train.engine_host_ms_per_step"]["layer"] \
        == "train engine"
