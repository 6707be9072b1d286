"""`correct` has to come out false when the timed path is broken underneath.

Each test drives a whole run of the harness in this process (only the look
for a chip is skipped: `JAX_PLATFORMS=cpu` makes it a rehearsal) with one
fault planted in the program, and reads the run's last line. The float8
control of the plain reference is read through the same comparison.
"""
import json

import numpy as np
import pytest

from benchmarks import harness

TRAIN, SERVE = "gpt_base.pretrain_b16s1024", "gpt3_1p3b.serve_closed8"


def run_cell(cell, capsys, seconds=2):
    run = harness.load_module(harness.os.path.join(harness.HERE, "run.py"),
                              "bench_run")
    assert run.main(["--workload", cell, "--seed", str(2 ** 31 + 23),
                     "--seconds", str(seconds), "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def failed(line):
    return sorted(k for k, v in line["compared"].items()
                  if k.endswith("_gap") and v["value"] > v["limit"])


def test_sound_runs_are_correct(capsys):
    for cell in (TRAIN, SERVE):
        line = run_cell(cell, capsys)
        assert line["correct"] is True, line["compared"]


def test_a_step_that_returns_its_state_unchanged(capsys, monkeypatch):
    from paddle_tpu.distributed.engine import ShardedTrainStep

    orig = ShardedTrainStep.train_batches

    def frozen(self, batches, n=None):
        snap = self.snapshot()
        out = orig(self, batches, n)
        self.restore(snap)
        return out

    monkeypatch.setattr(ShardedTrainStep, "train_batches", frozen)
    line = run_cell(TRAIN, capsys)
    assert line["correct"] is False
    assert {"moment_gap", "delta_gap"} <= set(failed(line))
    assert line["compared"]["delta_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(capsys, monkeypatch):
    from paddle_tpu.distributed.engine import ShardedTrainStep

    orig = ShardedTrainStep.train_batches

    def half(self, batches, n=None):
        return orig(self, [tuple(b[:len(b) // 2] for b in bt)
                           for bt in batches], n)

    monkeypatch.setattr(ShardedTrainStep, "train_batches", half)
    line = run_cell(TRAIN, capsys)
    assert line["correct"] is False and failed(line), line["compared"]


def test_a_token_altered_where_it_is_produced(capsys, monkeypatch):
    from paddle_tpu.inference.decode.engine import SequenceStream

    orig = SequenceStream._push
    monkeypatch.setattr(SequenceStream, "_push",
                        lambda self, tok: orig(self, int(tok) ^ 1))
    line = run_cell(SERVE, capsys)
    assert line["correct"] is False and failed(line) == ["token_gap"]


def test_the_float8_control_reads_above_the_program():
    """The control at a size a test can hold, through the cells' own
    comparisons: the reference in float8 in the program's place."""
    import jax

    from benchmarks import weights
    from benchmarks.traffic import generate

    train = harness.load_module(
        harness.os.path.join(harness.HERE, "drivers", "train.py"), "d_train")
    cell = harness.resolve_cell(TRAIN, rehearsal=True)
    model, mix = cell["model"], cell["mix"]
    seed = 2 ** 31 + 29
    batches = generate.token_batches(
        {**mix, "distinct_dispatches": 1}, model["vocab_size"], seed)[0]
    ref = train.reference_readings(model, mix, batches, seed)
    ctl = train.reference_readings(model, mix, batches, seed, quantized=True)
    checks = harness.Checks(cell["limits"])
    train.compare(ctl, ref, checks)
    assert not checks.correct, checks.rows

    serve = harness.load_module(
        harness.os.path.join(harness.HERE, "drivers", "serve.py"), "d_serve")
    cell = harness.resolve_cell(SERVE, rehearsal=True)
    model, mix = cell["model"], cell["mix"]
    rng = np.random.default_rng(7)
    # the control needs only contexts to choose its tokens in: at each
    # position the token that float8 puts first is held against the
    # reference, whatever was served there
    sample = [{"prompt": rng.integers(1, model["vocab_size"], 20,
                                      dtype=np.int32),
               "tokens": list(rng.integers(1, model["vocab_size"], 130))}
              for _ in range(8)]
    pad = mix["engine"]["max_length"]
    # through the cell's own comparison, with the limit that was read at
    # this size (`rehearsal` in the cell's limits file: at width 64 logits,
    # and so gaps, are a sixth of the served model's): the control comes
    # out not correct on every seed. On the chip at the cell's own size
    # calibrate.py judges the control the same way under the chip's limit.
    for s in (seed, 1, 2):
        ctl = serve.token_gaps(model, s, mix["weights_dtype"], sample, pad,
                               quantized=True)
        checks = harness.Checks(cell["limits"])
        checks.add("token_gap", float(ctl.max()))
        assert not checks.correct, checks.rows


def test_open_loop_sends_on_schedule_whatever_the_server_does():
    """The open-loop mixes kept for later cells are data: the same driver
    runs a Poisson schedule, times each request from when it was due and
    reports how late the generator ran."""
    from benchmarks import weights
    from benchmarks.traffic import generate

    serve = harness.load_module(
        harness.os.path.join(harness.HERE, "drivers", "serve.py"), "d_open")
    cell = harness.resolve_cell(SERVE, rehearsal=True)
    cell["mix"] = {**cell["mix"],
                   "arrival": {"kind": "poisson", "rate_per_s": 20.0}}
    model, mix = cell["model"], cell["mix"]
    tools = {"counter": harness.CompileCounter(), "spans": harness.Spans(),
             "tracer": harness.TraceWindow(False, 0, 0),
             "window_opened": lambda t: None}
    eng, pool = serve.build_server(cell, weights.make(model, 3, "bfloat16"))
    try:
        eng.warmup()
        serve.warm_traffic(cell, eng, pool, 3, tools["spans"])
        reqs = generate.requests(mix, model["vocab_size"], 3)
        win = serve.window(cell, eng, pool, reqs, 2.0, tools, seed=3)
    finally:
        pool.shutdown()
        eng.shutdown()
    due = generate.open_loop_schedule(mix["arrival"], 2.0, 3)
    recs = win["records"]
    assert len(recs) == len(due) > 20
    late = [r["t_submit"] - r["t_due"] for r in recs]
    assert min(late) >= 0 and float(np.median(late)) < 0.05
    assert all(r["status"] in ("completed", "cancelled_at_close")
               for r in recs)
