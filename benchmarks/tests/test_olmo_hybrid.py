"""The cell `olmo_hybrid_7b.serve_reason_closed16` at its rehearsal sizes: a
sound run is correct under the rehearsal limits, the planted fault (the
state not carried across a chunk boundary) and the float8 control are not,
`BENCHMARK.json` names files that are there, the configuration holds the
catalog row's numbers, and the counts of `flops_olmo_hybrid.py` agree with
hand-worked values.
"""
import json
import os

import numpy as np
import pytest

from benchmarks import flops_olmo_hybrid as fl
from benchmarks import harness

CELL = "olmo_hybrid_7b.serve_reason_closed16"
LIN_METRICS = {"serve_lin.step_mfu", "serve_lin.decode_roofline",
               "serve_lin.chunk_scan_roofline",
               "serve_lin.cache_mb_per_sequence"}


def run_cell(capsys, seconds=3, trace=0):
    run = harness.load_module(os.path.join(harness.HERE, "run.py"),
                              "bench_run_lin")
    assert run.main(["--workload", CELL, "--seed", str(2 ** 31 + 41),
                     "--seconds", str(seconds), "--trace", str(trace)]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def calibrate(capsys, *more):
    cal = harness.load_module(
        os.path.join(harness.HERE, "calibrate_lin.py"), "cal_lin")
    seeds = [2 ** 31 + 43, 2 ** 31 + 47, 5]
    assert cal.main(["--workload", CELL, "--seeds",
                     ",".join(map(str, seeds)), "--seconds", "3",
                     *more]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_rehearsal_is_correct_under_its_limits(capsys):
    line = run_cell(capsys, trace=1)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 16
    assert set(line["compared"]) == {
        "token_gap", "tokens_compared", "short_answers",
        "compiles_in_window"}
    # no device on the CPU: the readers of the device's trace find nothing
    # and stay silent, the counters' readers read
    assert set(line["metrics"]) == {
        "serve.decode_occupancy", "serve_lin.cache_mb_per_sequence"}
    per_seq = line["metrics"]["serve_lin.cache_mb_per_sequence"]["value"]
    # a slot of 3 layers x (4 x 16 x 8 float32 + 3 x 128 bfloat16) is
    # 0.0084 MB; the blocks of 1 full layer on top of it
    assert 0.0084 < per_seq < 0.05
    # the gap between tokens is computed and logged, and no metric of the
    # cell: its p95 spread 0.99 % over six seeds on the chip (PERF.md 2)
    assert set(line["end_to_end_in_traced_run"]) == {
        "serve_tokens_per_s", "serve_itl_p95_ms", "setup_s"}


def test_a_served_token_altered(capsys, monkeypatch):
    from paddle_tpu.inference.decode.engine import SequenceStream

    orig = SequenceStream._push
    monkeypatch.setattr(SequenceStream, "_push",
                        lambda self, tok: orig(self, int(tok) ^ 1))
    line = run_cell(capsys)
    assert line["correct"] is False
    assert line["compared"]["token_gap"]["value"] \
        > line["compared"]["token_gap"]["limit"]


def test_the_float8_control_is_not_correct(capsys):
    last = calibrate(capsys)
    assert last["verdicts"]["program"] == "0 of 3 seeds not correct", last
    assert last["verdicts"]["control_fp8"] == "3 of 3 seeds not correct"
    assert set(last["readings"]["token_gap"]) == {
        "program", "control_fp8", "control_state_bf16"}


def test_a_state_not_carried_across_a_chunk_boundary_is_not_correct(capsys):
    last = calibrate(capsys, "--fault", "carry", "--control", "0")
    assert last["verdicts"] == {"fault_carry": "3 of 3 seeds not correct"}


def test_benchmark_json_names_files_that_are_there():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    assert cell == {**cell, "config": "olmo_hybrid_7b", "chips": 1,
                    "traffic": "serve_reason_closed16"}
    assert conf["file"] == "benchmarks/configs/olmo_hybrid_7b.json"
    doc = harness.load_json(harness.ROOT, conf["file"])
    assert conf["source"] == doc["source"]
    assert conf["reduced"] == doc["reduced"] == sorted(
        doc["published"], key=doc["reduced"].index)
    mix = harness.load_json(harness.HERE, "traffic",
                            cell["traffic"] + ".json")
    for path in (("drivers", mix["driver"] + ".py"),
                 ("limits", CELL + ".json"),
                 ("reference", "olmo_hybrid_ref.py"),
                 ("weights_olmo_hybrid.py",), ("calibrate_lin.py",)):
        assert os.path.exists(os.path.join(harness.HERE, *path)), path
    resolved = harness.resolve_cell(CELL, rehearsal=False)
    names = {m["name"] for m in harness.metrics_for(resolved, "per_layer")}
    assert LIN_METRICS <= names
    assert names - LIN_METRICS == {
        "serve.decode_occupancy", "serve.device_idle_share",
        "serve.peak_hbm_gb", "serve.reserved_hbm_gb"}
    for name in names:
        assert os.path.exists(os.path.join(harness.HERE, "layers",
                                           name + ".py")), name
    for m in bench["per_layer"]:
        if m["name"] in LIN_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
    assert {m["name"] for m in harness.metrics_for(resolved, "end_to_end")} \
        == {"serve_tokens_per_s", "setup_s"}


def test_the_configuration_holds_the_catalog_rows_numbers():
    conf = harness.load_json(harness.HERE, "configs", "olmo_hybrid_7b.json")
    published = {"vocab_size": 100352, "hidden_size": 3840,
                 "intermediate_size": 11008, "num_attention_heads": 30,
                 "num_key_value_heads": 30, "rms_norm_eps": 1e-06,
                 "linear_num_key_heads": 30, "linear_num_value_heads": 30,
                 "linear_key_head_dim": 96, "linear_value_head_dim": 192,
                 "linear_conv_kernel_dim": 4,
                 "linear_allow_neg_eigval": True,
                 "tie_word_embeddings": False, "attention_bias": False}
    assert {k: conf[k] for k in published} == published
    assert conf["layer_types"] == (["linear_attention"] * 3
                                   + ["full_attention"]) * 8
    assert conf["rope_parameters"] == {"rope_theta": None}
    assert conf["published"] == {"num_hidden_layers": 32,
                                 "max_position_embeddings": 65536}
    assert (conf["num_hidden_layers"], conf["max_position_embeddings"]) \
        == (16, 2048)
    m = conf["model"]
    assert (m["num_layers"], m["num_heads"], m["num_kv_heads"],
            m["layer_norm_epsilon"], m["linear_num_heads"]) == (
        conf["num_hidden_layers"], conf["num_attention_heads"],
        conf["num_key_value_heads"], conf["rms_norm_eps"],
        conf["linear_num_key_heads"])
    assert m["head_dim"] * m["num_heads"] == m["hidden_size"]
    assert m["layer_pattern"] == conf["layer_types"][:4]
    for key in ("hidden_size", "vocab_size", "intermediate_size",
                "max_position_embeddings", "linear_key_head_dim",
                "linear_value_head_dim", "linear_conv_kernel_dim",
                "linear_allow_neg_eigval", "tie_word_embeddings"):
        assert m[key] == conf[key], key
    for key in ("assumed", "precision", "deployment", "rehearsal",
                "layers"):
        assert conf[key], key
    assert conf["deployment"].startswith(
        "one stage of a two-stage pipeline")
    # the weights the benchmark draws are the parameters counted above
    from benchmarks import weights_olmo_hybrid

    total = sum(int(np.prod(shape)) for shape, _, _ in
                weights_olmo_hybrid.shapes(m).values())
    assert round(total / 1e9, 2) == 4.10


def test_the_engine_takes_every_prompt_of_the_mix_at_the_served_size():
    cell = harness.resolve_cell(CELL, rehearsal=False)
    mix, geo = cell["mix"], cell["mix"]["engine"]
    assert geo["prefill_chunk"] == max(geo["prefill_buckets"])
    rows = mix["prompt_len"]["max"] + mix["output_len"]["max"]
    assert rows <= max(mix["check_pad"]) <= geo["max_length"]
    assert max(geo["decode_buckets"]) == mix["arrival"]["clients"]
    serve = harness.load_module(
        os.path.join(harness.HERE, "drivers", "serve.py"), "d_serve_lin")
    warm = serve.warm_requests(mix, cell["model"]["vocab_size"], 1)
    # the warm traffic walks every prefill bucket and a second chunk
    assert {len(r["prompt"]) for r in warm} == {64, 128, 256, 320}


def test_counts_agree_with_hand_worked_values_olmo_hybrid():
    m = harness.load_json(harness.HERE, "configs",
                          "olmo_hybrid_7b.json")["model"]
    mp = fl.matmul_params(m)
    # linear: 3840 x 30 x (96 + 96 + 192) + 3840 x 60 + 2 x 3840 x 5760;
    # full: 3840 x 90 x 128 + 3840 x 3840; mlp 3 x 3840 x 11008
    assert mp == {"linear": 88_704_000, "full": 58_982_400,
                  "mlp": 126_812_160, "head": 385_351_680}
    assert fl.counts(m) == (12, 4)
    assert fl.layer_params(m) == 12 * 215_516_160 + 4 * 185_794_560
    # the rule at one position, one layer: 7 x 30 x 192 x 96 and the
    # convolution's 2 x 4 x 11520
    assert fl.rule_flops_per_position(m) == 3_870_720 + 92_160
    # one decode position at cache position 99 (100 keys a full layer)
    want = 2 * fl.layer_params(m) + 12 * 3_962_880 \
        + 4 * 4 * 30 * 128 * 100 + 2 * 385_351_680
    assert fl.forward_flops(m, [99], 1) == want
    # bytes: a sequence's state 12 x (30 x 192 x 96 x 4 + 3 x 11520 x 2),
    # a token's rows 2 x 4 x 3840 x 2
    assert fl.state_bytes_per_sequence(m) == 12 * (2_211_840 + 69_120)
    assert fl.kv_bytes_per_token(m) == 61_440
    assert fl.weight_bytes(m) == 2 * (fl.layer_params(m) + 385_351_680)
    assert fl.decode_bytes(m, 1, 16, 16 * 700) == fl.weight_bytes(m) \
        + 2 * 16 * fl.state_bytes_per_sequence(m) + 11200 * 61_440
    assert fl.prompt_chunks(300, 256) == [(0, 256), (256, 44)]
    assert fl.chunk_bytes(m, 256, 44) == fl.weight_bytes(m) \
        + 2 * fl.state_bytes_per_sequence(m) + 300 * 61_440
