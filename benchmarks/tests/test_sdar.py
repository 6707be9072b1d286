"""The cell `sdar_30b_a3b.serve_bd4_closed16` at its rehearsal sizes: a
sound run is correct under the rehearsal limits, each planted fault and the
float8 control are not, the counts of `flops_sdar.py` agree with hand-worked
values, and the comparison rebuilds a block as it stood before a pass.
"""
import json

import numpy as np
import pytest

from benchmarks import flops_sdar, harness

CELL = "sdar_30b_a3b.serve_bd4_closed16"
BD = {"block_length": 4, "denoising_steps": 2, "mask_token_id": 255}


def run_cell(capsys, seconds=2):
    run = harness.load_module(harness.os.path.join(harness.HERE, "run.py"),
                              "bench_run_sdar")
    assert run.main(["--workload", CELL, "--seed", str(2 ** 31 + 41),
                     "--seconds", str(seconds), "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def not_ok(line):
    return sorted(k for k, v in line["compared"].items()
                  if k not in ("positions_compared",)
                  and v["value"] > v["limit"])


def driver():
    return harness.load_module(
        harness.os.path.join(harness.HERE, "drivers", "serve_bd.py"),
        "d_serve_bd")


def test_the_rehearsal_is_correct_under_its_limits(capsys):
    line = run_cell(capsys)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 16
    assert set(line["compared"]) == {
        "token_gap", "pick_gap", "positions_compared", "short_answers",
        "short_blocks", "compiles_in_window"}
    assert set(line["metrics"]) == {"serve_tokens_per_s",
                                    "serve_itl_p95_ms", "setup_s"}


def test_a_served_token_altered(capsys, monkeypatch):
    from paddle_tpu.inference.decode.engine import SequenceStream

    orig = SequenceStream._push
    monkeypatch.setattr(SequenceStream, "_push",
                        lambda self, tok: orig(self, int(tok) ^ 1))
    line = run_cell(capsys)
    assert line["correct"] is False and "token_gap" in not_ok(line)


def test_a_block_committed_after_one_pass(capsys, monkeypatch):
    """Every masked position fixed in a block's first pass: each token is
    its position's own arg-max, so only the count of passes tells."""
    from paddle_tpu.inference.decode.engine import DecodeEngine

    orig = DecodeEngine._bd_advance

    def hasty(self, seq, committed, best, conf):
        if not committed:
            st = seq.bd
            st["t"] += 1
            fix = np.flatnonzero(st["masked"])
            st["tokens"][fix] = best[fix]
            st["masked"][fix] = False
            st["passes"][fix] = st["t"]
            return
        orig(self, seq, committed, best, conf)

    monkeypatch.setattr(DecodeEngine, "_bd_advance", hasty)
    line = run_cell(capsys)
    assert line["correct"] is False and "short_blocks" in not_ok(line)


def test_the_least_confident_positions_fixed_first(capsys, monkeypatch):
    """Each token is still its position's own arg-max and every block has
    its passes: only `pick_gap` tells (`calibrate_bd.py --fault pick`)."""
    from paddle_tpu.inference.decode.engine import DecodeEngine

    orig = DecodeEngine._bd_advance
    monkeypatch.setattr(
        DecodeEngine, "_bd_advance",
        lambda self, seq, committed, best, conf: orig(
            self, seq, committed, best, -conf))
    line = run_cell(capsys)
    assert line["correct"] is False and not_ok(line) == ["pick_gap"]


def test_a_commit_that_writes_no_cache(capsys, monkeypatch):
    """The pool a dispatch returns is dropped: every later block attends to
    rows that no commit wrote."""
    from paddle_tpu.inference.decode.engine import DecodeEngine

    orig = DecodeEngine._bd_dispatch

    def dropped(self, active):
        pool = self.pool.tensors
        out = orig(self, active)
        self.pool.tensors = pool
        return out

    monkeypatch.setattr(DecodeEngine, "_bd_dispatch", dropped)
    line = run_cell(capsys)
    assert line["correct"] is False
    assert {"token_gap", "pick_gap"} & set(not_ok(line))


def test_the_float8_control_is_not_correct(capsys):
    cal = harness.load_module(
        harness.os.path.join(harness.HERE, "calibrate_bd.py"), "cal_bd")
    seeds = [2 ** 31 + 43, 2 ** 31 + 47, 5]
    assert cal.main(["--workload", CELL, "--seeds",
                     ",".join(map(str, seeds)), "--seconds", "2"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["verdicts"] == {
        "program": "0 of 3 seeds not correct",
        "control_fp8": "3 of 3 seeds not correct"}, last


def test_a_block_is_rebuilt_as_it_stood_before_a_pass():
    bd = driver()
    rec = {"prompt": np.arange(1, 7, dtype=np.int32),          # 4 + 2
           "tokens": [10, 11, 20, 21, 22, 23, 30],
           "passes": [1, 1, 2, 1, 1, 2, 1]}
    blocks = bd.blocks_of(rec, 4)
    assert [b["start"] for b in blocks] == [4, 8]     # the tail block is cut
    assert blocks[0]["given"] == 2 and blocks[1]["given"] == 0
    assert blocks[0]["tokens"].tolist() == [5, 6, 10, 11]
    assert blocks[0]["passes"].tolist() == [0, 0, 1, 1]
    assert blocks[1]["passes"].tolist() == [2, 1, 1, 2]
    assert bd.short_blocks([rec], BD) == 0
    hasty = dict(rec, passes=[1, 1, 1, 1, 1, 1, 1])
    assert bd.short_blocks([hasty], BD) == 1          # block 1 needed two
    pairs = bd.pick_pairs(rec, BD, 10, np.random.default_rng(0))
    assert sorted((b["start"], t) for b, t in pairs) == [(4, 1), (8, 1),
                                                        (8, 2)]


def test_a_prompt_id_that_equals_the_mask_is_drawn_again():
    bd = driver()
    cell = harness.resolve_cell(CELL, rehearsal=True)
    mix = {**cell["mix"], "block_diffusion": {**BD, "mask_token_id": 7}}
    reqs = bd.requests(mix, 16, 3)                    # 1 id in 15 is the mask
    assert len(reqs) == 4 * mix["lengths_pool"]
    assert all((r["prompt"] != 7).all() and (r["prompt"] >= 1).all()
               for r in reqs)


def test_the_engine_takes_every_prompt_of_the_mix_at_the_served_size():
    """`DecodeEngine` refuses a prompt longer than its largest prefill
    bucket, chunked or not: the mix's longest prompt, its warm requests and
    the reference's padding all have to fit the geometry as committed."""
    bd = driver()
    cell = harness.resolve_cell(CELL, rehearsal=False)
    mix, geo = cell["mix"], cell["mix"]["engine"]
    largest = max(geo["prefill_buckets"])
    assert mix["prompt_len"]["max"] <= min(largest, geo["max_length"] - 1)
    assert max(len(r["prompt"]) for r in bd.serve.warm_requests(
        mix, cell["model"]["vocab_size"], 1)) <= largest
    bl = mix["block_diffusion"]["block_length"]
    assert geo["block_size"] % bl == 0 and geo["prefill_chunk"] % bl == 0
    rows = -(-(mix["prompt_len"]["max"] + mix["output_len"]["max"]) // bl) * bl
    assert rows <= max(mix["check_pad"]) <= geo["max_length"]
    assert max(geo["decode_buckets"]) == mix["arrival"]["clients"]


def test_counts_agree_with_hand_worked_values_sdar():
    m = harness.load_json(harness.HERE, "configs",
                          "sdar_30b_a3b.json")["model"]
    mp = flops_sdar.matmul_params(m)
    # attention 2048 x (32 + 2 x 4) x 128 + 32 x 128 x 2048, router 2048 x
    # 128, one expert 3 x 2048 x 768, the head 151936 x 2048
    assert mp == {"attention": 18_874_368, "router": 262_144,
                  "expert": 4_718_592, "head": 311_164_928}
    assert flops_sdar.keys_attended(m, 0) == 4
    assert flops_sdar.keys_attended(m, 7) == 8
    # one denoising forward of the block at 256: 4 positions, each through
    # attention, router and 8 experts in 6 layers, 260 keys a position, the
    # head at 4 rows
    per_position = 18_874_368 + 262_144 + 8 * 4_718_592
    want = 6 * (2 * per_position * 4 + 4 * 32 * 128 * 260 * 4) \
        + 2 * 311_164_928 * 4
    assert flops_sdar.block_forwards_flops(m, 1, 0, 260) == want \
        == 5_322_047_488
    # its commit pass: the same less the head
    assert flops_sdar.block_forwards_flops(m, 1, 1, 260) \
        == want - 2 * 311_164_928 * 4
    # a prompt of 10: the 8 positions of its whole blocks, 4 + 8 keys
    assert flops_sdar.prompt_flops(m, 10) == 6 * (
        2 * per_position * 8 + 4 * 32 * 128 * (4 * 4 + 4 * 8))
    # bytes of a dispatch of 16 sequences at position 256 that touched 100
    # experts a layer, with a denoising pass among them
    assert flops_sdar.layer_bytes_outside_experts(m) == 38_273_024
    assert flops_sdar.expert_bytes(m) == 9_437_184
    assert flops_sdar.kv_bytes_per_token(m) == 12_288
    assert flops_sdar.dispatch_bytes(m, 1, 600, 1, 16 * 260) \
        == 6 * 38_273_024 + 600 * 9_437_184 + 622_329_856 + 4160 * 12_288
    # a dispatch in which every sequence commits reads no head
    assert flops_sdar.dispatch_bytes(m, 1, 600, 0, 16 * 260) \
        == 6 * 38_273_024 + 600 * 9_437_184 + 4160 * 12_288


def test_the_configuration_holds_the_catalog_rows_numbers():
    conf = harness.load_json(harness.HERE, "configs", "sdar_30b_a3b.json")
    published = {"head_dim": 128, "hidden_size": 2048,
                 "intermediate_size": 6144, "moe_intermediate_size": 768,
                 "num_attention_heads": 32, "num_experts": 128,
                 "num_experts_per_tok": 8, "num_key_value_heads": 4,
                 "rms_norm_eps": 1e-06, "rope_theta": 1000000,
                 "vocab_size": 151936, "decoder_sparse_step": 1,
                 "max_window_layers": 48}
    assert {k: conf[k] for k in published} == published
    assert conf["reduced"] == ["num_hidden_layers",
                               "max_position_embeddings"]
    assert (conf["num_hidden_layers"], conf["max_position_embeddings"]) \
        == (6, 2048)
    m = conf["model"]
    assert (m["num_layers"], m["num_heads"], m["num_kv_heads"],
            m["head_dim"], m["layer_norm_epsilon"]) == (
        conf["num_hidden_layers"], conf["num_attention_heads"],
        conf["num_key_value_heads"], conf["head_dim"],
        conf["rms_norm_eps"])
    for key in ("hidden_size", "vocab_size", "num_experts",
                "num_experts_per_tok", "moe_intermediate_size",
                "intermediate_size", "max_position_embeddings"):
        assert m[key] == conf[key], key
    assert m["tie_word_embeddings"] is conf["tie_word_embeddings"] is False
    assert m["block_attention"] == harness.load_json(
        harness.HERE, "traffic", "serve_bd4_closed16.json")[
        "block_diffusion"]["block_length"]
