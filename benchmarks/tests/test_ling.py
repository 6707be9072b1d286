"""The cell `ling_3p0_flash.serve_reason_closed32` at its rehearsal sizes:
the reference against hand-computed tiny cases, a sound run correct under
the rehearsal limits, the float8 control and the two planted faults (a
head's decay as its mean over the key channels; the router without its
selection bias) not, `BENCHMARK.json` naming files that are there, the
configuration holding the catalog row's numbers, and the counts of
`flops_ling.py` against hand-worked values.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops_ling as fl
from benchmarks import harness
from benchmarks.reference import ling_ref as ref

CELL = "ling_3p0_flash.serve_reason_closed32"
KDA_METRICS = {"serve_kda.step_mfu", "serve_kda.decode_roofline",
               "serve_kda.chunk_roofline", "serve_kda.state_mb_per_sequence",
               "serve_kda.expert_load_max_over_mean",
               "serve_kda.local_choice_share"}


def run_cell(capsys, seconds=3, trace=0):
    run = harness.load_module(os.path.join(harness.HERE, "run.py"),
                              "bench_run_kda")
    assert run.main(["--workload", CELL, "--seed", str(2 ** 31 + 41),
                     "--seconds", str(seconds), "--trace", str(trace)]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture
def sound_traces_afterwards():
    """A planted fault is traced into this process's layers: drop those
    traces once the test is over."""
    from paddle_tpu.models import linear_attention as la
    from paddle_tpu.models import moe

    split, route = la._split, moe.route
    yield
    la._split, moe.route = split, route
    jax.clear_caches()


def calibrate(capsys, *more):
    cal = harness.load_module(
        os.path.join(harness.HERE, "calibrate_kda.py"), "cal_kda")
    seeds = [2 ** 31 + 43, 2 ** 31 + 47, 5]
    assert cal.main(["--workload", CELL, "--seeds",
                     ",".join(map(str, seeds)), "--seconds", "3",
                     *more]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# ---- the reference against hand-computed cases ----------------------------

def test_the_recurrence_by_hand():
    """One head, d_k = 2, d_v = 1, two positions, worked on paper:
    S_0 = 0; g_1 = (log 1/2, 0), k_1 = (1, 0), v_1 = 2, beta_1 = 1:
      S_1 = (2, 0); o_1 = S_1 q_1 = 2 with q_1 = (1, 1).
    g_2 = (log 1/2, log 1/4), k_2 = (0.6, 0.8), v_2 = 1, beta_2 = 0.5:
      S' = (1, 0); S' k_2 = 0.6; err = 0.4; S_2 = (1, 0) + 0.5 x 0.4 x
      (0.6, 0.8) = (1.12, 0.16); o_2 = S_2 q_2 = 1.12 - 0.16 with
      q_2 = (1, -1)."""
    q = jnp.array([[[1.0, 1.0]], [[1.0, -1.0]]])
    k = jnp.array([[[1.0, 0.0]], [[0.6, 0.8]]])
    v = jnp.array([[[2.0]], [[1.0]]])
    g = jnp.log(jnp.array([[[0.5, 1.0]], [[0.5, 0.25]]]))
    beta = jnp.array([[1.0], [0.5]])
    o = np.asarray(ref.delta_rule(q, k, v, g, beta))
    np.testing.assert_allclose(o[:, 0, 0], [2.0, 0.96], rtol=1e-6)
    # the decay of the SECOND key channel took no part (S' there was 0):
    # with the decay as its mean over the channels the answer differs
    mean = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    assert abs(float(ref.delta_rule(q, k, v, mean, beta)[1, 0, 0])
               - 0.96) > 0.05


ROUTER = dict(num_experts=8, num_experts_per_tok=2, moe_n_group=4,
              moe_topk_group=2, norm_topk_prob=True,
              routed_scaling_factor=2.0)


def test_the_router_by_hand():
    """8 experts in 4 groups of 2, the best 2 groups kept, 2 a token. A
    router whose logits are the input itself; sigmoid scores s:
      logits (2, 0, 1, 1, -1, 3, 0, 0) -> group sums of s: group 0
      0.881 + 0.5, group 1 2 x 0.731, group 2 0.269 + 0.953, group 3
      2 x 0.5: groups 1 (1.462) and 0 (1.381) are kept, and expert 5, the
      single best, is out with its group. Chosen: expert 0 (0.881) and
      expert 2 (0.731, the lower index of the tie with 3); weights
      s / (0.881 + 0.731) x 2.
    A bias of +0.5 on expert 4 lifts group 2 (1.722) over group 0: groups 2
    and 1 are kept, the choice is 5 and 4 (0.953, 0.269 + 0.5), and the
    weights are the UNBIASED scores: (0.953, 0.269) / 1.222 x 2."""
    h = jnp.array([2.0, 0, 1, 1, -1, 3, 0, 0])
    eye = jnp.eye(8)
    s = np.asarray(jax.nn.sigmoid(h))
    idx, w = ref.route(h, eye, jnp.zeros(8), ROUTER)
    assert idx.tolist() == [0, 2]
    np.testing.assert_allclose(w, 2 * s[[0, 2]] / (s[0] + s[2]), rtol=1e-6)
    idx, w = ref.route(h, eye, jnp.zeros(8).at[4].set(0.5), ROUTER)
    assert idx.tolist() == [5, 4]
    np.testing.assert_allclose(w, 2 * s[[5, 4]] / (s[5] + s[4]), rtol=1e-6)
    # without the group limit the single best expert is chosen
    idx, _ = ref.route(h, eye, jnp.zeros(8), ROUTER, fault="no_groups")
    assert idx.tolist() == [5, 0]
    # without the bias, the biased router's choice is the unbiased one
    idx, _ = ref.route(h, eye, jnp.zeros(8).at[4].set(0.5), ROUTER,
                       fault="no_bias")
    assert idx.tolist() == [0, 2]


def test_absent_experts_add_nothing_by_hand():
    """Two tokens, 4 experts of width 1 whose SwiGLU is silu(a h) x b h x
    c, 2 a token: the chip that holds experts 2-3 adds only what the
    choices that fall on them give."""
    model = dict(num_experts=4, num_experts_per_tok=2, moe_n_group=2,
                 moe_topk_group=2, norm_topk_prob=False,
                 routed_scaling_factor=1.0, experts_held=[2, 2])
    h = jnp.array([[1.0, 0.0], [0.0, 1.0]])
    lp = {"mlp.router.weight": jnp.array([[3.0, 0, 1, -3], [-3, 2, 0, 3.0]]),
          "mlp.router_bias": jnp.zeros(4),
          # held experts 2 and 3: gate_up [2, hidden 2, 2], down [2, 1, 2]
          "mlp.experts_gate_up": jnp.array([[[1.0, 2.0], [0, 0]],
                                            [[0, 0], [1.0, 3.0]]]),
          "mlp.experts_down": jnp.array([[[1.0, 1.0]], [[2.0, 0.0]]])}
    from benchmarks.reference.gpt_ref import _mm

    y, idx = ref.experts(h, lp, model, _mm(False))
    # token 0 chooses experts 0 and 2 (3, 1 beat 0, -3): only 2 is here,
    # weight sigmoid(1): silu(1) x 2 x (1, 1); token 1 chooses 3 and 1:
    # only 3 is here, weight sigmoid(3): silu(1) x 3 x (2, 0)
    assert idx.tolist() == [[0, 2], [3, 1]]
    silu1 = float(jax.nn.silu(1.0))
    s1, s3 = float(jax.nn.sigmoid(1.0)), float(jax.nn.sigmoid(3.0))
    np.testing.assert_allclose(
        y, [[s1 * silu1 * 2, s1 * silu1 * 2], [s3 * silu1 * 6, 0.0]],
        rtol=1e-6)


def test_latent_attention_by_hand():
    """One head, latent 1, d_n = d_v = 1, d_r = 2, two positions, identity
    norms: position 0 attends to itself alone (o = v_0 x gate); position 1
    weighs both by softmax of (q_n k_n + q_r . rot(k_r)) / sqrt(3)."""
    model = dict(num_heads=1, kv_lora_rank=1, qk_nope_head_dim=1,
                 qk_rope_head_dim=2, v_head_dim=1, layer_norm_epsilon=0.0,
                 rope_theta=10000.0)
    x = jnp.array([[1.0, 0.0], [0.0, 2.0]])
    lp = {"attn.q_proj.weight": jnp.array([[1.0, 0, 0], [0, 1.0, 0]]),
          "attn.q_norm.weight": jnp.ones(3),
          "attn.kv_a_proj.weight": jnp.array([[2.0, 0, 0], [1.0, 1.0, 0]]),
          "attn.kv_norm.weight": jnp.ones(1),
          "attn.kv_b_proj.weight": jnp.array([[1.0, 3.0]]),
          "attn.gate_proj.weight": jnp.zeros((2, 1)),
          "attn.out_proj.weight": jnp.array([[1.0, 0.0]])}
    from benchmarks.reference.gpt_ref import _mm

    y = np.asarray(ref.latent_attention(x, lp, model, _mm(False)))
    # RMSNorm over one latent value is its sign: c = (1, 1), so k_n = 1 and
    # v = 3 at both positions; the gate is sigmoid(0) = 1/2. Position 0:
    # o = 3 / 2. Position 1: both values are 3, so whatever the weights,
    # o = 3 / 2 again; the output projection keeps channel 0.
    np.testing.assert_allclose(y, [[1.5, 0.0], [1.5, 0.0]], rtol=1e-6)
    # the rotation: at position 1 the key's rotated pair (2, 0) turns by
    # one radian in the first frequency
    r = np.asarray(ref._rotate(jnp.array([[0.0, 0.0], [2.0, 0.0]]), 1e4))
    np.testing.assert_allclose(r[1], [2 * np.cos(1.0), 2 * np.sin(1.0)],
                               rtol=1e-6)


# ---- the cell at its rehearsal sizes ---------------------------------------

def test_the_rehearsal_is_correct_under_its_limits(capsys):
    line = run_cell(capsys, trace=1)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 8
    assert set(line["compared"]) == {
        "token_gap", "token_gap_mean", "tokens_compared", "short_answers",
        "compiles_in_window"}
    # no device on the CPU: the readers of the device's trace find nothing
    # and stay silent, the counters' readers read
    assert set(line["metrics"]) == {
        "serve.decode_occupancy", "serve_kda.state_mb_per_sequence",
        "serve_kda.expert_load_max_over_mean",
        "serve_kda.local_choice_share"}
    per_seq = line["metrics"]["serve_kda.state_mb_per_sequence"]["value"]
    # a slot of 5 layers x (4 x 16 x 16 float32 + 3 x 192 bfloat16) is
    # 0.0262 MB; the latent rows of 1 layer (40 bfloat16 a token) on top
    assert 0.0262 < per_seq < 0.04
    # one routing group of eight is held: near an eighth of the choices
    share = line["metrics"]["serve_kda.local_choice_share"]["value"]
    assert 5.0 < share < 25.0
    assert line["metrics"]["serve_kda.expert_load_max_over_mean"][
        "value"] >= 1.0
    # the gap between tokens is computed and logged, and no metric
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
    last = calibrate(capsys, "--controls", "float8,mean_decay")
    assert last["verdicts"]["program"] == "0 of 3 seeds not correct", last
    assert last["verdicts"]["control_float8"] == "3 of 3 seeds not correct"
    assert last["verdicts"]["control_mean_decay"] \
        == "3 of 3 seeds not correct"
    assert set(last["readings"]) == {"token_gap", "token_gap_mean"}


@pytest.mark.parametrize("fault,by", [
    ("mean_decay", "token_gap"),      # the scalar rule under the new name
    ("no_bias", "token_gap_mean"),    # the router without its bias
    ("no_groups", "token_gap_mean"),  # ... without its limit to 4 groups
])
def test_a_planted_fault_is_not_correct(capsys, fault, by,
                                        sound_traces_afterwards):
    last = calibrate(capsys, "--fault", fault, "--controls", "")
    assert last["verdicts"] == {
        "fault_" + fault: "3 of 3 seeds not correct"}, last
    assert last["readings"][by]["fault_" + fault][0] > last["limits"][by]


def test_benchmark_json_names_files_that_are_there():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    assert cell == {**cell, "config": "ling_3p0_flash", "chips": 1,
                    "traffic": "serve_reason_closed32"}
    assert conf["file"] == "benchmarks/configs/ling_3p0_flash.json"
    doc = harness.load_json(harness.ROOT, conf["file"])
    assert conf["source"] == doc["source"]
    assert conf["reduced"] == doc["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "max_position_embeddings"]
    mix = harness.load_json(harness.HERE, "traffic",
                            cell["traffic"] + ".json")
    for path in (("drivers", mix["driver"] + ".py"),
                 ("limits", CELL + ".json"), ("reference", "ling_ref.py"),
                 ("weights_ling.py",), ("flops_ling.py",),
                 ("calibrate_kda.py",)):
        assert os.path.exists(os.path.join(harness.HERE, *path)), path
    resolved = harness.resolve_cell(CELL, rehearsal=False)
    names = {m["name"] for m in harness.metrics_for(resolved, "per_layer")}
    assert KDA_METRICS <= names
    assert names - KDA_METRICS == {
        "serve.decode_occupancy", "serve.device_idle_share",
        "serve.peak_hbm_gb", "serve.reserved_hbm_gb"}
    for name in names:
        assert os.path.exists(os.path.join(harness.HERE, "layers",
                                           name + ".py")), name
    for m in bench["per_layer"]:
        if m["name"] in KDA_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
    # on the rate and the set-up alone: the gap between tokens is logged
    assert {m["name"] for m in harness.metrics_for(resolved, "end_to_end")} \
        == {"serve_tokens_per_s", "setup_s"}
    # appended, nothing before them moved
    assert bench["configs"][-1]["name"] == "ling_3p0_flash"
    assert bench["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in bench["per_layer"][-6:]] == [
        "serve_kda.step_mfu", "serve_kda.decode_roofline",
        "serve_kda.chunk_roofline", "serve_kda.state_mb_per_sequence",
        "serve_kda.expert_load_max_over_mean",
        "serve_kda.local_choice_share"]


def test_the_configuration_holds_the_catalog_rows_numbers():
    conf = harness.load_json(harness.HERE, "configs", "ling_3p0_flash.json")
    published = {
        "hidden_size": 2560, "intermediate_size": 6144,
        "first_k_dense_replace": 2, "moe_intermediate_size": 768,
        "num_experts_per_tok": 8, "num_attention_heads": 32,
        "q_lora_rank": None, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128,
        "num_key_value_heads": 32, "rope_theta": 6000000,
        "rms_norm_eps": 1e-06, "head_dim": 128,
        "partial_rotary_factor": 0.5, "moe_router_enable_expert_bias": True,
        "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4,
        "use_qk_norm": True, "score_function": "sigmoid",
        "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
        "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
        "linear_silu": True, "rotary_dim": 64, "use_mla_nope": False,
        "short_conv_kernel_size": 4, "kda_safe_gate": True,
        "kda_lower_bound": -5, "norm_topk_prob": True,
        "no_kda_lora": True, "image_patch_token": 157157}
    assert {k: conf[k] for k in published} == published
    assert conf["expert_swiglu_limit_list"] == [0] * 35 + [4] * 7
    assert conf["share_expert_swiglu_limit_list"] == [0] * 34 + [5] * 6 \
        + [7] * 2
    assert {k: conf["published"][k] for k in conf["reduced"]} == {
        "num_hidden_layers": 42, "num_experts": 512, "vocab_size": 157184,
        "max_position_embeddings": 131072}
    assert "eight chips share each layer" in conf["published"]["deployment"]
    assert [conf[k] for k in conf["reduced"]] == [12, 64, 19648, 2048]
    # the floors of a cut: two whole periods, ten layers after the dense
    # ones, 64 routed experts, an eighth of the vocabulary
    assert conf["vocab_size"] * 8 == 157184
    m = conf["model"]
    assert (m["num_layers"], m["vocab_size"], m["max_position_embeddings"],
            m["experts_held"]) == (12, 19648, 2048, [0, 64])
    # the router keeps its published width, groups and choices a token
    assert (m["num_experts"], m["moe_n_group"], m["moe_topk_group"],
            m["num_experts_per_tok"]) == (512, 8, 4, 8)
    assert m["layer_pattern"] == ["linear_attention"] * 5 \
        + ["latent_attention"]
    assert len(m["layer_pattern"]) == conf["layer_group_size"]
    for key, same in (("hidden_size", "hidden_size"),
                      ("intermediate_size", "intermediate_size"),
                      ("moe_intermediate_size", "moe_intermediate_size"),
                      ("kv_lora_rank", "kv_lora_rank"),
                      ("qk_nope_head_dim", "qk_nope_head_dim"),
                      ("qk_rope_head_dim", "qk_rope_head_dim"),
                      ("v_head_dim", "v_head_dim"),
                      ("first_k_dense", "first_k_dense_replace"),
                      ("linear_key_head_dim", "head_dim"),
                      ("linear_value_head_dim", "head_dim"),
                      ("linear_num_heads", "num_attention_heads"),
                      ("linear_conv_kernel_dim", "short_conv_kernel_size"),
                      ("linear_gate_lower_bound", "kda_lower_bound"),
                      ("routed_scaling_factor", "routed_scaling_factor"),
                      ("rope_theta", "rope_theta"),
                      ("layer_norm_epsilon", "rms_norm_eps"),
                      ("moe_shared_expert_intermediate_size",
                       "moe_shared_expert_intermediate_size")):
        assert m[key] == conf[same], key
    for key in ("assumed", "precision", "deployment", "rehearsal",
                "layers"):
        assert conf[key], key
    # the weights the benchmark draws are the parameters counted: 9.47 GB
    from benchmarks import weights_ling

    total = sum(int(np.prod(shape)) for shape, _, _ in
                weights_ling.shapes(m).values())
    assert round(total / 1e9, 2) == 4.74
    assert abs(total - fl.total_params(m)) < 1e6      # norms, A, dt, bias


def test_the_engine_takes_every_prompt_of_the_mix_at_the_served_size():
    cell = harness.resolve_cell(CELL, rehearsal=False)
    mix, geo = cell["mix"], cell["mix"]["engine"]
    assert geo["prefill_chunk"] == max(geo["prefill_buckets"])
    rows = mix["prompt_len"]["max"] + mix["output_len"]["max"]
    assert rows <= max(mix["check_pad"]) <= geo["max_length"]
    assert max(geo["decode_buckets"]) == mix["arrival"]["clients"] == 32
    assert geo["decode_buckets"] == [1, 2, 4, 8, 16, 32]
    serve = harness.load_module(
        os.path.join(harness.HERE, "drivers", "serve.py"), "d_serve_kda")
    warm = serve.warm_requests(mix, cell["model"]["vocab_size"], 1)
    # the warm traffic walks every prefill bucket and a second chunk, with
    # ids from the vocabulary's slice
    assert {len(r["prompt"]) for r in warm} == {64, 128, 256, 320}
    assert max(int(r["prompt"].max()) for r in warm) < 19648


def test_counts_agree_with_hand_worked_values_ling():
    m = harness.load_json(harness.HERE, "configs",
                          "ling_3p0_flash.json")["model"]
    mp = fl.matmul_params(m)
    # KDA: q, k, v 2560 x 12288; f and beta 2560 x (4096 + 32); the gate
    # and W_o 2 x 2560 x 4096. Latent: W_q 2560 x 32 x 192, W_kva 2560 x
    # 576, the gates 2560 x 32, W_o 4096 x 2560; W_kvb 512 x 32 x 256
    assert mp == {"linear": 62_996_480, "latent": 27_770_880,
                  "latent_kvb": 4_194_304, "dense_mlp": 47_185_920,
                  "router": 1_310_720, "shared": 5_898_240,
                  "expert": 5_898_240, "head": 50_298_880}
    assert fl.counts(m) == (10, 2, 10)
    fixed = 10 * 62_996_480 + 2 * 31_965_184 + 2 * 47_185_920 \
        + 10 * 7_208_960
    assert fl.fixed_params(m) == fixed == 860_356_608
    assert fl.total_params(m) == fixed + 2 * 50_298_880 \
        + 10 * 64 * 5_898_240
    # the rule at one position, one layer: 7 x 32 x 128 x 128 and the
    # convolution's 2 x 4 x 12288
    assert fl.rule_flops_per_position(m) == 3_670_016 + 98_304
    # one decode position at cache position 99 (100 rows a latent layer,
    # 2 x 32 x (1024 + 64) operations a row), 9 of its 80 choices here
    want = 2 * fixed + 10 * 3_768_320 + 2 * 69_632 * 100 \
        + 2 * 5_898_240 * 9 + 2 * 50_298_880
    assert fl.forward_flops(m, [99], 1, 9) == want
    # bytes: a sequence's state 10 x (32 x 128 x 128 x 4 + 3 x 12288 x 2),
    # a token's latent rows 2 x 576 x 2, an expert 11.8 MB
    assert fl.state_bytes_per_sequence(m) == 10 * (2_097_152 + 73_728)
    assert fl.latent_bytes_per_token(m) == 2304
    assert fl.expert_bytes(m) == 11_796_480
    assert fl.decode_bytes(m, 1, 32, 32 * 700, 250) \
        == 2 * (fixed + 50_298_880) + 250 * 11_796_480 \
        + 2 * 32 * fl.state_bytes_per_sequence(m) + 22400 * 2304
    assert fl.prompt_chunks(300, 256) == [(0, 256), (256, 44)]
    assert fl.chunk_bytes(m, 256, 44, 600) == 2 * (fixed + 50_298_880) \
        + 600 * 11_796_480 + 2 * fl.state_bytes_per_sequence(m) + 300 * 2304
