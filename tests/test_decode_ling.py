"""`DecodeEngine` over the Ling-flash model (ISSUE 37): leading dense layers
and the period of five per-channel delta-rule layers to one latent-attention
layer, three kinds of cache entry in one paged pool (state slots, one latent
row a token, none with keys and values), expert layers that hold a share of
the router's experts, against the plain reference's full forward on logits;
the counters and span attributes it brought.
"""
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import weights_ling  # noqa: E402
from benchmarks.reference import ling_ref as ref  # noqa: E402
from paddle_tpu.inference import DecodeEngine, ServingPool  # noqa: E402
from paddle_tpu.models import linear_attention as la  # noqa: E402
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM  # noqa: E402
from paddle_tpu.obs import flight  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "configs",
                       "ling_3p0_flash.json")) as f:
    _CONF = json.load(f)
MODEL = {**_CONF["model"], **_CONF["rehearsal"], "initializer_range": 0.1}
GEO = dict(max_length=160, block_size=16, decode_buckets=(1, 2, 4),
           prefill_buckets=(16, 32), prefill_chunk=32, default_timeout=120.0)
LIN, LATENT, EXPERT, HELD, TOP_K = 5, 1, 4, 4, 8


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def weights():
    return weights_ling.make(MODEL, 2147483659, "float32")


def build(weights, **more):
    net = GPTForCausalLM(GPTConfig(**{**MODEL, **more}))
    net.eval()
    for n, p in net.named_parameters():
        p._value = weights[n]
    return net


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, MODEL["vocab_size"], n, dtype=np.int32)
            for n in lengths]


def worst_gap(weights, prompt, tokens, choices=None):
    """How far the reference's logit of a served token lies under its best,
    the worst over the tokens: the reference's full forward of prompt +
    tokens, one position a served token. `choices` gains the reference's
    (local, made) expert choices a layer."""
    seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    rows = np.arange(len(prompt) - 1, len(seq))
    lg = np.asarray(ref.served_logits(weights, seq, rows, MODEL,
                                      choices=choices))
    return float(np.max(lg.max(-1) - lg[np.arange(len(tokens)), tokens]))


def test_chunked_prefill_then_decode_agree_with_the_reference(weights):
    """Five requests over three batch slots: prompts under a bucket, of a
    whole chunk, and of several chunks with a padded tail; the batch shrinks
    and refills through every bucket, and slots are reused after a finished
    sequence."""
    t0 = time.perf_counter()
    eng = DecodeEngine(build(weights), **GEO)
    pool = ServingPool(decode_engine=eng, default_timeout=120.0)
    ps = prompts((5, 32, 100, 33, 71))
    news = (14, 9, 12, 20, 6)
    streams = [pool.submit_generate(p, n) for p, n in zip(ps, news)]
    outs = [np.asarray(s.result()) for s in streams]
    st = eng.stats()
    assert [len(o) for o in outs] == list(news)
    local = made = 0
    for p, o in zip(ps, outs):
        counted = []
        assert worst_gap(weights, p, o, counted) <= 1e-4
        local += sum(c[0] for c in counted)
        made += sum(c[1] for c in counted)
    # the three kinds of cache, counted
    assert st["lin_layers"] == LIN
    assert st["lin_chunk_tokens"] == sum(len(p) for p in ps)
    assert st["lin_step_tokens"] == sum(news) - len(news)
    assert st["lin_state_slots"] == 0 and st["kv_blocks_in_use"] == 0
    assert st["mla_rows_in_use"] == 0
    # a latent row: 32 + 8 float32 values over one layer
    assert st["mla_row_bytes"] == LATENT * 40 * 4
    assert st["prefill_chunks"] == 1 + 1 + 4 + 2 + 3
    assert st["prefix_cache"]["enabled"] is False
    # the expert layers' choices: every position that went through a layer
    # (the prompts and every served token but a request's last) chose
    # TOP_K experts in each of the EXPERT layers, and those that fell on
    # the HELD experts are the ones the reference counts, one for one
    positions = sum(len(p) for p in ps) + sum(news) - len(news)
    assert made == positions * TOP_K * EXPERT
    assert st["moe_choices_total"] == made
    assert st["moe_choices_local"] == local
    assert st["moe_experts_held"] == HELD
    counts = np.asarray(st["moe_expert_tokens"])
    assert counts.shape == (EXPERT, HELD) and counts.sum() == local
    assert 0 < local < made / 2
    dispatches = st["steps"] + st["prefill_chunks"]
    assert st["moe_layer_dispatches"] == dispatches * EXPERT
    assert 0 < st["moe_chunk_distinct_experts"] \
        <= st["moe_distinct_experts"] <= dispatches * EXPERT * HELD
    assert st["moe_expert_reads"] >= st["moe_distinct_experts"]
    # every dispatch took the pool donated and none rebuilt it
    assert st["pool_rebuilds"] == 0
    assert st["donated_dispatches"] >= dispatches
    pool.shutdown()
    eng.shutdown()
    blocks = eng.stats()["blocks"]
    assert blocks["state_slots"] == 0 and blocks["allocated"] == 0
    assert blocks["state_slot_allocs"] == blocks["state_slot_frees"] == 5
    # the spans carry the layers of each kind they ran
    spans, _ = flight.recorder().spans_between(t0, time.perf_counter(),
                                               "decode.")
    steps = [s for s in spans if s.name == "decode.step"]
    chunks = [s for s in spans if s.name in ("decode.prefill",
                                             "decode.prefill_chunk")]
    assert steps and len(chunks) == st["prefill_chunks"]
    assert all(s.attrs["recurrent_layers"] == LIN
               and s.attrs["latent_layers"] == LATENT
               for s in steps + chunks)


def test_a_live_engine_counts_latent_rows_beside_slots(weights):
    eng = DecodeEngine(build(weights), **GEO)
    seen = []
    hook_eng = {}

    def hook(kind, ids, info):
        if kind == "decode" and info["bucket"] == 2:
            st = hook_eng["eng"].stats()
            seen.append((st["lin_state_slots"], st["mla_rows_in_use"],
                         st["kv_blocks_in_use"]))

    eng._fault_hook = hook
    hook_eng["eng"] = eng
    streams = [eng.submit(p, 8) for p in prompts((20, 40), seed=1)]
    for s in streams:
        s.result()
    assert seen
    slots, rows, blocks = max(seen)
    # two sequences: two slots; 20 + 40 tokens and their answers in blocks
    # of 16 rows
    assert slots == 2 and rows == blocks * 16 and 5 <= blocks <= 7
    # a slot: 5 layers x (a [4, 16, 16] float32 state + a [3, 192] window)
    assert eng.pool.slot_bytes == LIN * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    eng.shutdown()


def test_a_sequence_in_a_bucket_is_the_sequence_alone(weights):
    """Padded slots of a bucket are routed like any token and counted
    nowhere: one sequence in a bucket of 4 counts what it counts alone."""
    prompt, = prompts((37,), seed=4)
    alone = DecodeEngine(build(weights), **dict(GEO, decode_buckets=(1,)))
    out = alone.generate(prompt, 10)
    st1 = alone.stats()
    alone.shutdown()
    wide = DecodeEngine(build(weights), **dict(GEO, decode_buckets=(4,)))
    assert wide.generate(prompt, 10) == out
    st4 = wide.stats()
    wide.shutdown()
    for key in ("moe_choices_total", "moe_choices_local",
                "moe_expert_tokens", "moe_distinct_experts"):
        assert st1[key] == st4[key], key
    assert st1["moe_choices_total"] == (37 + 9) * TOP_K * EXPERT


@pytest.mark.parametrize("fault", ["mean_decay", "no_bias", "no_groups"])
def test_the_planted_faults_are_seen(weights, fault, monkeypatch, tmp_path):
    """The faults of `benchmarks/calibrate_kda.py --fault`, planted in the
    program: the comparison with the reference sees each."""
    import importlib.util

    from paddle_tpu.jit.aot import CompileCache

    spec = importlib.util.spec_from_file_location(
        "cal_kda_for_test", os.path.join(ROOT, "benchmarks",
                                         "calibrate_kda.py"))
    cal = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cal)
    from paddle_tpu.models import moe

    monkeypatch.setattr(la, "_split", la._split)     # restored afterwards
    monkeypatch.setattr(moe, "route", moe.route)
    cal.plant_fault(fault)
    # the layers' impls are traced once a process and shape: neither may
    # the sound trace serve the faulty program nor the faulty one a later
    # test
    jax.clear_caches()
    try:
        eng = DecodeEngine(build(weights), **GEO,
                           compile_cache=CompileCache(str(tmp_path)))
        prompt, = prompts((100,), seed=3)
        out = np.asarray(eng.generate(prompt, 24))
        eng.shutdown()
    finally:
        jax.clear_caches()
    assert worst_gap(weights, prompt, out) > 1e-3


@pytest.mark.parametrize("option,value", [
    ("prefix_cache", True),
    ("speculate_k", 2),
    ("quant", "int8"),
    ("adapters", "pool"),
])
def test_what_a_recurrent_model_refuses_stays_refused(weights, option,
                                                      value):
    with pytest.raises(ValueError, match="recurrent"):
        DecodeEngine(build(weights), **GEO,
                     **{option: object() if value == "pool" else value})
