"""`DecodeEngine` over a model with recurrent layers (ISSUE 33): a state slot
a sequence beside the paged pool, chunked prefill that hands the state from
chunk to chunk, decode buckets that read and write it by slot, against the
plain reference's full forward; a freed slot zeroed for its next owner; the
options refused with such a model; the new counters and span attributes.
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

from benchmarks import weights_olmo_hybrid  # noqa: E402
from benchmarks.reference import olmo_hybrid_ref as ref  # noqa: E402
from paddle_tpu.inference import DecodeEngine, ServingPool  # noqa: E402
from paddle_tpu.inference.decode.block_pool import (  # noqa: E402
    BlockKVCache, OutOfBlocks)
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM  # noqa: E402
from paddle_tpu.obs import flight  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "configs",
                       "olmo_hybrid_7b.json")) as f:
    _CONF = json.load(f)
MODEL = {**_CONF["model"], **_CONF["rehearsal"], "initializer_range": 0.1}
GEO = dict(max_length=160, block_size=16, decode_buckets=(1, 2, 4),
           prefill_buckets=(16, 32), prefill_chunk=32, default_timeout=120.0)
LIN_LAYERS = 3


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def weights():
    return weights_olmo_hybrid.make(MODEL, 2147483659, "float32")


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


def worst_gap(weights, prompt, tokens):
    """How far the reference's logit of a served token lies under its best,
    the worst over the tokens: the reference's full forward of prompt +
    tokens, one position a served token."""
    seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    lg = np.asarray(ref.logits(weights, seq, MODEL))[len(prompt) - 1:]
    return float(np.max(lg.max(-1) - lg[np.arange(len(tokens)), tokens]))


def test_chunked_prefill_then_decode_agree_with_the_reference(weights):
    """Five requests over three batch slots: prompts under a bucket, of a
    whole chunk, and of several chunks with a padded tail; the batch shrinks
    and refills through every bucket, and slots are reused."""
    t0 = time.perf_counter()
    eng = DecodeEngine(build(weights), **GEO)
    pool = ServingPool(decode_engine=eng, default_timeout=120.0)
    ps = prompts((5, 32, 100, 33, 71))
    news = (14, 9, 12, 20, 6)
    streams = [pool.submit_generate(p, n) for p, n in zip(ps, news)]
    outs = [np.asarray(s.result()) for s in streams]
    st = eng.stats()
    assert [len(o) for o in outs] == list(news)
    for p, o in zip(ps, outs):
        assert worst_gap(weights, p, o) <= 1e-4
    # the two kinds of cache, counted
    assert st["lin_layers"] == LIN_LAYERS
    assert st["lin_chunk_tokens"] == sum(len(p) for p in ps)
    assert st["lin_step_tokens"] == sum(news) - len(news)
    assert st["lin_state_slots"] == 0 and st["kv_blocks_in_use"] == 0
    assert 3 <= st["lin_state_slots_peak"] <= 4
    assert st["prefill_chunks"] == 1 + 1 + 4 + 2 + 3
    assert st["prefix_cache"]["enabled"] is False
    pool.shutdown()
    eng.shutdown()
    blocks = eng.stats()["blocks"]
    assert blocks["state_slots"] == 0 and blocks["allocated"] == 0
    assert blocks["state_slot_allocs"] == blocks["state_slot_frees"] == 5
    # the spans carry the number of recurrent layers they ran
    spans, _ = flight.recorder().spans_between(t0, time.perf_counter(),
                                               "decode.")
    steps = [s for s in spans if s.name == "decode.step"]
    chunks = [s for s in spans if s.name in ("decode.prefill",
                                             "decode.prefill_chunk")]
    assert steps and len(chunks) == st["prefill_chunks"]
    assert all(s.attrs["recurrent_layers"] == LIN_LAYERS
               for s in steps + chunks)


def test_a_live_engine_counts_slots_and_bytes(weights):
    eng = DecodeEngine(build(weights), **GEO)
    seen = []
    hook_eng = {}

    def hook(kind, ids, info):
        if kind == "decode" and info["bucket"] == 2:
            seen.append(hook_eng["eng"].pool.slots_in_use)

    eng._fault_hook = hook
    hook_eng["eng"] = eng
    streams = [eng.submit(p, 8) for p in prompts((20, 40), seed=1)]
    for s in streams:
        s.result()
    assert seen and max(seen) == 2
    slot_bytes = eng.pool.slot_bytes
    # 3 layers x (a [4, 16, 8] float32 state + a [3, 128] window)
    assert slot_bytes == LIN_LAYERS * (4 * 16 * 8 * 4 + 3 * 128 * 4)
    assert eng.stats()["blocks"]["state_slot_bytes"] == slot_bytes
    eng.shutdown()


def test_a_freed_slot_is_zero_for_its_next_owner(weights):
    """One batch slot, so the second request takes the first's state slot:
    at its first chunk's dispatch the slot reads zero in every recurrent
    layer, and its tokens are those of a fresh engine."""
    geo = dict(GEO, decode_buckets=(1,))
    first, second = prompts((45, 38), seed=2)
    eng = DecodeEngine(build(weights), **geo)
    seen = {}

    def hook(kind, ids, info):
        if kind == "prefill" and info["start"] == 0:
            slot = eng.pool._slot_of[ids[0]]
            seen[ids[0]] = (slot, [
                float(abs(np.asarray(t[slot], np.float32)).max())
                for i, layer in enumerate(eng.pool.tensors)
                if eng.pool.slot_layers[i] for t in layer])

    eng._fault_hook = hook
    eng.generate(first, 10)
    slot = 1
    # what the first owner left behind is not zero
    left = [float(abs(np.asarray(t[slot], np.float32)).max())
            for i, layer in enumerate(eng.pool.tensors)
            if eng.pool.slot_layers[i] for t in layer]
    assert min(left) > 0
    out = eng.generate(second, 10)
    assert [v[0] for v in seen.values()] == [slot, slot]
    assert all(x == 0.0 for _, zeros in seen.values() for x in zeros)
    eng.shutdown()
    fresh = DecodeEngine(build(weights), **geo)
    assert fresh.generate(second, 10) == out
    fresh.shutdown()


def test_a_state_not_carried_across_a_chunk_boundary_is_seen(weights):
    """The planted fault of `benchmarks/calibrate_lin.py --fault carry`: the
    comparison with the reference sees it."""
    eng = DecodeEngine(build(weights), **GEO)
    sound = DecodeEngine._prefill_chunk

    def faulty(seq):
        if seq.prefill_pos > 0:
            eng._zero_slot(seq.slot)
        return sound(eng, seq)

    eng._prefill_chunk = faulty
    prompt, = prompts((100,), seed=3)
    out = np.asarray(eng.generate(prompt, 12))
    eng.shutdown()
    assert worst_gap(weights, prompt, out) > 0.05


@pytest.mark.parametrize("option,value", [
    ("prefix_cache", True),
    ("speculate_k", 2),
    ("draft_model", "self"),
    ("block_diffusion", {"block_length": 4, "denoising_steps": 2,
                         "mask_token_id": 1}),
    ("quant", "int8"),
    ("mesh", "mesh"),
    ("adapters", "pool"),
])
def test_options_that_need_rows_are_refused(weights, option, value):
    # (the block option is checked against the model's own mask first)
    net = build(weights, **({"block_attention": 4}
                            if option == "block_diffusion" else {}))
    if value == "self":
        value = net
    elif value == "mesh":
        from paddle_tpu import sharding

        value = sharding.cpu_mesh(tp=1)
    elif value == "pool":
        value = object()
    with pytest.raises(ValueError, match="recurrent"):
        DecodeEngine(net, **GEO, **{option: value})


def test_a_recurrent_draft_is_refused(weights):
    plain = GPTForCausalLM(GPTConfig(
        vocab_size=MODEL["vocab_size"], hidden_size=32, num_layers=2,
        num_heads=4, max_position_embeddings=160))
    with pytest.raises(ValueError, match="recurrent"):
        DecodeEngine(plain, **GEO, draft_model=build(weights), speculate_k=2)


def test_the_pools_slots():
    spec = (((16,), "float32", 2), ((16,), "float32", 2))
    state = (((3, 8), "float32"), ((2, 4, 4), "float32"))
    pool = BlockKVCache(4, 2, [state, spec], slot_layers=[True, False],
                        num_slots=2)
    assert [t.shape for t in pool.tensors[0]] == [(3, 3, 8), (3, 2, 4, 4)]
    assert [t.shape for t in pool.tensors[1]] == [(4, 2, 16)] * 2
    assert pool.slot_bytes == (3 * 8 + 2 * 4 * 4) * 4
    a, b = pool.alloc_slot("a"), pool.alloc_slot("b")
    assert (a, b) == (1, 2) and pool.alloc_slot("a") == 1     # slot 0 kept
    with pytest.raises(OutOfBlocks, match="state slots"):
        pool.alloc_slot("c")
    assert pool.free_slot("a") == 1 and pool.free_slot("a") is None
    assert pool.alloc_slot("c") == 1
    st = pool.stats()
    assert (st["state_slots"], st["state_slots_peak"],
            st["state_slots_total"]) == (2, 2, 2)
    with pytest.raises(ValueError, match="slots"):
        pool.shard_(None)
    with pytest.raises(ValueError, match="num_slots"):
        BlockKVCache(4, 2, [state], slot_layers=[True])
    plain = BlockKVCache(4, 2, [spec]).stats()
    assert plain["state_slots_total"] == plain["state_slots"] == 0


def test_a_prompt_longer_than_the_largest_bucket_is_chunked(weights):
    eng = DecodeEngine(build(weights), **GEO)
    assert eng.max_prompt == GEO["max_length"] - 1
    with pytest.raises(ValueError, match="max_length"):
        eng.submit(prompts((150,))[0], 20)
    eng.shutdown()
