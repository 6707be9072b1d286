"""SDAR-class serving (ISSUE 28): the sparse-expert block with its own
head size, RMSNorm on q and k and the block mask in `models/gpt.py`, the
expert layer in `models/moe.py`, and generation by diffusion over blocks in
`DecodeEngine(block_diffusion=...)`, each against the benchmark's plain
reference (`benchmarks/reference/sdar_ref.py`, the same file the benchmark
uses) on seeded float32 weights at the configuration's rehearsal sizes.
"""
import importlib
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from benchmarks import weights_sdar  # noqa: E402
from benchmarks.reference import sdar_ref  # noqa: E402
from paddle_tpu.inference import DecodeEngine, ServingPool  # noqa: E402
from paddle_tpu.inference.serving import RequestFailed  # noqa: E402
from paddle_tpu.models import moe  # noqa: E402
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM  # noqa: E402
from paddle_tpu.obs import flight, trace  # noqa: E402

# `paddle_tpu.models.gpt` the attribute is the factory function
gpt_mod = importlib.import_module("paddle_tpu.models.gpt")

with open(os.path.join(ROOT, "benchmarks", "configs",
                       "sdar_30b_a3b.json")) as f:
    _CONF = json.load(f)
# the rehearsal sizes; a wider initialiser than the served 0.02, so that
# the tiny model's answers are not one token repeated
MODEL = {**_CONF["model"], **_CONF["rehearsal"], "initializer_range": 0.1}
BL, STEPS, MASK = 4, 2, 255
BD = {"block_length": BL, "denoising_steps": STEPS, "mask_token_id": MASK}
GEO = dict(max_length=96, block_size=16, decode_buckets=(1, 2, 4, 8, 16),
           prefill_buckets=(16, 32, 64), prefill_chunk=16,
           default_timeout=120.0)


@pytest.fixture(scope="module")
def weights():
    return weights_sdar.make(MODEL, 2147483659, "float32")


def build(weights, model=MODEL):
    net = GPTForCausalLM(GPTConfig(**model))
    net.eval()
    for n, p in net.named_parameters():
        p._value = weights[n]
    return net


@pytest.fixture(scope="module")
def net(weights):
    return build(weights)


@pytest.fixture(scope="module")
def eng(net):
    e = DecodeEngine(net, block_diffusion=BD, **GEO)
    yield e
    e.shutdown()


def prompt_of(n, seed):
    ids = np.random.default_rng(seed).integers(0, MASK, n)
    return ids.astype(np.int32)


def reference(weights, prompt, n, model=MODEL):
    return sdar_ref.generate(weights, prompt, n, BL, STEPS, MASK, model,
                             pad_to=GEO["max_length"])


# ---- the model ------------------------------------------------------------

def test_whole_forward_under_the_block_mask_matches_reference(net, weights):
    ids = np.stack([prompt_of(24, 1), prompt_of(24, 2)])
    got = np.asarray(net(paddle.to_tensor(ids))._value)
    want = np.asarray(sdar_ref.logits(weights, ids, MODEL))
    assert got.shape == (2, 24, MODEL["vocab_size"])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_the_block_mask_is_part_of_the_forward(net, weights):
    """A block's first position sees its block's last token (and no later
    block's); under the plain causal mask of the same weights it sees
    neither."""
    ids = prompt_of(12, 3)[None]
    moved = ids.copy()
    moved[0, BL - 1] = (moved[0, BL - 1] + 1) % MASK
    later = ids.copy()
    later[0, BL] = (later[0, BL] + 1) % MASK
    causal = build(weights, {**MODEL, "block_attention": 0})

    def first(model, x):
        return np.asarray(model(paddle.to_tensor(x))._value)[0, 0]

    assert np.abs(first(net, ids) - first(net, moved)).max() > 1e-3
    np.testing.assert_array_equal(first(net, ids), first(net, later))
    np.testing.assert_array_equal(first(causal, ids), first(causal, moved))


@pytest.mark.parametrize("block", [0, BL], ids=["causal", "block_mask"])
@pytest.mark.parametrize("scaled", [False, True], ids=["plain", "int8_scales"])
def test_grouped_query_heads_attend_as_rows_of_their_kv_head(block, scaled):
    """8 query heads over 2 KV heads against the same core given K and V
    repeated a query head (its one-to-one path, the older program)."""
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(0, 1, (2, BL, 8, 16)).astype(np.float32))
    kk, vv = (jnp.asarray(rng.normal(0, 1, (2, 24, 2, 16)).astype(np.float32))
              for _ in "kv")
    scales = [jnp.asarray(rng.uniform(0.5, 2, (2, 24, 2)).astype(np.float32))
              for _ in "kv"] if scaled else [None, None]
    got = gpt_mod._cached_attn_core(q, kk, vv, 12, 8, *scales, block=block)
    want = gpt_mod._cached_attn_core(
        q, *(None if a is None else jnp.repeat(a, 4, axis=2)
             for a in (kk, vv)), 12, 8,
        *(None if a is None else jnp.repeat(a, 4, axis=2) for a in scales),
        block=block)
    assert got.shape == want.shape == (2, BL, 8, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=1e-6)


def test_head_dim_is_a_field_of_its_own():
    assert GPTConfig(hidden_size=64, num_heads=4).head_dim == 16
    cfg = GPTConfig(hidden_size=64, num_heads=4, num_kv_heads=2,
                    head_dim=32, num_layers=1, vocab_size=32)
    attn = gpt_mod.GPTAttention(cfg)
    assert attn.qkv_proj.weight.shape == [64, (4 + 2 * 2) * 32]
    assert attn.out_proj.weight.shape == [4 * 32, 64]
    pool = GPTForCausalLM(cfg).init_block_pool(4, 8)
    assert pool.tensors[0][0].shape == (4, 8, 2 * 32)


def test_decode_signature_names_what_no_shape_shows(net):
    assert gpt_mod.gpt("gpt_tiny").decode_signature() == ""
    assert gpt_mod.gpt("gpt_tiny", num_kv_heads=2).decode_signature() \
        == "gqa-rows"
    assert net.decode_signature().endswith(":gqa-rows")
    assert "block4" in net.decode_signature()


def test_lazy_guard_builds_no_initial_value(weights):
    with paddle.LazyGuard():
        lazy = GPTForCausalLM(GPTConfig(**MODEL))
    p = dict(lazy.named_parameters())["transformer.layers.0.mlp.experts_down"]
    assert type(p._v_).__name__ == "EngineRef"
    assert p.shape == list(weights[
        "transformer.layers.0.mlp.experts_down"].shape)
    p._value = weights["transformer.layers.0.mlp.experts_down"]
    assert isinstance(p._v_, jax.Array)
    q = lazy.transformer.ln_f.weight
    assert np.asarray(q._value).shape == (MODEL["hidden_size"],)  # built
    assert isinstance(q._v_, jax.Array)                           # once


# ---- the expert layer -----------------------------------------------------

def _layer_params(rng, h=16, m=8, n=6):
    return {"mlp.router.weight": rng.normal(0, 1, (h, n)).astype(np.float32),
            "mlp.experts_gate_up":
                rng.normal(0, 0.3, (n, h, 2 * m)).astype(np.float32),
            "mlp.experts_down":
                rng.normal(0, 0.3, (n, m, h)).astype(np.float32)}


def _run_layer(lp, x, k=2):
    lp = {name: jnp.asarray(v) for name, v in lp.items()}
    got, counts = moe._sparse_experts_impl(
        jnp.asarray(x), lp["mlp.router.weight"], lp["mlp.experts_gate_up"],
        lp["mlp.experts_down"], top_k=k, norm_topk=True)
    model = {"num_experts_per_tok": k, "norm_topk_prob": True}
    want = sdar_ref.experts(jnp.asarray(x), lp, model, sdar_ref._mm(False))
    return np.asarray(got), np.asarray(counts), np.asarray(want)


@pytest.mark.parametrize("positions", [1, 3, 40],
                         ids=["by_assignment_1", "by_assignment_3",
                              "by_expert_40"])
def test_expert_layer_matches_the_reference_loop(positions):
    rng = np.random.default_rng(positions)
    lp = _layer_params(rng)
    x = rng.normal(0, 1, (positions, 16)).astype(np.float32)
    got, counts, want = _run_layer(lp, x)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    assert counts.sum() == positions * 2 and counts.shape == (6,)


@pytest.mark.parametrize("positions", [2, 40])
def test_two_equal_router_scores_go_to_the_lower_expert(positions):
    """Experts 1 and 4 share a router column: wherever that score is the
    second best, expert 1 is taken and 4 is not, in both schedules, as the
    reference does."""
    rng = np.random.default_rng(7)
    lp = _layer_params(rng)
    lp["mlp.router.weight"][:, 4] = lp["mlp.router.weight"][:, 1]
    x = rng.normal(0, 1, (positions, 16)).astype(np.float32)
    got, counts, want = _run_layer(lp, x)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    idx, _ = sdar_ref.route(jnp.asarray(x), lp, {
        "num_experts_per_tok": 2, "norm_topk_prob": True},
        sdar_ref._mm(False))
    idx = np.asarray(idx)
    assert ((idx == 4).any(-1) <= (idx == 1).any(-1)).all()
    assert counts[1] >= counts[4]


@pytest.mark.parametrize("positions", [3, 40])
def test_an_expert_that_gets_no_token(positions):
    rng = np.random.default_rng(11)
    lp = _layer_params(rng)
    x = rng.normal(0, 1, (positions, 16)).astype(np.float32)
    # expert 2's score is the lowest at every position
    lp["mlp.router.weight"][:, 2] = 0.0
    x[:, 0] = 10.0
    lp["mlp.router.weight"][0, :] = 1.0
    lp["mlp.router.weight"][0, 2] = -100.0
    got, counts, want = _run_layer(lp, x)
    assert counts[2] == 0 and counts.sum() == positions * 2
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_expert_counts_are_collected_a_layer(net):
    ids = paddle.to_tensor(prompt_of(8, 5)[None])
    with moe.expert_counts() as counts:
        net(ids)
    assert len(counts) == MODEL["num_layers"]
    for c in counts:
        assert int(np.asarray(c).sum()) == 8 * MODEL["num_experts_per_tok"]
    assert getattr(moe._TLS, "counts", None) is None


# ---- the expert layer under vmap: a dispatch's positions -------------------

# Widest gap between a sequence's expert sum in a batch of 16 and the same
# sequence alone, as a share of the largest entry: float32 sums of k (2 to
# 8) weighted expert outputs taken in expert order against assignment
# order, each a float32 dot over 16 and 8 terms.
VMAP_TOLERANCE = 5e-6


def _eqn_shapes(jaxpr):
    """Shapes of every value an equation of `jaxpr` makes, sub-jaxprs
    (the scan, the loop, a jit) included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield eqn.primitive.name, tuple(getattr(v.aval, "shape", ()))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqn_shapes(sub)


def _copies_of_the_stack(fn, arg, stack):
    """Values that hold more than one expert's `[hidden, 2m]`: a batched
    `gate_up[e]`, a copy of each sequence's chosen experts (the stack
    itself is an input, not made; one expert sliced out of it is fine)."""
    return [(name, shape) for name, shape in _eqn_shapes(
        jax.make_jaxpr(fn)(arg).jaxpr)
        if shape[-2:] == tuple(stack.shape[-2:])
        and int(np.prod(shape[:-2])) > 1]


@pytest.mark.parametrize("n, k, schedule", [
    (128, 1, "_by_assignment"), (64, 4, "_by_expert"), (6, 2, "_by_expert")],
    ids=["64_of_128_by_assignment", "256_over_64_by_expert",
         "128_over_6_by_expert"])
def test_a_batch_of_sequences_is_more_positions_of_one_pass(
        monkeypatch, n, k, schedule):
    """16 sequences x 4 positions under `vmap`, the weights unbatched: the
    per-sequence call's values, one schedule chosen for the dispatch's 64
    positions, and no gather of the stack with a batch axis."""
    rng = np.random.default_rng(n + k)
    lp = {name: jnp.asarray(v)
          for name, v in _layer_params(rng, n=n).items()}
    x = jnp.asarray(rng.normal(0, 1, (16, BL, 16)).astype(np.float32))
    ran = []
    for name in ("_by_assignment", "_by_expert"):
        real = getattr(moe, name)
        monkeypatch.setattr(
            moe, name, lambda *a, _n=name, _f=real: ran.append(
                (_n, a[0].shape[0])) or _f(*a))

    def layer(h):
        return moe._sparse_experts_impl(
            h, lp["mlp.router.weight"], lp["mlp.experts_gate_up"],
            lp["mlp.experts_down"], top_k=k, norm_topk=True)

    together, counts = jax.vmap(layer)(x)
    # (traced once un-batched for its signature; the rule's trace runs)
    assert ran[-1] == (schedule, 16 * BL)
    alone = [layer(x[i]) for i in range(16)]
    want = np.stack([np.asarray(y) for y, _ in alone])
    assert np.abs(np.asarray(together) - want).max() \
        <= VMAP_TOLERANCE * np.abs(want).max()
    # the router is not folded: counts stay a sequence's own
    assert counts.shape == (16, n)
    np.testing.assert_array_equal(
        np.asarray(counts), np.stack([np.asarray(c) for _, c in alone]))
    assert (np.asarray(counts).sum(1) == BL * k).all()
    stack = lp["mlp.experts_gate_up"]
    assert _copies_of_the_stack(jax.vmap(layer), x, stack) == []
    if schedule == "_by_assignment":
        # what the rule is for: the same function under a plain `vmap`
        def plain(hs):
            idx, w, _ = jax.vmap(lambda h: moe.route(
                h, lp["mlp.router.weight"], k, True))(hs)
            return jax.vmap(lambda h, i, v: moe.apply_experts.fun(
                h, i, v, stack, lp["mlp.experts_down"]))(hs, idx, w)

        assert _copies_of_the_stack(plain, x, stack)


def test_sequences_under_vmap_in_a_vmap_fold_twice():
    rng = np.random.default_rng(3)
    lp = {name: jnp.asarray(v) for name, v in _layer_params(rng).items()}
    x = jnp.asarray(rng.normal(0, 1, (2, 3, BL, 16)).astype(np.float32))

    def layer(h):
        return moe._sparse_experts_impl(
            h, lp["mlp.router.weight"], lp["mlp.experts_gate_up"],
            lp["mlp.experts_down"], top_k=2, norm_topk=True)[0]

    got = np.asarray(jax.vmap(jax.vmap(layer))(x))
    want = np.asarray(jax.vmap(layer)(x.reshape(6, BL, 16))).reshape(got.shape)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)


def test_a_stack_of_its_own_a_sequence_is_not_shared():
    """Weights batched too (nobody serves so; the rule must not fold
    them): each sequence through its own stack."""
    rng = np.random.default_rng(4)
    lps = [_layer_params(rng) for _ in range(3)]
    x = rng.normal(0, 1, (3, BL, 16)).astype(np.float32)
    stacked = {name: jnp.stack([jnp.asarray(lp[name]) for lp in lps])
               for name in lps[0]}
    got, _ = jax.vmap(lambda h, r, g, d: moe._sparse_experts_impl(
        h, r, g, d, top_k=2, norm_topk=True))(
        jnp.asarray(x), stacked["mlp.router.weight"],
        stacked["mlp.experts_gate_up"], stacked["mlp.experts_down"])
    for i, lp in enumerate(lps):
        np.testing.assert_allclose(np.asarray(got[i]), _run_layer(lp, x[i])[0],
                                   atol=1e-5, rtol=1e-5)


# ---- the engine against the reference's generate --------------------------

@pytest.mark.parametrize("remainder", [0, 1, 2, 3])
def test_prompt_remainders_open_the_first_block(eng, weights, remainder):
    prompt = prompt_of(8 + remainder, 20 + remainder)
    got = eng.submit(prompt, 12).result(with_passes=True)
    want = reference(weights, prompt, 12)
    assert got == want
    # the first block starts with `remainder` positions fixed: it needs
    # ceil((4 - r) / 2) passes, every later block 2
    first = got[1][:BL - remainder]
    assert max(first) == -(-(BL - remainder) // (BL // STEPS))
    assert sorted(set(got[1][BL - remainder:])) == [1, 2]


def test_a_prompt_that_spans_two_chunks(eng, weights):
    prompt = prompt_of(37, 31)              # chunks of 16: 16 + 16 + 4 | 1
    before = eng.stats()["prefill_chunks"]
    got = eng.submit(prompt, 9).result(with_passes=True)
    assert eng.stats()["prefill_chunks"] - before == 3
    assert got == reference(weights, prompt, 9)


def test_a_prompt_shorter_than_a_block_has_no_prefill(eng, weights):
    prompt = prompt_of(3, 32)
    before = eng.stats()["prefill_chunks"]
    got = eng.submit(prompt, 6).result(with_passes=True)
    assert eng.stats()["prefill_chunks"] == before
    assert got == reference(weights, prompt, 6)


@pytest.mark.parametrize("max_new", [1, 5, 6, 7])
def test_max_new_that_ends_inside_a_block(eng, weights, max_new):
    prompt = prompt_of(10, 40 + max_new)
    tokens, passes = eng.submit(prompt, max_new).result(with_passes=True)
    assert len(tokens) == len(passes) == max_new
    assert (tokens, passes) == reference(weights, prompt, max_new)


def test_sixteen_sequences_in_different_phases(eng, weights):
    """Buckets 1-16: sequences admitted a round apart, with other prompt
    remainders, sit in other passes of their blocks in the same dispatch;
    each still gets the tokens and passes it gets alone."""
    trace.enable()
    trace.set_sample_rate(1.0)
    flight.recorder().reset()
    t0 = time.perf_counter()
    jobs = [(prompt_of(5 + 3 * i, 100 + i), 18 + (i % 5)) for i in range(16)]
    streams = [eng.submit(p, n) for p, n in jobs]
    outs = [s.result(with_passes=True) for s in streams]
    for (p, n), got in zip(jobs, outs):
        assert got == reference(weights, p, n)
    st = eng.stats()
    assert st["peak_resident"] == 16
    spans, _ = flight.recorder().spans_between(
        t0, time.perf_counter(), "decode.round.decode")
    rounds = [s for s in spans if s.name == "decode.round.decode"]
    assert rounds and all("denoise" in s.attrs and "commit" in s.attrs
                          for s in rounds)
    assert any(s.attrs["denoise"] and s.attrs["commit"] for s in rounds)
    assert max(s.attrs["denoise"] + s.attrs["commit"] for s in rounds) > 4


def test_counters_stop_equating_steps_with_tokens(net, weights):
    with DecodeEngine(net, block_diffusion=BD, **GEO) as e:
        tokens = e.submit(prompt_of(8, 50), 12).result()
        st = e.stats()
    assert len(tokens) == 12 and st["tokens_out"] == 12
    assert st["bd_blocks_committed"] == 3 and st["bd_forwards"] == 9
    assert st["bd_commit_forwards"] == 3 and st["bd_tokens_fixed"] == 12
    assert st["steps"] == 9 and st["step_active"] == 9
    assert st["bd_head_dispatches"] == 6
    # keys attended: blocks at 8, 12, 16, three forwards each
    assert st["bd_context_tokens"] == 3 * (12 + 16 + 20)
    counts = np.asarray(st["moe_expert_tokens"])
    assert counts.shape == (MODEL["num_layers"], MODEL["num_experts"])
    assert (counts.sum(1) == 9 * BL * MODEL["num_experts_per_tok"]).all()
    assert st["moe_layer_dispatches"] == 9 * MODEL["num_layers"]
    assert 0 < st["moe_distinct_experts"] <= 9 * MODEL["num_layers"] * 8
    # one sequence alone: 9 forwards at bucket 1, 4 x 2 assignments a layer
    assert st["moe_expert_reads"] == 9 * MODEL["num_layers"] * min(
        BL * MODEL["num_experts_per_tok"], MODEL["num_experts"])
    assert st["moe_expert_reads"] >= st["moe_distinct_experts"]
    assert st["moe_load_max_over_mean_sum"] >= st["moe_layer_dispatches"]
    assert st["block_diffusion"] == BD


def test_step_spans_name_block_and_pass(net):
    trace.enable()
    trace.set_sample_rate(1.0)
    flight.recorder().reset()
    t0 = time.perf_counter()
    with DecodeEngine(net, block_diffusion=BD, **GEO) as e:
        e.submit(prompt_of(8, 51), 8).result()
    spans, _ = flight.recorder().spans_between(t0, time.perf_counter(),
                                               "decode.step")
    steps = [s for s in spans if s.name == "decode.step"]
    assert [s.attrs["pass"] for s in steps] == [[1], [2], [0]] * 2
    assert [s.attrs["block"] for s in steps] == [[0]] * 3 + [[1]] * 3
    assert [(s.attrs["denoise"], s.attrs["commit"]) for s in steps] \
        == [(1, 0), (1, 0), (0, 1)] * 2


def test_a_wedged_step_is_retried_from_the_same_state(net, weights):
    """The block's state changes only after a dispatch has returned: a step
    that hangs past its timeout is dispatched again and nothing differs."""
    hung = []

    def hook(tag, ids, info):
        if tag == "decode" and not hung and info.get("denoise"):
            hung.append(ids)
            time.sleep(1.2)

    with DecodeEngine(net, block_diffusion=BD, fault_hook=hook,
                      step_timeout=0.4, step_retries=2, **GEO) as e:
        prompt = prompt_of(9, 60)
        got = e.submit(prompt, 10).result(with_passes=True)
        st = e.stats()
    assert hung and st["wedged_steps"] >= 1
    assert got == reference(weights, prompt, 10)


def test_a_failed_shared_step_reruns_alone_and_blames_one(net, weights):
    poisoned = []

    def hook(tag, ids, info):
        if tag == "decode" and poisoned and poisoned[0] in ids:
            raise RuntimeError("injected fault")

    with DecodeEngine(net, block_diffusion=BD, fault_hook=hook, **GEO) as e:
        gate = threading.Event()
        jobs = [(prompt_of(6 + i, 70 + i), 9) for i in range(3)]
        streams = [e.submit(p, n) for p, n in jobs]
        poisoned.append(streams[1].id)
        gate.set()
        outs = []
        for s in streams:
            try:
                outs.append(s.result(with_passes=True))
            except RequestFailed:
                outs.append(None)
        st = e.stats()
    assert outs[1] is None and st["failed"] == 1
    assert st["isolation_rounds"] >= 1
    for i in (0, 2):
        assert outs[i] == reference(weights, *jobs[i])


def test_the_prefix_cache_keeps_whole_blocks_of_the_prompt(net, weights):
    """A resubmitted prompt hits the cache (its whole blocks; the remainder
    is no part of the key), copies the shared tail block before its first
    commit writes into it, and answers as before."""
    with DecodeEngine(net, block_diffusion=BD, **GEO) as e:
        prompt = prompt_of(22, 80)           # 20 cached rows + remainder 2
        first = e.submit(prompt, 8).result(with_passes=True)
        other = np.concatenate([prompt[:20], prompt_of(3, 81)])
        second = e.submit(prompt, 8).result(with_passes=True)
        third = e.submit(other, 8).result(with_passes=True)
        st = e.stats()
    assert first == second == reference(weights, prompt, 8)
    assert third == reference(weights, other, 8)
    assert st["prefix_cache"]["full_hits"] == 2
    assert st["cow_copies"] >= 2
    assert st["blocks"]["allocated"] == st["prefix_cache"]["physical_blocks"]


def test_through_the_serving_pool(net, weights):
    e = DecodeEngine(net, block_diffusion=BD, **GEO)
    pool = ServingPool(decode_engine=e, default_timeout=120.0)
    try:
        prompt = prompt_of(13, 90)
        stream = pool.submit_generate(prompt, 7)
        assert list(stream) == reference(weights, prompt, 7)[0]
        assert stream.passes == reference(weights, prompt, 7)[1]
    finally:
        pool.shutdown()
        e.shutdown()


# ---- the step as one batched forward (ISSUE 31) ---------------------------
# `_bd_fn`'s executables called directly on a pool of random rows, so every
# block holds bytes that a stray write would change (the shape of
# tests/test_decode_batched_step.py).

# Widest gap between what a sequence gets in a bucket of 16 and the same
# sequence at bucket 1 (the other expert schedule: 128 assignments over 8
# experts against 8), as a share of the largest value compared. float32:
# sums of 2 expert outputs and of 64-term dots reordered, through two
# layers; bfloat16: one rounding (2**-8) of an activation carried through
# them. The CPU backend reads 6e-7 and 0 (the reordered float32 sums round
# to the same bfloat16 values there).
BUCKET_TOLERANCE = {"float32": 1e-5, "bfloat16": 3e-2}
LIVE, NB = 13, GEO["max_length"] // GEO["block_size"]


def _bd_engine(net, dtype="float32", **kw):
    if dtype != "float32":
        net = build({n: v.astype(jnp.dtype(dtype))
                     for n, v in weights_sdar.make(
                         MODEL, 2147483659, "float32").items()})
    return DecodeEngine(net, block_diffusion=BD,
                        num_blocks=1 + 16 * NB, **{**GEO, **kw})


def _random_pool(eng, seed):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    return [tuple(jax.random.normal(next(keys), t.shape, jnp.float32)
                  .astype(t.dtype) for t in layer)
            for layer in eng.pool.tensors]


def _bd_inputs(live, bucket, seed, commits=lambda i: i % 3 == 0):
    """Step inputs for `live` sequences in `bucket` slots, each with its own
    run of blocks, its block at its own offset, in its own phase (a commit
    pass forwards a block of fixed tokens, a denoising pass one with masks
    left); a padded slot carries table 0, position 0, `valid` 0."""
    rng = np.random.RandomState(seed)
    tokens = np.zeros((bucket, BL), np.int32)
    positions, commit, valid = (np.zeros(bucket, np.int32) for _ in "pcv")
    tables = np.zeros((bucket, NB), np.int32)
    for i in range(live):
        tables[i] = 1 + i * NB + np.arange(NB)
        positions[i] = BL * rng.randint(1, GEO["max_length"] // BL - 1)
        commit[i], valid[i] = commits(i), 1
        tokens[i] = rng.randint(0, MASK, BL)
        if not commit[i]:
            tokens[i, rng.randint(0, BL):] = MASK
    return tokens, positions, tables, commit, valid


def _bd_step(eng, pool, *args, rows=slice(None)):
    """The step consumes the pool it is given (donated): it gets a copy, so
    the caller can dispatch from `pool` again and hold it beside the
    result."""
    import jax
    import jax.numpy as jnp

    pv, bv = eng._weights()
    args = [a[rows] for a in args]
    new_pool, out = eng._bd_fn(len(args[0]))(
        pv, bv, jax.tree_util.tree_map(jnp.copy, pool), *args)
    return new_pool, [np.asarray(o) for o in out]


def _written(pool, new_pool):
    """{(block, row)} of the rows a step changed, a pool tensor."""
    return [{(int(b), int(o)) for b, o in zip(*np.nonzero(
        (np.asarray(t0, np.float32) != np.asarray(t1, np.float32)).any(-1)))}
        for l0, l1 in zip(pool, new_pool) for t0, t1 in zip(l0, l1)]


@pytest.mark.parametrize("dtype", sorted(BUCKET_TOLERANCE))
def test_a_sequence_in_a_bucket_of_16_against_the_sequence_alone(net, dtype):
    """13 sequences in mixed phases + 3 padded slots through the bucket-16
    step against each through the bucket-1 step: the confidences of a
    denoising pass and the rows a commit writes within `BUCKET_TOLERANCE`
    (float32: the same arg-max tokens), the experts counted are the live
    sequences' own, and nothing but the commits' rows changes outside
    reserved block 0."""
    e = _bd_engine(net, dtype)
    try:
        tol, k = BUCKET_TOLERANCE[dtype], MODEL["num_experts_per_tok"]
        pool = _random_pool(e, 5)
        args = _bd_inputs(LIVE, 16, 6)
        _, positions, tables, commit, _ = args
        new_pool, (best, conf, counts) = _bd_step(e, pool, *args)
        assert counts.shape == (MODEL["num_layers"], MODEL["num_experts"])
        assert (counts.sum(1) == LIVE * BL * k).all()      # no padded slot's
        want_rows, alone_counts = set(), 0
        flat_new = [np.asarray(t, np.float32) for l in new_pool for t in l]
        for i in range(LIVE):
            pool_1, (best_1, conf_1, counts_1) = _bd_step(
                e, pool, *args, rows=slice(i, i + 1))
            alone_counts = alone_counts + counts_1
            at = [(int(tables[i, positions[i] // 16]),
                   int(positions[i] % 16) + j) for j in range(BL)]
            if not commit[i]:
                assert all(b == 0 for w in _written(pool, pool_1)
                           for b, _ in w)
                assert np.abs(conf[i] - conf_1[0]).max() <= tol
                if dtype == "float32":
                    assert best[i].tolist() == best_1[0].tolist()
                continue
            assert (best_1 == 0).all() and (conf_1 == 0).all()  # no head
            want_rows |= set(at)
            for got, t1 in zip(flat_new, (t for l in pool_1 for t in l)):
                t1 = np.asarray(t1, np.float32)
                for b, o in at:
                    assert np.abs(got[b, o] - t1[b, o]).max() \
                        <= tol * max(1.0, np.abs(t1[b, o]).max()), (i, b, o)
        np.testing.assert_array_equal(counts, alone_counts)
        assert len(want_rows) == BL * int(commit.sum())
        for changed in _written(pool, new_pool):
            assert want_rows <= changed, want_rows - changed
            assert all(b == 0 for b, _ in changed - want_rows), \
                sorted(changed - want_rows)
    finally:
        e.shutdown(drain_timeout=10.0)


def test_a_dispatch_of_commits_alone_skips_the_head_and_repeats_itself(eng):
    """5 commits + 3 padded slots (whose `commit` is 0 like a denoising
    pass's): no logits are made, the 5 blocks are written, and the same
    dispatch from the same pool gives the same bytes."""
    pool = _random_pool(eng, 7)
    args = _bd_inputs(5, 8, 8, commits=lambda i: True)
    pool_1, (best, conf, _) = _bd_step(eng, pool, *args)
    pool_2, _ = _bd_step(eng, pool, *args)
    assert (best == 0).all() and (conf == 0).all()
    for a, b in zip(_written(pool, pool_1), _written(pool, pool_2)):
        assert a == b and len({r for r in a if r[0]}) == 5 * BL
    for l1, l2 in zip(pool_1, pool_2):
        for t1, t2 in zip(l1, l2):
            assert np.array_equal(np.asarray(t1), np.asarray(t2))
    args = _bd_inputs(5, 8, 8, commits=lambda i: i != 2)
    _, (best, conf, _) = _bd_step(eng, pool, *args)
    assert (conf[:5] > 0).all()               # one denoises: the head ran


def test_the_scanned_steps_cache_key_does_not_serve_the_batched_bd_step(
        net, tmp_path, monkeypatch):
    """A persistent cache filled under the parent's keys (the step keyed on
    tag, fingerprint and avals alone): the batched step is built anew
    beside it, the prefill executable is served from it, and what the
    batched step stored serves the next engine."""
    from paddle_tpu.jit import aot

    cache = aot.CompileCache(str(tmp_path))
    real, keyed_as_parent, sources = aot.compile_jit, [True], {}

    def compile_jit(fn, avals, *, tag, extra_key=None, **kw):
        if tag.startswith("decode-step-bd-b"):
            assert extra_key is not None
            if keyed_as_parent[0]:
                extra_key = None
        out = real(fn, avals, tag=tag, extra_key=extra_key, **kw)
        sources[tag] = out[1]
        return out

    monkeypatch.setattr(aot, "compile_jit", compile_jit)

    def built():
        sources.clear()
        e = DecodeEngine(net, block_diffusion=BD, compile_cache=cache, **GEO)
        try:
            e._bd_fn(2)
            e._prefill_fn(16)
        finally:
            e.shutdown(drain_timeout=10.0)
        return dict(sources)

    assert built() == {"decode-step-bd-b2": "compiled",
                       "decode-prefill-p16": "compiled"}
    keyed_as_parent[0] = False
    assert built() == {"decode-step-bd-b2": "compiled",
                       "decode-prefill-p16": "disk"}
    assert built() == {"decode-step-bd-b2": "disk",
                       "decode-prefill-p16": "disk"}


# ---- the option -----------------------------------------------------------

def test_off_by_default_and_then_nothing_differs():
    """With the option off the engine is the parent's: the fingerprint of
    the dense model's executables is the value recorded on commit a2dd588,
    no new counter shows, a stream has no passes. The grouped-query model's
    is its own since ISSUE 31 (recorded then; a2dd588 read 01f48218...):
    its query heads attend as rows of their KV head, and an executable
    cached with the K/V repeat in it must not be served in that program's
    place."""
    paddle.seed(0)
    want = ["04dc55ae22636abd55578284528a7d78503afbd4bef8d2e2b9050ac9c62f1390",
            "e5d660dcbcfabba034840cf998a99d32f67570650078588fc6c8f0f64fcfaf43"]
    for kw, fp in zip((dict(), dict(num_kv_heads=2, rope=True, swiglu=True,
                                    rms_norm=True,
                                    tie_word_embeddings=False)), want):
        model = gpt_mod.gpt("gpt_tiny", **kw)
        with DecodeEngine(model, max_length=48, block_size=8,
                          decode_buckets=(1, 2), prefill_buckets=(8,),
                          default_timeout=60.0) as e:
            assert e._fingerprint == fp
            s = e.submit(np.arange(1, 6, dtype=np.int32), 3)
            assert s.result(with_passes=True) == (s.tokens, [])
            st = e.stats()
            assert not [k for k in st if k.startswith(("bd_", "moe_"))]
            assert "block_diffusion" not in st


@pytest.mark.parametrize("over, message", [
    (dict(block_diffusion={**BD, "block_length": 8, "denoising_steps": 2}),
     "attention block"),
    (dict(block_diffusion={**BD, "denoising_steps": 3}), "must divide"),
    (dict(block_diffusion={**BD, "mask_token_id": 256}), "vocabulary"),
    (dict(block_diffusion={"block_length": 4}), "needs integer"),
    (dict(block_size=6, prefill_buckets=(6, 12), prefill_chunk=6),
     "must divide block_size"),
    (dict(quant="int8"), "does not compose"),
    (dict(speculate_k=2, draft_model="self"), "does not compose"),
])
def test_what_is_untested_together_is_refused_at_construction(
        net, over, message):
    kw = {**GEO, "block_diffusion": BD, **over}
    if kw.get("draft_model") == "self":
        kw["draft_model"] = net
    with pytest.raises(ValueError, match=message):
        DecodeEngine(net, **kw)


def test_sampling_and_resumption_are_refused_at_submit(eng):
    from paddle_tpu.inference.sampling import SamplingParams

    with pytest.raises(ValueError, match="greedily"):
        eng.submit(prompt_of(8, 1), 4,
                   sampling=SamplingParams(temperature=0.8, seed=1))
    with pytest.raises(ValueError, match="greedily"):
        eng.submit(prompt_of(8, 1), 4, resume_committed=[1, 2])
    with pytest.raises(ValueError, match="whole blocks"):
        eng.submit(prompt_of(60, 1), 37)     # 97 rows, 100 in whole blocks
    assert len(eng.submit(prompt_of(61, 1), 34).result()) == 34   # 95: 96
