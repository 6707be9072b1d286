"""The layers ISSUE 37 brought, at small sizes against the plain reference
the benchmark uses (`benchmarks/reference/ling_ref.py`): latent attention
(prefill through the expanded heads, then decode absorbed, against the
reference's full forward), the router (ties, the selection bias, the limit
to the best groups), an expert layer that holds a share of the router's
experts (the eight shares and the shared expert counted once add up to the
uncut layer), and the whole model with leading dense layers and the period
of six through its uncached and its cached forwards.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from benchmarks import weights_ling  # noqa: E402
from benchmarks.reference import ling_ref as ref  # noqa: E402
from benchmarks.reference.gpt_ref import _mm, layer_params  # noqa: E402
from paddle_tpu.models import moe  # noqa: E402
from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,  # noqa: E402
                                   CacheQuantError)

with open(os.path.join(ROOT, "benchmarks", "configs",
                       "ling_3p0_flash.json")) as f:
    _CONF = json.load(f)
MODEL = {**_CONF["model"], **_CONF["rehearsal"], "initializer_range": 0.1}
LATENT_LAYER, EXPERT_LAYER = 5, 2


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def weights():
    return weights_ling.make(MODEL, 2147483659, "float32")


def build(weights, model=MODEL):
    net = GPTForCausalLM(GPTConfig(**model))
    net.eval()
    for n, p in net.named_parameters():
        p._value = weights[n]
    return net


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, MODEL["vocab_size"], n, dtype=np.int32)


def tensor(a):
    return paddle.to_tensor(np.asarray(a))


def test_the_model_has_the_benchmarks_parameters(weights):
    net = GPTForCausalLM(GPTConfig(**MODEL))
    shapes = {n: tuple(p.shape) for n, p in net.named_parameters()}
    assert shapes == {n: tuple(v.shape) for n, v in weights.items()}
    kinds = [b.kind for b in net.transformer.layers]
    assert kinds == (["linear_attention"] * 5 + ["latent_attention"])
    # two leading layers keep the dense feed-forward, the rest hold 4 of
    # the router's 32 experts and a shared one
    assert [b.experts for b in net.transformer.layers] == [False] * 2 \
        + [True] * 4
    assert shapes["transformer.layers.2.mlp.router.weight"] == (64, 32)
    assert shapes["transformer.layers.2.mlp.experts_gate_up"] == (4, 64, 64)
    assert (net.recurrent_layers(), net.latent_layers(),
            net.expert_layers()) == (5, 1, 4)


# ---- latent attention -----------------------------------------------------

def latent_reference(weights, x):
    lp = {k: jnp.asarray(v, jnp.float32)
          for k, v in layer_params(weights, LATENT_LAYER).items()}
    return np.asarray(ref.latent_attention(jnp.asarray(x), lp, MODEL,
                                           _mm(False)))


@pytest.mark.parametrize("chunks", [
    (40,),                  # one prompt chunk through the expanded heads
    (16, 24),               # two chunks: the second meets the first's rows
    (33, 1, 1, 1, 1, 1, 1, 1),   # a chunk, then seven absorbed steps
    (1,) * 12,              # absorbed from the first position on
])
def test_latent_prefill_then_absorbed_decode_agree_with_the_reference(
        weights, chunks):
    net = build(weights)
    attn = net.transformer.layers[LATENT_LAYER].attn
    s = sum(chunks)
    x = np.random.default_rng(1).normal(size=(1, s, 64)).astype(np.float32)
    want = latent_reference(weights, x[0])
    # the uncached forward
    np.testing.assert_allclose(attn(tensor(x))._value[0], want, atol=2e-5)
    # chunks from a cache of 64 rows: 32 + 8 wide, the latent and the
    # rotated key
    rows = tensor(np.zeros((1, 64, 40), np.float32))
    got, lo = [], 0
    for n in chunks:
        pos = tensor(np.int32(lo))
        ids = tensor(np.arange(lo, lo + n, dtype=np.int32)[None])
        y, (rows,) = attn(tensor(x[:, lo:lo + n]), ids, cache=(rows, pos))
        got.append(np.asarray(y._value[0]))
        lo += n
    np.testing.assert_allclose(np.concatenate(got), want, atol=2e-5)
    assert rows.shape == [1, 64, 40]
    assert not np.asarray(rows._value[0, s:]).any()


def test_a_latent_row_has_no_int8_layout(weights):
    net = build(weights)
    with pytest.raises(CacheQuantError, match="latent"):
        net.init_cache(1, 32, quant="int8")
    with pytest.raises(CacheQuantError, match="latent"):
        net.init_block_pool(8, 16, quant="int8", num_slots=2)
    pool = net.init_block_pool(8, 16, num_slots=2)
    # three kinds of entry: (window, state) slots, one tensor of rows
    assert pool.slot_layers == (True,) * 5 + (False,)
    assert [tuple(t.shape) for t in pool.tensors[LATENT_LAYER]] \
        == [(8, 16, 40)]


# ---- the router -----------------------------------------------------------

def route_both(h, router_w, bias, model, **kw):
    """(ids, weights) of every row of h by the program and by the
    reference."""
    idx, w, counts = moe.route(
        jnp.asarray(h), jnp.asarray(router_w), model["num_experts_per_tok"],
        model["norm_topk_prob"], bias=jnp.asarray(bias), score="sigmoid",
        n_group=model["moe_n_group"], topk_group=model["moe_topk_group"],
        scale=model["routed_scaling_factor"], **kw)
    want = [ref.route(jnp.asarray(row), jnp.asarray(router_w),
                      jnp.asarray(bias), model) for row in h]
    return (np.asarray(idx), np.asarray(w), np.asarray(counts),
            np.stack([np.asarray(i) for i, _ in want]),
            np.stack([np.asarray(x) for _, x in want]))


ROUTER = dict(num_experts=16, num_experts_per_tok=4, moe_n_group=4,
              moe_topk_group=2, norm_topk_prob=True,
              routed_scaling_factor=2.5)


def test_the_router_agrees_with_the_reference_on_bias_and_groups():
    rng = np.random.default_rng(0)
    h = rng.normal(size=(64, 8)).astype(np.float32)
    router_w = rng.normal(size=(8, 16)).astype(np.float32)
    bias = (0.3 * rng.normal(size=16)).astype(np.float32)
    idx, w, counts, want_idx, want_w = route_both(h, router_w, bias, ROUTER)
    assert np.array_equal(idx, want_idx)
    np.testing.assert_allclose(w, want_w, rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-6)
    assert counts.sum() == 64 * 4 and counts.shape == (16,)
    # every choice lies in two of the four groups of four
    assert all(len({e // 4 for e in row}) <= 2 for row in idx)
    # the bias decides some choices, and so does the limit to two groups
    plain, _, _ = moe.route(jnp.asarray(h), jnp.asarray(router_w), 4, True,
                            score="sigmoid", scale=2.5)
    no_bias, _, _ = moe.route(jnp.asarray(h), jnp.asarray(router_w), 4,
                              True, score="sigmoid", n_group=4,
                              topk_group=2, scale=2.5)
    no_groups, _, _ = moe.route(jnp.asarray(h), jnp.asarray(router_w), 4,
                                True, bias=jnp.asarray(bias),
                                score="sigmoid", scale=2.5)
    assert (np.asarray(no_bias) != idx).any()
    assert (np.asarray(no_groups) != idx).any()
    assert (np.asarray(plain) != np.asarray(no_groups)).any()
    # the reference's planted faults are exactly those routers
    for fault, got in (("no_bias", no_bias), ("no_groups", no_groups)):
        want = [np.asarray(ref.route(jnp.asarray(r), jnp.asarray(router_w),
                                     jnp.asarray(bias), ROUTER, fault)[0])
                for r in h]
        assert np.array_equal(np.asarray(got), np.stack(want)), fault


def test_the_router_breaks_ties_by_the_lower_index_as_the_reference():
    # every expert scores alike (a zero router): the bias alone orders
    # them, and it ties inside and across groups
    h = np.ones((3, 8), np.float32)
    router_w = np.zeros((8, 16), np.float32)
    bias = np.array([0, 0, 1, 1, 2, 2, 2, 2, 0, 0, 1, 1, 2, 2, 2, 2],
                    np.float32) * 0.1
    idx, w, _, want_idx, want_w = route_both(h, router_w, bias, ROUTER)
    assert np.array_equal(idx, want_idx)
    # groups 1 and 3 tie (0.5 + 0.2, twice): the lower, group 1, is kept
    # first and both are kept; inside them the lower indices first
    assert idx[0].tolist() == [4, 5, 6, 7]
    np.testing.assert_allclose(w, want_w, rtol=1e-6)
    np.testing.assert_allclose(w, 2.5 / 4, rtol=1e-6)


def test_choices_on_absent_experts_add_nothing_and_are_counted_apart():
    rng = np.random.default_rng(1)
    h = rng.normal(size=(40, 8)).astype(np.float32)
    router_w = rng.normal(size=(8, 16)).astype(np.float32)
    bias = np.zeros(16, np.float32)
    full = route_both(h, router_w, bias, ROUTER)
    idx, w, counts, _, _ = route_both(h, router_w, bias, ROUTER,
                                      held=(4, 4))
    here = (full[0] >= 4) & (full[0] < 8)
    # ids relative to the first held expert, 4 (past the stack) where
    # absent; the weights those of the uncut router, 0 where absent
    assert np.array_equal(idx, np.where(here, full[0] - 4, 4))
    np.testing.assert_allclose(w, np.where(here, full[1], 0.0))
    assert np.array_equal(counts, full[2][4:8]) and counts.sum() == here.sum()
    # padding positions are routed and not counted
    valid = jnp.arange(40) < 25
    _, _, some = moe.route(jnp.asarray(h), jnp.asarray(router_w), 4, True,
                           score="sigmoid", n_group=4, topk_group=2,
                           held=(4, 4), valid=valid)
    assert int(some.sum()) == int(here[:25].sum())


# ---- an expert layer that holds a share ----------------------------------

def expert_reference(weights, x, held):
    lp = {k: jnp.asarray(v, jnp.float32)
          for k, v in layer_params(weights, EXPERT_LAYER).items()}
    y, _ = ref.experts(jnp.asarray(x), lp, {**MODEL, "experts_held": held},
                       _mm(False))
    shared = ref._swiglu(jnp.asarray(x), lp["mlp.shared.gate_up_proj.weight"],
                         lp["mlp.shared.down_proj.weight"], _mm(False))
    return np.asarray(y), np.asarray(shared)


@pytest.mark.parametrize("positions", [3, 40])
def test_the_eight_shares_and_the_shared_expert_once_are_the_whole_layer(
        positions):
    """Eight chips hold one routing group of 4 experts each. Each computes
    its own experts' part for the tokens routed to them, and the shared
    expert: the parts, with the shared expert counted once, add up to what
    the uncut layer (all 32 experts on one chip) gives. 3 positions take the
    pass over the assignments, 40 the pass over the experts."""
    whole = {**MODEL, "experts_held": [0, 32]}
    w = weights_ling.make(whole, 11, "float32")
    x = np.random.default_rng(2).normal(
        size=(1, positions, 64)).astype(np.float32)
    uncut = build(w, whole).transformer.layers[EXPERT_LAYER].mlp
    want = np.asarray(uncut(tensor(x))._value)
    want_routed, want_shared = expert_reference(w, x[0], [0, 32])
    np.testing.assert_allclose(want[0], want_routed + want_shared, atol=2e-5)
    routed = np.zeros_like(want)
    for share in range(8):
        held = [4 * share, 4]
        ws = dict(w)
        for name in ("experts_gate_up", "experts_down"):
            key = f"transformer.layers.{EXPERT_LAYER}.mlp.{name}"
            ws.update({k.replace(f".{EXPERT_LAYER}.", f".{i}."):
                       v[4 * share:4 * share + 4]
                       for i in range(2, 6) for k, v in ((
                           key.replace(f".{EXPERT_LAYER}.", f".{i}."),
                           w[key.replace(f".{EXPERT_LAYER}.", f".{i}.")]),)})
        layer = build(ws, {**MODEL, "experts_held": held}) \
            .transformer.layers[EXPERT_LAYER].mlp
        with moe.expert_counts() as counts:
            part = np.asarray(layer(tensor(x))._value)
        ref_part, shared = expert_reference(ws, x[0], held)
        np.testing.assert_allclose(part[0], ref_part + shared, atol=2e-5)
        routed += part - shared
        assert counts[0].shape == (4,)
    np.testing.assert_allclose(routed[0] + want_shared, want[0], atol=5e-5)
    # and the parts are not trivially the whole
    assert np.abs(routed[0]).max() > 10 * 5e-5


# ---- the whole model -------------------------------------------------------

def test_the_forward_agrees_with_the_reference(weights):
    ids = tokens(60)
    got = build(weights)(tensor(ids[None]))._value[0]
    want = ref.logits(weights, ids, MODEL)
    np.testing.assert_allclose(got, want, atol=5e-5)
    # the planted faults and the float8 control move it
    for kw in ({"fault": "mean_decay"}, {"fault": "no_bias"},
               {"fault": "no_groups"}, {"quantized": True}):
        moved = np.asarray(ref.logits(weights, ids, MODEL, **kw))
        assert np.abs(moved - np.asarray(want)).max() > 1e-2, kw


def test_a_cached_chunk_then_steps_agree_with_the_forward(weights):
    """A chunk of 32, a padded chunk (12 real positions in a bucket of 16),
    then single positions: the latent rows, the state slots and the expert
    counts under `valid_len`."""
    net = build(weights)
    ids = tokens(50, seed=1)
    want = np.asarray(ref.logits(weights, ids, MODEL))
    caches = net.init_cache(1, 96)
    assert [len(c) for c in caches] == [2] * 5 + [1]
    with moe.expert_counts() as counts:
        lg, caches = net.decode_step(tensor(ids[None, :32]), caches,
                                     tensor(np.int32(0)),
                                     tensor(np.int32(32)))
    np.testing.assert_allclose(lg._value[0], want[:32], atol=5e-5)
    assert [int(c.sum()) for c in counts] == [
        int(n) for n in np.asarray(jnp.stack(counts)).sum(-1)]
    padded = np.zeros((1, 16), np.int32)
    padded[0, :12] = ids[32:44]
    with moe.expert_counts() as counts:
        lg, caches = net.decode_step(tensor(padded), caches,
                                     tensor(np.int32(32)),
                                     tensor(np.int32(12)))
    np.testing.assert_allclose(lg._value[0, :12], want[32:44], atol=5e-5)
    # the reference's count of the 12 real positions' choices on the held
    # experts, layer by layer
    made = []
    ref.served_logits(weights, ids[:44], np.arange(44), MODEL, choices=made)
    first = []
    ref.served_logits(weights, ids[:32], np.arange(32), MODEL, choices=first)
    assert [int(c.sum()) for c in counts] == [
        a[0] - b[0] for a, b in zip(made, first)]
    for t in range(44, 50):
        lg, caches = net.decode_step(tensor(ids[None, t:t + 1]), caches,
                                     tensor(np.int32(t)))
        np.testing.assert_allclose(lg._value[0, 0], want[t], atol=5e-5)


def test_the_signature_names_what_no_shape_does(weights):
    sig = build(weights).decode_signature()
    for part in ("gate1b-5.0sigmoid", "mla@5:32+8n16v16g1", "dense2",
                 "held(0, 4)", "sigmoidb1g8/4x2.5s32"):
        assert part in sig, (part, sig)
    other = build(weights, {**MODEL, "experts_held": [4, 4]})
    assert other.decode_signature() != sig


@pytest.mark.parametrize("bad", [
    {"experts_held": [30, 4]},              # past the router's 32
    {"experts_held": [0]},
    {"moe_n_group": 5},                     # does not divide 32
    {"moe_topk_group": 9},
    {"kv_lora_rank": 0},
])
def test_a_configuration_that_cannot_be_is_refused(bad):
    with pytest.raises(ValueError):
        GPTConfig(**{**MODEL, **bad})
