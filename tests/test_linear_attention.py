"""Gated delta-rule layers beside full attention (ISSUE 33): the three forms
of the rule in `models/linear_attention.py` against the plain reference's
recurrence (`benchmarks/reference/olmo_hybrid_ref.py`, the file the
benchmark uses), the hybrid block of `models/gpt.py` against the reference's
forward, and the cache entries the model hands a cached step.
"""
import importlib
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
from benchmarks import weights_olmo_hybrid  # noqa: E402
from benchmarks.reference import olmo_hybrid_ref as ref  # noqa: E402
from paddle_tpu.models import linear_attention as la  # noqa: E402
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM  # noqa: E402

gpt_mod = importlib.import_module("paddle_tpu.models.gpt")

with open(os.path.join(ROOT, "benchmarks", "configs",
                       "olmo_hybrid_7b.json")) as f:
    _CONF = json.load(f)
MODEL = {**_CONF["model"], **_CONF["rehearsal"], "initializer_range": 0.1}
H, DK, DV = 3, 8, 16


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def operands(s, seed=0):
    """q, k (unit length), v, g = log alpha, beta in (0, 2), a start state."""
    rng = np.random.default_rng(seed)
    q, k = (rng.normal(size=(s, H, DK)).astype(np.float32) for _ in "qk")
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(s, H, DV)).astype(np.float32)
    g = -0.3 * np.abs(rng.normal(size=(s, H))).astype(np.float32)
    beta = rng.uniform(0, 2, size=(s, H)).astype(np.float32)
    return q, k, v, g, beta


def reference_rule(q, k, v, g, beta):
    """The reference's position-by-position recurrence, from a zero state."""
    return np.asarray(ref.delta_rule(*map(jnp.asarray, (q, k, v)),
                                     jnp.exp(g), jnp.asarray(beta)))


def run_in_pieces(form, ops, cuts):
    """`form` over the pieces of the sequence that `cuts` marks, the state
    carried from piece to piece."""
    state = jnp.zeros((H, DV, DK), jnp.float32)
    outs, lo = [], 0
    for hi in list(cuts) + [len(ops[0])]:
        o, state = form(*(t[lo:hi] for t in ops), state)
        outs.append(o)
        lo = hi
    return np.concatenate(outs), np.asarray(state)


@pytest.mark.parametrize("form,cuts", [
    ("recurrent", ()),                    # the whole sequence
    ("chunked", ()),                      # whole, blocks of 64 + a tail
    ("chunked", (7, 71, 72, 140)),        # unequal chunks, carried state
    ("chunked", (64, 128)),               # whole blocks exactly
    ("steps", None),                      # one position at a time
])
def test_the_three_forms_agree_with_the_references_recurrence(form, cuts):
    ops = operands(150)
    want = reference_rule(*ops)
    if form == "steps":
        state = jnp.zeros((H, DV, DK), jnp.float32)
        outs = []
        for t in range(len(ops[0])):
            o, state = la.delta_rule_step(*(x[t] for x in ops), state)
            outs.append(o)
        got = np.stack(outs)
    else:
        fn = la.delta_rule_recurrent if form == "recurrent" \
            else la.delta_rule_chunked
        got, _ = run_in_pieces(fn, ops, cuts)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_forms_leave_the_same_state():
    ops = operands(130, seed=1)
    _, s_rec = run_in_pieces(la.delta_rule_recurrent, ops, ())
    _, s_chunk = run_in_pieces(la.delta_rule_chunked, ops, (33, 97))
    np.testing.assert_allclose(s_chunk, s_rec, atol=2e-5)


@pytest.mark.parametrize("n", [16, 32, 64])
def test_unit_lower_inverse(n):
    rng = np.random.default_rng(n)
    a = np.tril(rng.normal(size=(2, 3, n, n)).astype(np.float32) * 0.4, -1)
    inv = np.asarray(la._unit_lower_inverse(jnp.asarray(a)))
    eye = np.eye(n, dtype=np.float32)
    np.testing.assert_allclose(inv @ (eye + a), np.broadcast_to(eye, a.shape),
                               atol=1e-4)


def build(weights, model=MODEL):
    net = GPTForCausalLM(GPTConfig(**model))
    net.eval()
    for n, p in net.named_parameters():
        p._value = weights[n]
    return net


@pytest.fixture(scope="module")
def weights():
    return weights_olmo_hybrid.make(MODEL, 2147483659, "float32")


def test_the_model_has_the_benchmarks_parameters(weights):
    net = build(weights)
    shapes = {n: tuple(p.shape) for n, p in net.named_parameters()}
    assert shapes == {n: tuple(w.shape) for n, w in weights.items()}
    kinds = [blk.kind for blk in net.transformer.layers]
    assert kinds == ["linear_attention"] * 3 + ["full_attention"]
    assert not hasattr(net.transformer, "wpe")


def test_the_forward_agrees_with_the_reference(weights):
    net = build(weights)
    ids = np.random.default_rng(3).integers(1, MODEL["vocab_size"], (2, 90),
                                            dtype=np.int32)
    got = np.asarray(net(paddle.to_tensor(ids))._value)
    for row in range(2):
        want = np.asarray(ref.logits(weights, ids[row], MODEL))
        np.testing.assert_allclose(got[row], want, atol=2e-4)


def test_a_cached_chunk_then_steps_agree_with_the_forward(weights):
    """`decode_step` over `init_cache` entries: a chunk whose bucket is
    padded past `valid_len`, a second chunk from the carried state, then
    single positions."""
    net = build(weights)
    ids = np.random.default_rng(4).integers(1, MODEL["vocab_size"], (1, 70),
                                            dtype=np.int32)
    want = np.asarray(ref.logits(weights, ids[0], MODEL))
    caches = net.init_cache(1, MODEL["max_position_embeddings"])
    assert [len(e) for e in caches] == [2, 2, 2, 2]
    assert caches[0][1].dtype == jnp.float32          # the state
    assert tuple(caches[0][0].shape) == (1, 3, 4 * (8 + 8 + 16))
    got, pos = [], 0
    for n, bucket in ((37, 48), (20, 32)):            # padded buckets
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = ids[0, pos:pos + n]
        lg, caches = net.decode_step(
            paddle.to_tensor(toks), caches,
            paddle.to_tensor(np.int32(pos)), paddle.to_tensor(np.int32(n)))
        got.append(np.asarray(lg._value)[0, :n])
        pos += n
    for t in range(pos, 70):
        lg, caches = net.decode_step(paddle.to_tensor(ids[:, t:t + 1]),
                                     caches, paddle.to_tensor(np.int32(t)))
        got.append(np.asarray(lg._value)[0])
    np.testing.assert_allclose(np.concatenate(got), want, atol=2e-4)


def test_the_signature_is_new_only_for_the_new_model():
    assert gpt_mod.gpt("gpt_tiny").decode_signature() == ""
    sig = GPTForCausalLM(GPTConfig(**MODEL)).decode_signature()
    assert sig.startswith("kindsl,l,l,f:after1:qkwhole1:pos0:lin4x8x16c4")
    # a pre-norm block of attention alone takes the old cache call
    plain = GPTForCausalLM(GPTConfig(vocab_size=64, hidden_size=32,
                                     num_layers=2, num_heads=4))
    assert plain.decode_signature() == ""
    assert all(len(e) == 2 for e in plain.init_cache(1, 16))


def test_a_pattern_has_to_fit_the_depth():
    with pytest.raises(ValueError, match="layer_pattern"):
        GPTConfig(num_layers=6, layer_pattern=("linear_attention",) * 3
                  + ("full_attention",), linear_num_heads=2,
                  linear_key_head_dim=8, linear_value_head_dim=8)
    with pytest.raises(ValueError, match="layer_pattern"):
        GPTConfig(num_layers=4, layer_pattern=("window_attention",))
    with pytest.raises(ValueError, match="linear_num_heads"):
        GPTConfig(num_layers=4, layer_pattern=("linear_attention",))
