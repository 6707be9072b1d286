"""Gated delta-rule layers beside full attention (ISSUE 33): the three forms
of the rule in `models/linear_attention.py` against the plain reference's
recurrence (`benchmarks/reference/olmo_hybrid_ref.py`, the file the
benchmark uses), the hybrid block of `models/gpt.py` against the reference's
forward, and the cache entries the model hands a cached step. Since ISSUE 37
the log-decay is one a key channel and one a head is its broadcast: every
case of the forms runs under both gates, the per-channel one against
`benchmarks/reference/ling_ref.py`'s recurrence.
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
from benchmarks.reference import ling_ref  # noqa: E402
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


GATES = ("head", "channel")


def operands(s, seed=0, gate="head"):
    """q, k (unit length), v, the log-decay g (one a head, or with
    `gate="channel"` one a key channel, in (-5, 0) and spread over four
    orders of magnitude), beta in (0, 2)."""
    rng = np.random.default_rng(seed)
    q, k = (rng.normal(size=(s, H, DK)).astype(np.float32) for _ in "qk")
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(s, H, DV)).astype(np.float32)
    if gate == "channel":
        g = -5.0 * rng.uniform(0, 1, size=(s, H, DK)).astype(np.float32) ** 4
    else:
        g = -0.3 * np.abs(rng.normal(size=(s, H))).astype(np.float32)
    beta = rng.uniform(0, 2, size=(s, H)).astype(np.float32)
    return q, k, v, g, beta


def reference_rule(q, k, v, g, beta):
    """The reference's position-by-position recurrence, from a zero state:
    the hybrid cell's for one decay a head, the Ling cell's for one a key
    channel."""
    q, k, v, beta = map(jnp.asarray, (q, k, v, beta))
    if g.ndim == 3:
        return np.asarray(ling_ref.delta_rule(q, k, v, jnp.asarray(g), beta))
    return np.asarray(ref.delta_rule(q, k, v, jnp.exp(g), beta))


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


@pytest.mark.parametrize("gate", GATES)
@pytest.mark.parametrize("form,cuts", [
    ("recurrent", ()),                    # the whole sequence
    ("chunked", ()),                      # whole, blocks of 64 + a tail
    ("chunked", (7, 71, 72, 140)),        # unequal chunks, carried state
    ("chunked", (64, 128)),               # whole blocks exactly
    ("steps", None),                      # one position at a time
])
def test_the_three_forms_agree_with_the_references_recurrence(form, cuts,
                                                              gate):
    ops = operands(150, gate=gate)
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


@pytest.mark.parametrize("gate", GATES)
def test_the_forms_leave_the_same_state(gate):
    ops = operands(130, seed=1, gate=gate)
    _, s_rec = run_in_pieces(la.delta_rule_recurrent, ops, ())
    _, s_chunk = run_in_pieces(la.delta_rule_chunked, ops, (33, 97))
    np.testing.assert_allclose(s_chunk, s_rec, atol=2e-5)


def test_one_decay_a_head_is_the_broadcast_over_the_key_channels():
    """A gate constant over d_k IS the scalar rule: bit for bit in the
    recurrent form (outputs and state), whichever way it is handed in."""
    q, k, v, g, beta = operands(90, seed=2)
    zero = jnp.zeros((H, DV, DK), jnp.float32)
    o_head, s_head = la.delta_rule_recurrent(q, k, v, g, beta, zero)
    spread = np.ascontiguousarray(np.broadcast_to(g[..., None], k.shape))
    o_chan, s_chan = la.delta_rule_recurrent(q, k, v, spread, beta, zero)
    assert np.array_equal(np.asarray(o_head), np.asarray(o_chan))
    assert np.array_equal(np.asarray(s_head), np.asarray(s_chan))
    # and what the scalar rule was before the gate had channels

    def before(state, x):
        qt, kt, vt, gt, bt = x
        state = state * jnp.exp(gt)[:, None, None]
        sk = jnp.sum(state * kt[:, None, :], axis=-1)
        state = state + (bt[:, None] * (vt - sk))[:, :, None] \
            * kt[:, None, :]
        return state, jnp.sum(state * qt[:, None, :], axis=-1)

    s_was, o_was = jax.lax.scan(before, zero, (q, k, v, g, beta))
    assert np.array_equal(np.asarray(o_head), np.asarray(o_was))
    assert np.array_equal(np.asarray(s_head), np.asarray(s_was))


@pytest.mark.parametrize("s", [64, 150])
def test_a_chunk_at_the_gates_lower_bound_stays_finite(s):
    """g = -5 in every channel of every position: the running sum passes
    float32's exp(-88) after 18 positions, which is what a factored
    exp(G_i) exp(-G_j) over a block of 64 would overflow on."""
    q, k, v, _, beta = operands(s, seed=3)
    g = np.full((s, H, DK), -5.0, np.float32)
    zero = jnp.zeros((H, DV, DK), jnp.float32)
    o_chunk, s_chunk = la.delta_rule_chunked(q, k, v, g, beta, zero)
    o_rec, s_rec = la.delta_rule_recurrent(q, k, v, g, beta, zero)
    assert np.isfinite(np.asarray(o_chunk)).all()
    np.testing.assert_allclose(o_chunk, o_rec, atol=2e-5)
    np.testing.assert_allclose(s_chunk, s_rec, atol=2e-5)
    # an unbounded gate too: the tiles never exponentiate a positive sum
    g = np.full((s, H), -40.0, np.float32)
    o_chunk, _ = la.delta_rule_chunked(q, k, v, g, beta, zero)
    o_rec, _ = la.delta_rule_recurrent(q, k, v, g, beta, zero)
    np.testing.assert_allclose(o_chunk, o_rec, atol=2e-5)


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
