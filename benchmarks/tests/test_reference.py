"""The plain reference against the program at gpt_tiny size on the CPU:
logits, loss and gradients of `paddle_tpu.models.gpt`, the optimizer the
engine runs, and prefill then decode through `DecodeEngine`'s cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness, weights
from benchmarks.reference import gpt_ref

MODEL = dict(harness.load_json(harness.HERE, "configs",
                               "gpt_base.json")["model"],
             **harness.load_json(harness.HERE, "configs",
                                 "gpt_base.json")["rehearsal"])
SEED = 2 ** 31 + 11


@pytest.fixture(scope="module")
def net():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    model = GPTForCausalLM(GPTConfig(**MODEL))
    w = weights.make(MODEL, SEED)
    for n, p in model.named_parameters():
        p._value = w[n]
    assert set(w) == {n for n, _ in model.named_parameters()}
    return model


def test_weights_are_the_seeds_and_biases_are_not_zero():
    a, b = weights.make(MODEL, SEED), weights.make(MODEL, SEED)
    c = weights.make(MODEL, SEED + 1)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["transformer.wte.weight"],
                              c["transformer.wte.weight"])
    assert float(jnp.abs(a["transformer.layers.0.attn.qkv_proj.bias"]).min()) > 0
    assert abs(float(a["transformer.ln_f.weight"].mean()) - 1.0) < 0.02


def test_logits_loss_and_gradients_agree_with_the_program(net):
    import paddle_tpu as paddle
    from paddle_tpu.distributed.functional import functionalize

    ids = np.random.default_rng(3).integers(0, MODEL["vocab_size"], (3, 48),
                                            dtype=np.int32)
    w = weights.make(MODEL, SEED)
    with jax.default_matmul_precision("highest"):
        got = net(paddle.to_tensor(ids))._value
        want = gpt_ref.logits(w, jnp.asarray(ids), MODEL)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

        apply_fn, params, buffers = functionalize(
            net, method=lambda b: net.loss(b))
        pv = {n: p._value for n, p in params.items()}
        bv = {n: b._value for n, b in buffers.items()}
        from paddle_tpu.core.tensor import Tensor

        def loss_of(pv):
            out, _ = apply_fn(pv, bv, Tensor(jnp.asarray(ids)))
            return out._value if hasattr(out, "_value") else out

        loss, grads = jax.value_and_grad(loss_of)(pv)
        ref_loss, ref_grads = gpt_ref.loss_and_grads(w, jnp.asarray(ids),
                                                     MODEL, rows=2)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    for n in ref_grads:
        np.testing.assert_allclose(grads[n], ref_grads[n], atol=3e-6,
                                   rtol=2e-4, err_msg=n)


def test_float8_control_differs_from_the_reference():
    ids = np.random.default_rng(4).integers(0, MODEL["vocab_size"], (2, 32),
                                            dtype=np.int32)
    w = weights.make(MODEL, SEED)
    ref = gpt_ref.logits(w, jnp.asarray(ids), MODEL)
    ctl = gpt_ref.logits(w, jnp.asarray(ids), MODEL, quantized=True)
    err = float(jnp.max(jnp.abs(ctl - ref)))
    assert 1e-3 < err < 1.0
    _, g = gpt_ref.loss_and_grads(w, jnp.asarray(ids), MODEL, quantized=True)
    assert all(float(jnp.linalg.norm(v)) > 0 for v in g.values())


def test_prefill_then_decode_through_the_engine_agrees(net):
    from paddle_tpu.inference import DecodeEngine

    serve = harness.load_module(
        harness.os.path.join(harness.HERE, "drivers", "serve.py"),
        "driver_serve_t")
    eng = DecodeEngine(net, max_length=96, block_size=16,
                       decode_buckets=(1, 2), prefill_buckets=(16, 32, 64),
                       prefill_chunk=16)
    try:
        rng = np.random.default_rng(5)
        sample = []
        for n, new in ((40, 12), (9, 20)):
            prompt = rng.integers(1, MODEL["vocab_size"], n, dtype=np.int32)
            sample.append({"prompt": prompt,
                           "tokens": eng.generate(prompt, new)})
    finally:
        eng.shutdown()
    gaps = serve.token_gaps(MODEL, SEED, "float32", sample, pad_to=96)
    assert len(gaps) == 32 and float(gaps.max()) <= 1e-4
    # the same tokens, altered where they are produced, are seen
    for s in sample:
        s["tokens"] = [(t + 1) % MODEL["vocab_size"] for t in s["tokens"]]
    bad = serve.token_gaps(MODEL, SEED, "float32", sample, pad_to=96)
    assert float(bad.max()) > 0.1
