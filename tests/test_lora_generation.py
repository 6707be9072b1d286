"""LoRA + generation tests (reference: BASELINE config 5 — LLaMA LoRA
fine-tune + inference)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import gpt, generate, GenerationConfig
from paddle_tpu.nn.lora import (LoRAConfig, LoRALinear, apply_lora,
                                merge_lora, lora_parameters)


def _tiny_llama():
    paddle.seed(0)
    return gpt("gpt_tiny", num_layers=2, rope=True, swiglu=True,
               vocab_size=128, max_position_embeddings=64)


def test_apply_lora_freezes_base_and_trains_adapters():
    m = _tiny_llama()
    n_before = sum(1 for _ in m.parameters())
    apply_lora(m, LoRAConfig(r=4, target_modules=("qkv", "out")))
    loras = lora_parameters(m)
    assert loras and all(not p.stop_gradient for p in loras)
    frozen = [p for n, p in m.named_parameters()
              if "lora" not in n]
    assert len(frozen) == n_before
    assert all(p.stop_gradient for p in frozen)

    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 128, (4, 16)).astype("int32"))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2, parameters=loras)
    losses = []
    for _ in range(5):   # suite-budget trim: 8 -> 5 eager steps (same
        loss = m.loss(ids)                 # decreasing-loss assertion)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_lora_zero_init_is_identity_and_merge_matches():
    m = _tiny_llama()
    ids = paddle.to_tensor(
        np.random.RandomState(1).randint(0, 128, (2, 8)).astype("int32"))
    m.eval()
    ref = m(ids).numpy()
    apply_lora(m, LoRAConfig(r=4))
    np.testing.assert_allclose(m(ids).numpy(), ref, rtol=1e-5)  # B=0 init
    # perturb adapters, then merging must preserve outputs
    for p in lora_parameters(m):
        p.set_value(np.random.RandomState(2).randn(*p.shape)
                    .astype(np.float32) * 0.01)
    unmerged = m(ids).numpy()
    merge_lora(m)
    np.testing.assert_allclose(m(ids).numpy(), unmerged, rtol=1e-4,
                               atol=1e-5)
    assert not np.allclose(unmerged, ref)


def test_generate_greedy_matches_stepwise():
    m = _tiny_llama()
    m.eval()
    ids = np.random.RandomState(3).randint(0, 128, (2, 5)).astype(np.int32)
    # suite-budget trim: 3 new tokens (was 4) — each stepwise reference
    # token pays a full uncached forward at a new length
    out = generate(m, paddle.to_tensor(ids), max_new_tokens=3).numpy()
    assert out.shape == (2, 8)
    np.testing.assert_array_equal(out[:, :5], ids)
    # stepwise greedy reference
    cur = ids
    for _ in range(3):
        logits = m(paddle.to_tensor(cur)).numpy()
        nxt = logits[:, -1].argmax(-1).astype(np.int32)
        cur = np.concatenate([cur, nxt[:, None]], 1)
    np.testing.assert_array_equal(out, cur)


def test_generate_sampling_and_eos():
    m = _tiny_llama()
    m.eval()
    ids = np.zeros((1, 3), np.int32)
    out = generate(m, paddle.to_tensor(ids),
                   GenerationConfig(max_new_tokens=6, do_sample=True,
                                    top_k=10, top_p=0.9, temperature=0.8,
                                    seed=5)).numpy()
    assert out.shape == (1, 9)
    assert (out < 128).all() and (out >= 0).all()
    # eos stopping: force eos as the only likely token? just smoke the path
    out2 = generate(m, paddle.to_tensor(ids), max_new_tokens=3,
                    eos_token_id=7).numpy()
    after_eos = False
    for tok in out2[0, 3:]:
        if after_eos:
            assert tok == 0  # pad after eos
        if tok == 7:
            after_eos = True


def test_cached_and_uncached_decode_agree():
    """KV-cached decode must produce exactly the uncached tokens."""
    m = _tiny_llama()
    m.eval()
    ids = paddle.to_tensor(
        np.random.RandomState(9).randint(0, 128, (2, 12)).astype(np.int32))
    a = generate(m, ids, GenerationConfig(max_new_tokens=6,
                                          use_cache=True)).numpy()
    b = generate(m, ids, GenerationConfig(max_new_tokens=6,
                                          use_cache=False)).numpy()
    np.testing.assert_array_equal(a, b)


def test_int8_kv_cache_token_parity():
    """int8 KV cache (model.cache_quant='int8'): greedy tokens must match
    the bf16 cache exactly on a small model, and the cache entries must be
    int8 quads half the bf16 bytes (the capability is cache MEMORY — see
    docs/decode_perf.md round-4 addendum for the throughput verdict)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import gpt, generate, GenerationConfig

    paddle.seed(0)
    m = gpt("gpt_tiny")
    m.eval()
    rng = np.random.RandomState(0)
    prompt = paddle.to_tensor(rng.randint(0, 256, (2, 8)).astype("int32"))
    cfg = GenerationConfig(max_new_tokens=10, do_sample=False,
                           use_cache=True)
    out_bf16 = generate(m, prompt, cfg).numpy()
    m.cache_quant = "int8"
    out_int8 = generate(m, prompt, cfg).numpy()
    # quantization perturbs logits; near-tied argmaxes may legitimately
    # flip a token, so assert a high match fraction (plus the logits
    # closeness below) rather than exact equality
    assert (out_bf16 == out_int8).mean() > 0.85, (out_bf16, out_int8)

    caches = m.init_cache(2, 32)
    assert len(caches[0]) == 4
    kq, ks, vq, vs = caches[0]
    assert str(kq.dtype).endswith("int8") and str(vq.dtype).endswith("int8")
    # flat int8 rows [B, T, Hkv*D], one f32 scale a (position, head)
    assert tuple(kq.shape) == (2, 32, m.cfg.num_kv_heads * m.cfg.head_dim)
    assert tuple(ks.shape) == (2, 32, m.cfg.num_kv_heads)
    # logits parity through a cached prefill step
    lb_model = gpt("gpt_tiny")
    lb_model.eval()
    lb_model.set_state_dict(m.state_dict())
    lb, _ = lb_model.decode_step(prompt, lb_model.init_cache(2, 16),
                                 paddle.to_tensor(np.int32(0)))
    lq, _ = m.decode_step(prompt, m.init_cache(2, 16),
                          paddle.to_tensor(np.int32(0)))
    err = np.abs(lb.numpy() - lq.numpy()).max() / max(
        np.abs(lb.numpy()).max(), 1.0)
    assert err < 0.05, err

    # unsupported quant mode raises
    m.cache_quant = "int3"
    try:
        m.init_cache(2, 8)
        raise AssertionError("expected ValueError")
    except ValueError:
        pass
