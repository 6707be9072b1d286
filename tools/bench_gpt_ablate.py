"""On-chip ablation of the flagship GPT pretrain step (BASELINE north
star): where the gap between measured MFU and the matmul-only ideal lives.
Run on the real chip: `python tools/bench_gpt_ablate.py [variants]`."""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BATCH = int(os.environ.get("BENCH_BATCH", "16"))
SEQ = int(os.environ.get("BENCH_SEQLEN", "1024"))
STEPS = int(os.environ.get("BENCH_STEPS", "10"))


def run(name, loss_fn=None, patch=None, batch=BATCH, steps=STEPS,
        optimizer="adamw", clip=True):
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import importlib
    G = importlib.import_module("paddle_tpu.models.gpt")

    paddle.seed(0)
    undo = patch(G) if patch else None
    try:
        model = G.gpt("gpt_base")
        clip_obj = paddle.nn.ClipGradByGlobalNorm(1.0) if clip else None
        if optimizer == "adamw":
            opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                         parameters=model.parameters(),
                                         grad_clip=clip_obj)
        else:
            opt = paddle.optimizer.SGD(learning_rate=1e-4,
                                       parameters=model.parameters())
        mesh = dist.build_mesh(dp=-1, devices=jax.devices()[:1])
        eng = dist.parallelize(model, opt, loss_fn=loss_fn, mesh=mesh,
                               compute_dtype="bfloat16")
        rng = np.random.RandomState(0)
        ids = paddle.to_tensor(
            rng.randint(0, 50304, (batch, SEQ)).astype("int32"))
        float(eng.train_batch(ids))  # compile+fence
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            loss = None
            for _ in range(steps):
                loss = eng.train_batch(ids)
            float(loss)
            best = min(best, (time.perf_counter() - t0) / steps)
        tps = batch * SEQ / best
        print(f"{name:46s}: {best*1e3:7.2f} ms/step  {tps:9.0f} tok/s",
              flush=True)
        return best
    finally:
        if undo:
            undo()


def loss_trunk_only(m, ids):
    # skip LM head matmul AND cross entropy
    return m.transformer(ids).mean()


def loss_logits_mean(m, ids):
    # LM head matmul kept; cross entropy replaced by a cheap reduction
    return m(ids).astype("float32").mean()


def patch_no_attention(G):
    import paddle_tpu.nn.functional as F
    orig = G.GPTAttention.forward

    def fwd(self, x, position_ids=None, cache=None):
        h = self.cfg.hidden_size
        qkv = self.qkv_proj(x)
        return self.dropout(self.out_proj(qkv[:, :, :h]))

    G.GPTAttention.forward = fwd
    return lambda: setattr(G.GPTAttention, "forward", orig)


def patch_no_layernorm(G):
    import paddle_tpu.nn as nn
    orig = nn.LayerNorm.forward
    nn.LayerNorm.forward = lambda self, x: x
    return lambda: setattr(nn.LayerNorm, "forward", orig)


def matmul_ceiling():
    """Achievable bf16 matmul throughput at the model's own shapes:
    fwd+bwd-shaped chain per layer x12 + LM head, timed alone."""
    import jax
    import jax.numpy as jnp

    T, H, I, V = BATCH * SEQ, 768, 3072, 50304
    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (T, H), jnp.bfloat16)
    wqkv = jax.random.normal(k, (H, 2304), jnp.bfloat16)
    wo = jax.random.normal(k, (768, H), jnp.bfloat16)
    w1 = jax.random.normal(k, (H, I), jnp.bfloat16)
    w2 = jax.random.normal(k, (I, H), jnp.bfloat16)
    wv = jax.random.normal(k, (H, V), jnp.bfloat16)

    @jax.jit
    def chain(x):
        acc = x
        for _ in range(12):
            # fwd matmuls + the two grad matmuls each implies (3x FLOPs) —
            # emulate with 3 passes over the same shapes
            for _ in range(3):
                a = acc @ wqkv
                acc = (a[:, :768] @ wo + acc)
                acc = (acc @ w1) @ w2 + acc
        l = acc @ wv
        for _ in range(2):
            l = (l @ wv.T) @ wv
        return l.mean()

    from paddle_tpu.device import chip_peaks

    peak = chip_peaks(jax.devices()[0].device_kind)["bf16_flops"]
    float(chain(x))
    t0 = time.perf_counter()
    n = 5
    for _ in range(n):
        r = chain(x)
    float(r)
    dt = (time.perf_counter() - t0) / n
    flops = 3 * 12 * (2 * T * H * 2304 + 2 * T * 768 * H + 4 * T * H * I) \
        + 5 * 2 * T * H * V
    print(f"{'matmul-only chain (model shapes)':46s}: {dt*1e3:7.2f} ms "
          f" -> {flops/dt/1e12:6.1f} TF/s ({flops/dt/peak*100:4.1f}% peak)",
          flush=True)


if __name__ == "__main__":
    which = sys.argv[1:] or ["ceiling", "base", "nohead", "noce", "noattn",
                             "noln", "sgd", "bs32"]
    if "ceiling" in which:
        matmul_ceiling()
    if "base" in which:
        run(f"baseline (bs={BATCH}, seq={SEQ}, AdamW+clip)")
    if "nohead" in which:
        run("trunk only (no LM head, no CE)", loss_fn=loss_trunk_only)
    if "noce" in which:
        run("logits.mean (LM head, no CE)", loss_fn=loss_logits_mean)
    if "noattn" in which:
        run("attention core removed", patch=patch_no_attention)
    if "noln" in which:
        run("layernorm removed", patch=patch_no_layernorm)
    if "sgd" in which:
        run("SGD, no clip (optimizer cost)", optimizer="sgd", clip=False)
    if "adamw_noclip" in which:
        run("AdamW, no clip (clip cost isolate)", clip=False)
    if "bs32" in which:
        run("bs=32", batch=32)
