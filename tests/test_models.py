"""Model-zoo smoke + correctness tests (reference test model:
test/dygraph_to_static model-level tests, SURVEY.md §4)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.models import gpt, resnet18


def test_gpt_forward_loss_backward():
    m = gpt("gpt_tiny")
    ids = paddle.to_tensor(np.random.randint(0, 256, (2, 16)).astype("int32"))
    logits = m(ids)
    assert logits.shape == [2, 16, 256]
    loss = m.loss(ids)
    assert loss.shape == []
    loss.backward()
    for name, p in m.named_parameters():
        assert p.grad is not None, name


def test_gpt_llama_variant():
    m = gpt("gpt_tiny", rope=True, swiglu=True, rms_norm=True,
            tie_word_embeddings=False)
    ids = paddle.to_tensor(np.random.randint(0, 256, (2, 16)).astype("int32"))
    loss = m.loss(ids)
    loss.backward()
    assert np.isfinite(float(loss))
    # no biases in llama-style stack
    names = [n for n, _ in m.named_parameters()]
    assert not any(n.endswith("bias") and "norm" not in n and "ln" not in n
                   for n in names)


def test_gpt_loss_decreases_with_sgd():
    m = gpt("gpt_tiny")
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=m.parameters())
    ids = paddle.to_tensor(np.random.randint(0, 64, (4, 16)).astype("int32"))
    losses = []
    for _ in range(3):   # suite budget: SGD at 0.1 separates in 3 steps
        loss = m.loss(ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_resnet18_train_eval():
    m = resnet18(num_classes=10)
    x = paddle.to_tensor(np.random.randn(2, 3, 32, 32).astype("float32"))
    y = m(x)
    assert y.shape == [2, 10]
    lab = paddle.to_tensor(np.array([1, 2]).astype("int64"))
    loss = F.cross_entropy(y, lab)
    loss.backward()
    assert m.conv1.weight.grad is not None
    # BN running stats updated in train mode
    rm = m.bn1._buffers["_mean"].numpy().copy()
    m(x)
    assert not np.allclose(rm, m.bn1._buffers["_mean"].numpy())
    m.eval()
    rm2 = m.bn1._buffers["_mean"].numpy().copy()
    m(x)
    np.testing.assert_allclose(rm2, m.bn1._buffers["_mean"].numpy())


@pytest.mark.parametrize("data_format", ["NCHW", "NHWC"])
def test_bottleneck_block_trains(data_format):
    """The bottleneck block's one forward (conv-BN-ReLU x 3 + identity)
    trains: every weight gets a gradient, every BN moves its running
    mean in train mode and leaves it in eval mode, and NHWC reproduces
    NCHW on the same weights."""
    from paddle_tpu.models.resnet import BottleneckBlock

    paddle.seed(5)
    blk = BottleneckBlock(16, 4, data_format=data_format)
    x = np.random.RandomState(5).randn(2, 16, 6, 6).astype("float32")
    nhwc = data_format == "NHWC"
    inp = paddle.to_tensor(x.transpose(0, 2, 3, 1) if nhwc else x)
    before = [bn._buffers["_mean"].numpy().copy()
              for bn in (blk.bn1, blk.bn2, blk.bn3)]
    y = blk(inp)
    assert y.shape == list(inp.shape)
    (y * y).mean().backward()
    for name, p in blk.named_parameters():
        assert p.grad is not None, name
    for bn, was in zip((blk.bn1, blk.bn2, blk.bn3), before):
        assert not np.allclose(was, bn._buffers["_mean"].numpy())
    blk.eval()
    kept = blk.bn3._buffers["_mean"].numpy().copy()
    out = blk(inp).numpy()
    np.testing.assert_allclose(kept, blk.bn3._buffers["_mean"].numpy())
    if nhwc:
        ref = BottleneckBlock(16, 4)
        ref.set_state_dict(blk.state_dict())
        ref.eval()
        np.testing.assert_allclose(
            out, ref(paddle.to_tensor(x)).numpy().transpose(0, 2, 3, 1),
            rtol=1e-4, atol=1e-5)


def test_sdpa_attention_dropout_path():
    """Dropout on attention probabilities is the composed path alone:
    it bites in training, a seed repeats it, and `training=False` or
    `dropout_p=0` is the plain attention."""
    rng = np.random.RandomState(6)
    q, k, v = (paddle.to_tensor(rng.randn(2, 8, 2, 16).astype("float32"))
               for _ in range(3))
    plain = F.scaled_dot_product_attention(q, k, v, is_causal=True).numpy()
    paddle.seed(11)
    a = F.scaled_dot_product_attention(q, k, v, dropout_p=0.5,
                                       is_causal=True).numpy()
    paddle.seed(11)
    b = F.scaled_dot_product_attention(q, k, v, dropout_p=0.5,
                                       is_causal=True).numpy()
    np.testing.assert_array_equal(a, b)
    assert not np.allclose(a, plain, atol=1e-3)
    off = F.scaled_dot_product_attention(q, k, v, dropout_p=0.5,
                                         is_causal=True, training=False)
    np.testing.assert_allclose(off.numpy(), plain, rtol=1e-5, atol=1e-6)


def test_rope_rotation_property():
    # rotating by position p then attending is equivalent to relative shift:
    # check norm preservation (rotation is orthogonal)
    q = paddle.to_tensor(np.random.randn(1, 8, 2, 16).astype("float32"))
    k = paddle.to_tensor(np.random.randn(1, 8, 2, 16).astype("float32"))
    pos = paddle.to_tensor(np.arange(8, dtype="int32")[None, :])
    qr, kr = F.apply_rotary_pos_emb(q, k, pos)
    np.testing.assert_allclose(
        np.linalg.norm(q.numpy(), axis=-1),
        np.linalg.norm(qr.numpy(), axis=-1), rtol=1e-5)


def test_conformer_ctc_trains():
    import paddle_tpu as paddle
    from paddle_tpu.models import conformer_tiny

    paddle.seed(0)
    model = conformer_tiny()
    rng = np.random.RandomState(0)
    feats = paddle.to_tensor(rng.randn(2, 64, 32).astype("float32"))
    labels = paddle.to_tensor(rng.randint(1, 29, (2, 4)).astype("int64"))
    # T'=16 >= 2L+1=9: every alignment feasible, loss stays finite

    logits = model(feats)
    assert logits.shape == [2, 16, 31]  # T/4, vocab+blank

    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    losses = []
    for _ in range(3):   # suite-budget trim: 6 -> 4 -> 3 eager steps
        loss = model.loss(feats, labels)   # (same decreasing-loss bar)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_conformer_nondiv4_feat_dim():
    from paddle_tpu.models.conformer import ConformerCTC
    m = ConformerCTC(feat_dim=30, dim=32, num_blocks=1, num_heads=4,
                     vocab_size=20)
    feats = paddle.to_tensor(np.random.RandomState(0).randn(2, 32, 30)
                             .astype("float32"))
    assert m(feats).shape == [2, 8, 21]


def test_ctc_infeasible_alignment_is_huge_loss():
    import paddle_tpu as paddle
    import jax.numpy as jnp
    import jax
    T, B, C = 4, 1, 6
    lp = paddle.to_tensor(np.asarray(
        jax.nn.log_softmax(jnp.zeros((T, B, C)), -1)))
    labels = paddle.to_tensor(np.array([[1, 1, 1, 1]], np.int64))  # repeats need blanks: min path 2L-1=7 > T
    il = paddle.to_tensor(np.array([T], np.int64))
    ll = paddle.to_tensor(np.array([4], np.int64))
    out = paddle.nn.functional.ctc_loss(lp, labels, il, ll, blank=0,
                                        reduction="none")
    assert float(out.numpy()[0]) > 1e20  # unmissable signal, not silent 69


def test_conformer_length_masking():
    """Padding must not change a short utterance's loss/logits."""
    from paddle_tpu.models.conformer import ConformerCTC
    import paddle_tpu as paddle

    paddle.seed(0)
    m = ConformerCTC(feat_dim=16, dim=32, num_blocks=1, num_heads=4,
                     vocab_size=20)
    m.eval()
    # trained models have nonzero biases; zero-init would hide conv-module
    # padding leaks (the GLU re-populates padded rows via LN/pw1 biases)
    import jax.numpy as jnp
    for n, p in m.named_parameters():
        if n.endswith("bias") or "norm" in n:
            p._value = jnp.full_like(p._value, 0.5)
    rng = np.random.RandomState(0)
    feats_short = rng.randn(1, 32, 16).astype("float32")
    # same content zero-padded to 64 frames, with true length 32
    feats_padded = np.concatenate(
        [feats_short, np.zeros((1, 32, 16), np.float32)], axis=1)
    lens = paddle.to_tensor(np.array([32], np.int64))

    lo_short = m(paddle.to_tensor(feats_short)).numpy()
    lo_padded = m(paddle.to_tensor(feats_padded),
                  feat_lengths=lens).numpy()
    np.testing.assert_allclose(lo_padded[:, :8], lo_short[:, :8],
                               rtol=1e-4, atol=1e-4)

    labels = paddle.to_tensor(np.array([[3, 5]], np.int64))
    l1 = float(m.loss(paddle.to_tensor(feats_short), labels).numpy())
    l2 = float(m.loss(paddle.to_tensor(feats_padded), labels,
                      feat_lengths=lens).numpy())
    np.testing.assert_allclose(l1, l2, rtol=1e-4)
