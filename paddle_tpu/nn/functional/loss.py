"""Loss functionals (reference: python/paddle/nn/functional/loss.py; kernels
phi/kernels cross_entropy/bce/...)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...ops._helpers import apply, wrap, Tensor


def _reduce(out, reduction):
    if reduction == "mean":
        return jnp.mean(out)
    if reduction == "sum":
        return jnp.sum(out)
    return out


from functools import partial as _partial


@_partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _ce_hard(logits, label, axis, reduction, ignore_index):
    out, _ = _ce_hard_fwd(logits, label, axis, reduction, ignore_index)
    return out


def _ce_hard_fwd(logits, label, axis, reduction, ignore_index):
    # two fused reduction passes over logits (max, then exp-sum in f32
    # accumulation); residuals are only [T]-sized, logits itself is the one
    # big tensor kept alive for the backward.
    m = jnp.max(logits, axis=axis, keepdims=True)
    sumexp = jnp.sum(jnp.exp((logits - m).astype(jnp.float32)), axis=axis,
                     keepdims=True)
    lse = m.astype(jnp.float32) + jnp.log(sumexp)
    safe = jnp.where(label == ignore_index, 0, label)
    # the label's logit as a masked reduction, not a gather: exact (one
    # non-zero term per row), it fuses into the passes above, and XLA:TPU
    # spends minutes generating code for a one-element-per-row gather out
    # of a [tokens, 50k] operand (197 s against 0.8 s, PERF.md PR 21)
    cols = jax.lax.broadcasted_iota(safe.dtype, logits.shape,
                                    axis % logits.ndim)
    picked = jnp.sum(
        jnp.where(cols == jnp.expand_dims(safe, axis), logits, 0)
        .astype(jnp.float32), axis=axis, keepdims=True)
    loss = jnp.squeeze(lse - picked, axis)
    mask = (label != ignore_index)
    loss = jnp.where(mask, loss, 0.0)
    denom = None
    if reduction == "mean":
        denom = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
        out = (jnp.sum(loss) / denom).astype(logits.dtype)
    elif reduction == "sum":
        out = jnp.sum(loss).astype(logits.dtype)
    else:
        out = loss.astype(logits.dtype)
    return out, (logits, safe, mask, jnp.squeeze(lse, axis), denom)


def _ce_hard_bwd(axis, reduction, ignore_index, res, g):
    logits, safe, mask, lse, denom = res
    gf = jnp.asarray(g, jnp.float32)
    if reduction == "mean":
        scale = gf / denom
    elif reduction == "sum":
        scale = gf
    else:
        scale = gf  # per-element [*T] cotangent
    scale = scale * mask.astype(jnp.float32)
    p = jnp.exp(logits.astype(jnp.float32) - jnp.expand_dims(lse, axis))
    onehot = jax.nn.one_hot(safe, logits.shape[axis], axis=axis,
                            dtype=jnp.float32)
    d = (p - onehot) * jnp.expand_dims(scale, axis)
    return d.astype(logits.dtype), None


_ce_hard.defvjp(_ce_hard_fwd, _ce_hard_bwd)


def _ce_impl(logits, label, *, soft_label, axis, use_softmax, reduction,
             ignore_index, has_weight, fwd_ad=False):
    if not soft_label and use_softmax and not fwd_ad:
        # hard-label softmax CE: hand-written vjp (below) — the AD of the
        # composed log_softmax+take_along_axis would materialize logp AND a
        # scattered d_logp over the full [T, V] logits (23 ms/step of pure
        # HBM traffic at the flagship 16k x 50k shape); the fused backward
        # is one fused pass: d_logits = (softmax - onehot) * mask * g.
        lbl = label
        if lbl.ndim == logits.ndim:
            lbl = jnp.squeeze(lbl, axis=axis)
        return _ce_hard(logits, lbl, axis, reduction, ignore_index)
    if soft_label:
        if use_softmax:
            logp = jax.nn.log_softmax(logits, axis=axis)
        else:
            logp = jnp.log(jnp.maximum(logits, 1e-30))
        loss = -jnp.sum(label * logp, axis=axis)
    else:
        # reachable with use_softmax=False (inputs already probabilities)
        # or under forward-mode AD (composed ops differentiate in any mode)
        if use_softmax:
            logp = jax.nn.log_softmax(logits, axis=axis)
        else:
            logp = jnp.log(jnp.maximum(logits, 1e-30))
        lbl = label
        if lbl.ndim == logp.ndim:
            lbl = jnp.squeeze(lbl, axis=axis)
        safe = jnp.where(lbl == ignore_index, 0, lbl)
        picked = jnp.take_along_axis(logp, jnp.expand_dims(safe, axis), axis=axis)
        loss = -jnp.squeeze(picked, axis)
        mask = (lbl != ignore_index)
        loss = jnp.where(mask, loss, 0.0)
        if reduction == "mean":
            denom = jnp.maximum(jnp.sum(mask.astype(loss.dtype)), 1.0)
            return jnp.sum(loss) / denom
    return _reduce(loss, reduction)


def _ce_weight_impl(logits, label, weight, *, soft_label, axis, use_softmax,
                    reduction, ignore_index):
    logp = jax.nn.log_softmax(logits, axis=axis) if use_softmax else jnp.log(jnp.maximum(logits, 1e-30))
    lbl = label
    if lbl.ndim == logp.ndim:
        lbl = jnp.squeeze(lbl, axis=axis)
    safe = jnp.where(lbl == ignore_index, 0, lbl)
    picked = jnp.take_along_axis(logp, jnp.expand_dims(safe, axis), axis=axis)
    loss = -jnp.squeeze(picked, axis)
    w = jnp.take(weight, safe)
    mask = (lbl != ignore_index).astype(loss.dtype)
    loss = loss * w * mask
    if reduction == "mean":
        return jnp.sum(loss) / jnp.maximum(jnp.sum(w * mask), 1e-12)
    return _reduce(loss, reduction)


def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0, name=None):
    """Reference: F.cross_entropy (loss.py). Fused softmax+gather — XLA fuses
    the log_softmax/take_along_axis pipeline into one kernel."""
    x, l = wrap(input), wrap(label)
    if label_smoothing > 0.0 and not soft_label:
        from .common import one_hot
        nc = x.shape[axis]
        l = one_hot(l if l.ndim < x.ndim else l.squeeze(axis), nc)
        l = l * (1.0 - label_smoothing) + label_smoothing / nc
        soft_label = True
    if weight is not None and not soft_label:
        return apply("cross_entropy_w", _ce_weight_impl, (x, l, wrap(weight)),
                     {"soft_label": soft_label, "axis": int(axis),
                      "use_softmax": bool(use_softmax), "reduction": reduction,
                      "ignore_index": int(ignore_index)})
    from ...core.fwd_ad import forward_ad_active
    return apply("cross_entropy", _ce_impl, (x, l),
                 {"soft_label": bool(soft_label), "axis": int(axis),
                  "use_softmax": bool(use_softmax), "reduction": reduction,
                  "ignore_index": int(ignore_index), "has_weight": False,
                  "fwd_ad": forward_ad_active()})


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction="none", axis=axis)
    if return_softmax:
        from .activation import softmax
        return loss, softmax(logits, axis=axis)
    return loss


def _mse_impl(x, y, *, reduction):
    return _reduce(jnp.square(x - y), reduction)


def mse_loss(input, label, reduction="mean", name=None):
    return apply("mse_loss", _mse_impl, (wrap(input), wrap(label)),
                 {"reduction": reduction})


def _l1_impl(x, y, *, reduction):
    return _reduce(jnp.abs(x - y), reduction)


def l1_loss(input, label, reduction="mean", name=None):
    return apply("l1_loss", _l1_impl, (wrap(input), wrap(label)),
                 {"reduction": reduction})


def _smooth_l1_impl(x, y, *, reduction, delta):
    d = x - y
    ad = jnp.abs(d)
    loss = jnp.where(ad < delta, 0.5 * d * d / delta, ad - 0.5 * delta)
    return _reduce(loss, reduction)


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    return apply("smooth_l1", _smooth_l1_impl, (wrap(input), wrap(label)),
                 {"reduction": reduction, "delta": float(delta)})


def _huber_impl(x, y, *, reduction, delta):
    d = x - y
    ad = jnp.abs(d)
    loss = jnp.where(ad <= delta, 0.5 * d * d, delta * (ad - 0.5 * delta))
    return _reduce(loss, reduction)


def huber_loss(input, label, delta=1.0, reduction="mean", name=None):
    return apply("huber", _huber_impl, (wrap(input), wrap(label)),
                 {"reduction": reduction, "delta": float(delta)})


def _nll_impl(logp, label, *, reduction, ignore_index):
    safe = jnp.where(label == ignore_index, 0, label)
    picked = jnp.take_along_axis(logp, safe[..., None] if logp.ndim == label.ndim + 1 else safe, axis=1 if logp.ndim > 1 else 0)
    if picked.ndim > label.ndim:
        picked = jnp.squeeze(picked, 1)
    loss = -picked
    mask = label != ignore_index
    loss = jnp.where(mask, loss, 0.0)
    if reduction == "mean":
        return jnp.sum(loss) / jnp.maximum(jnp.sum(mask.astype(loss.dtype)), 1.0)
    return _reduce(loss, reduction)


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean",
             name=None):
    return apply("nll", _nll_impl, (wrap(input), wrap(label)),
                 {"reduction": reduction, "ignore_index": int(ignore_index)})


def _bce_impl(x, y, *, reduction, eps):
    x = jnp.clip(x, eps, 1.0 - eps)
    loss = -(y * jnp.log(x) + (1.0 - y) * jnp.log(1.0 - x))
    return _reduce(loss, reduction)


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):
    out = apply("bce", _bce_impl, (wrap(input), wrap(label)),
                {"reduction": "none", "eps": 1e-12})
    if weight is not None:
        out = out * wrap(weight)
    from ...ops.reduction import mean as _mean, sum as _sum
    if reduction == "mean":
        return _mean(out)
    if reduction == "sum":
        return _sum(out)
    return out


def _bce_logits_impl(x, y, *, reduction):
    loss = jnp.maximum(x, 0) - x * y + jnp.log1p(jnp.exp(-jnp.abs(x)))
    return _reduce(loss, reduction)


def binary_cross_entropy_with_logits(logit, label, weight=None, reduction="mean",
                                     pos_weight=None, name=None):
    if pos_weight is not None:
        lw = apply("bce_logits_pw", _bce_logits_pw_impl,
                   (wrap(logit), wrap(label), wrap(pos_weight)), {"reduction": "none"})
    else:
        lw = apply("bce_logits", _bce_logits_impl, (wrap(logit), wrap(label)),
                   {"reduction": "none"})
    if weight is not None:
        lw = lw * wrap(weight)
    from ...ops.reduction import mean as _mean, sum as _sum
    if reduction == "mean":
        return _mean(lw)
    if reduction == "sum":
        return _sum(lw)
    return lw


def _bce_logits_pw_impl(x, y, pw, *, reduction):
    log_w = (pw - 1.0) * y + 1.0
    loss = (1.0 - y) * x + log_w * (jnp.log1p(jnp.exp(-jnp.abs(x))) + jnp.maximum(-x, 0))
    return _reduce(loss, reduction)


def _kl_impl(x, y, *, reduction, log_target):
    if log_target:
        loss = jnp.exp(y) * (y - x)
    else:
        loss = y * (jnp.log(jnp.maximum(y, 1e-30)) - x)
    if reduction == "batchmean":
        return jnp.sum(loss) / x.shape[0]
    return _reduce(loss, reduction)


def kl_div(input, label, reduction="mean", log_target=False, name=None):
    return apply("kl_div", _kl_impl, (wrap(input), wrap(label)),
                 {"reduction": reduction, "log_target": bool(log_target)})


def _margin_ranking_impl(x, y, label, *, margin, reduction):
    loss = jnp.maximum(0.0, -label * (x - y) + margin)
    return _reduce(loss, reduction)


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean",
                        name=None):
    return apply("margin_ranking", _margin_ranking_impl,
                 (wrap(input), wrap(other), wrap(label)),
                 {"margin": float(margin), "reduction": reduction})


def _hinge_impl(x, y, *, reduction):
    loss = jnp.maximum(0.0, 1.0 - x * y)
    return _reduce(loss, reduction)


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    return apply("hinge_embed", _hinge_embed_impl, (wrap(input), wrap(label)),
                 {"margin": float(margin), "reduction": reduction})


def _hinge_embed_impl(x, y, *, margin, reduction):
    loss = jnp.where(y == 1.0, x, jnp.maximum(0.0, margin - x))
    return _reduce(loss, reduction)


def _cosine_embed_impl(x1, x2, y, *, margin, reduction):
    cos = jnp.sum(x1 * x2, -1) / jnp.maximum(
        jnp.linalg.norm(x1, axis=-1) * jnp.linalg.norm(x2, axis=-1), 1e-12)
    loss = jnp.where(y == 1, 1.0 - cos, jnp.maximum(0.0, cos - margin))
    return _reduce(loss, reduction)


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean",
                          name=None):
    return apply("cosine_embed", _cosine_embed_impl,
                 (wrap(input1), wrap(input2), wrap(label)),
                 {"margin": float(margin), "reduction": reduction})


def _triplet_impl(a, p, n, *, margin, p_norm, swap, reduction):
    dp = jnp.linalg.norm(a - p, ord=p_norm, axis=-1)
    dn = jnp.linalg.norm(a - n, ord=p_norm, axis=-1)
    if swap:
        dpn = jnp.linalg.norm(p - n, ord=p_norm, axis=-1)
        dn = jnp.minimum(dn, dpn)
    loss = jnp.maximum(dp - dn + margin, 0.0)
    return _reduce(loss, reduction)


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean", name=None):
    return apply("triplet", _triplet_impl,
                 (wrap(input), wrap(positive), wrap(negative)),
                 {"margin": float(margin), "p_norm": float(p), "swap": bool(swap),
                  "reduction": reduction})


def _soft_margin_impl(x, y, *, reduction):
    loss = jnp.log1p(jnp.exp(-y * x))
    return _reduce(loss, reduction)


def soft_margin_loss(input, label, reduction="mean", name=None):
    return apply("soft_margin", _soft_margin_impl, (wrap(input), wrap(label)),
                 {"reduction": reduction})


def _poisson_nll_impl(x, y, *, log_input, full, eps, reduction):
    if log_input:
        loss = jnp.exp(x) - y * x
    else:
        loss = x - y * jnp.log(x + eps)
    if full:
        stirling = y * jnp.log(y + eps) - y + 0.5 * jnp.log(2 * jnp.pi * (y + eps))
        loss = loss + jnp.where(y > 1, stirling, 0.0)
    return _reduce(loss, reduction)


def poisson_nll_loss(input, label, log_input=True, full=False, epsilon=1e-8,
                     reduction="mean", name=None):
    return apply("poisson_nll", _poisson_nll_impl, (wrap(input), wrap(label)),
                 {"log_input": bool(log_input), "full": bool(full),
                  "eps": float(epsilon), "reduction": reduction})


def _mlsm_impl(x, y, *, reduction):
    # multi-label soft margin
    loss = -(y * jax.nn.log_sigmoid(x) + (1 - y) * jax.nn.log_sigmoid(-x))
    loss = jnp.mean(loss, axis=-1)
    return _reduce(loss, reduction)


def multi_label_soft_margin_loss(input, label, weight=None, reduction="mean",
                                 name=None):
    return apply("mlsm", _mlsm_impl, (wrap(input), wrap(label)),
                 {"reduction": reduction})


def square_error_cost(input, label):
    return apply("square_error", _square_error_impl, (wrap(input), wrap(label)))


def _square_error_impl(x, y):
    return jnp.square(x - y)


def log_loss(input, label, epsilon=1e-4, name=None):
    return apply("log_loss", _log_loss_impl, (wrap(input), wrap(label)),
                 {"eps": float(epsilon)})


def _log_loss_impl(x, y, *, eps):
    return -y * jnp.log(x + eps) - (1.0 - y) * jnp.log(1.0 - x + eps)


def _ctc_loss_impl(log_probs, labels, input_lengths, label_lengths, *, blank):
    # log_probs: [T, B, C] log-softmax already applied
    T, B, C = log_probs.shape
    S = labels.shape[1]
    # extended labels with blanks: [B, 2S+1]
    ext = jnp.full((B, 2 * S + 1), blank, dtype=labels.dtype)
    ext = ext.at[:, 1::2].set(labels)
    ext_len = 2 * label_lengths + 1

    neg_inf = -1e30
    alpha = jnp.full((B, 2 * S + 1), neg_inf)
    alpha = alpha.at[:, 0].set(log_probs[0, :, blank])
    alpha = alpha.at[:, 1].set(jnp.take_along_axis(log_probs[0], ext[:, 1:2], axis=1)[:, 0])

    def logsumexp3(a, b, c):
        m = jnp.maximum(jnp.maximum(a, b), c)
        m_safe = jnp.where(m == neg_inf, 0.0, m)
        # clamp the sum away from 0: jnp.where still differentiates the
        # unselected branch, and d/dx log(0) poisons every grad with NaN.
        # The floor must be a NORMAL f32 (1e-38 is subnormal; flush-to-zero
        # turns 1/floor into inf and the zero cotangent into NaN)
        s = jnp.exp(a - m_safe) + jnp.exp(b - m_safe) + jnp.exp(c - m_safe)
        return jnp.where(
            m == neg_inf, neg_inf,
            m_safe + jnp.log(jnp.maximum(s, 1e-30)))

    same = jnp.concatenate([jnp.full((B, 2), False),
                            ext[:, 2:] == ext[:, :-2]], axis=1)

    def step(alpha, logp_t):
        prev1 = jnp.concatenate([jnp.full((B, 1), neg_inf), alpha[:, :-1]], axis=1)
        prev2 = jnp.concatenate([jnp.full((B, 2), neg_inf), alpha[:, :-2]], axis=1)
        prev2 = jnp.where(same, neg_inf, prev2)
        blank_mask = ext == blank
        prev2 = jnp.where(blank_mask, neg_inf, prev2)
        a = logsumexp3(alpha, prev1, prev2)
        emit = jnp.take_along_axis(logp_t, ext, axis=1)
        return a + emit, None

    def scan_step(carry, t):
        alpha = carry
        new_alpha, _ = step(alpha, log_probs[t])
        # freeze past input length
        new_alpha = jnp.where((t < input_lengths)[:, None], new_alpha, alpha)
        return new_alpha, None

    alpha, _ = jax.lax.scan(scan_step, alpha, jnp.arange(1, T))
    idx_last = (ext_len - 1)[:, None]
    a_last = jnp.take_along_axis(alpha, idx_last, axis=1)[:, 0]
    a_prev = jnp.take_along_axis(alpha, jnp.maximum(idx_last - 1, 0), axis=1)[:, 0]
    m = jnp.maximum(a_last, a_prev)
    m_safe = jnp.where(m == neg_inf, 0.0, m)
    s = jnp.exp(a_last - m_safe) + jnp.exp(a_prev - m_safe)
    # infeasible alignment (input shorter than 2L+1) must surface as a huge
    # loss, not a silent finite value; the where keeps its gradient NaN-free
    total = jnp.where(m == neg_inf, neg_inf,
                      m_safe + jnp.log(jnp.maximum(s, 1e-30)))
    return -total


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC via in-graph dynamic programming (lax.scan over time) — the
    reference uses warpctc (phi/kernels/gpu/warpctc_kernel.cu); this is the
    XLA-native equivalent."""
    out = apply("ctc_loss", _ctc_loss_impl,
                (wrap(log_probs), wrap(labels), wrap(input_lengths),
                 wrap(label_lengths)), {"blank": int(blank)})
    from ...ops.reduction import mean as _mean, sum as _sum
    if reduction == "mean":
        ll = wrap(label_lengths)
        normed = out / ll.astype(out.dtype)
        return _mean(normed)
    if reduction == "sum":
        return _sum(out)
    return out
