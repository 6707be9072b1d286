"""Sparse-expert feed-forward layer of the served decoder (Qwen3-MoE class).

    p = softmax_f32(W_r h)                       over all E experts
    y = sum_{e in topk(p)} w_e * W_down,e (silu(W_gate,e h) * W_up,e h)
    w_e = p_e / sum_topk p   (`norm_topk_prob`), else p_e

No token is dropped and no capacity is set: every position gets exactly its
k experts. The experts are stacked `[E, ...]` (gate and up fused along the
last axis), and `apply_experts` picks one of two exact schedules from its
static shapes:

* few positions (positions x k <= E): one pass over the positions x k
  assignments, each reading its own expert's weights out of the stack — at
  most positions x k experts' bytes move, not all E;
* many positions: one pass over the E experts, each applied to every
  position and weighted 0 where it was not chosen — every expert's bytes
  move once.

"Positions" are the DISPATCH's, not one sequence's: under `jax.vmap` with
the weights unbatched (the decode engine's block-diffusion step batches a
bucket's per-sequence forwards that way) `apply_experts` folds the batch
axis into the positions and chooses the schedule for all of them, so a
bucket of 16 blocks of 4 is 64 positions in one pass over the experts, and
never a gather that copies each sequence's chosen experts out of the
stack. A prompt chunk comes un-batched and is its own 256 positions. The
router stays an ordinary traced function: its counts are per sequence.

`distributed/moe.py::MoELayer` is the GShard capacity-factor training layer
and drops tokens; this one serves.

The layer also returns how many positions chose each expert (`[E]` int32).
A caller that wants the counts opens `expert_counts()` around the forward
and finds one array a layer in the list it yields (the decode engine sums
them over a dispatch); outside such a block they are dropped.
"""
from __future__ import annotations

import contextlib
import math
import threading

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import apply

_TLS = threading.local()


@contextlib.contextmanager
def expert_counts():
    """Collect every `SparseExperts` forward's per-expert position counts
    traced inside the block, in layer order."""
    prev = getattr(_TLS, "counts", None)
    _TLS.counts = out = []
    try:
        yield out
    finally:
        _TLS.counts = prev


def route(h, router_w, top_k, norm_topk, *, bias=None, score="softmax",
          n_group=0, topk_group=0, scale=1.0, held=None, valid=None):
    """(expert ids [T,k], their weights [T,k] float32, counts int32).

    The scores run in float32 over ALL the router's experts: a softmax, or
    with `score="sigmoid"` each expert's own sigmoid. A selection `bias`
    [E] is added to the scores for the choice only; the weights are the
    scores themselves. With `n_group` groups of which `topk_group` are
    kept, a group's score is the sum of its two largest (biased) scores and
    the choice is made inside the best groups. `lax.top_k` takes the lower
    index of two equal scores. The chosen weights are divided by their sum
    (`norm_topk`) and multiplied by `scale`.

    `held = (first, count)`: this layer holds experts `first ..
    first + count - 1` of the router's E and no other. The ids come back
    relative to `first`, a choice that fell on an absent expert as `count`
    (past the stack: `apply_experts` adds nothing for it) with weight 0,
    and `counts` is over the held experts, `[count]`; else `[E]`.
    `valid` [T] bool leaves padding positions out of the counts."""
    logits = jnp.einsum("th,he->te", h.astype(jnp.float32),
                        router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    n = router_w.shape[-1]
    if score == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    chosen_by = probs if bias is None else probs + bias.astype(jnp.float32)
    if n_group > 1 and topk_group < n_group:
        grouped = chosen_by.reshape(-1, n_group, n // n_group)
        best2, _ = jax.lax.top_k(grouped, 2)
        _, keep = jax.lax.top_k(jnp.sum(best2, axis=-1), topk_group)
        kept = jnp.zeros(grouped.shape[:2], bool).at[
            jnp.arange(grouped.shape[0])[:, None], keep].set(True)
        chosen_by = jnp.where(kept[..., None], grouped,
                              -jnp.inf).reshape(-1, n)
    if chosen_by is probs:
        w, idx = jax.lax.top_k(probs, top_k)
    else:
        _, idx = jax.lax.top_k(chosen_by, top_k)
        w = jnp.take_along_axis(probs, idx, axis=-1)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    if scale != 1.0:
        w = w * scale
    counted = n
    if held is not None:
        first, counted = held
        here = (idx >= first) & (idx < first + counted)
        idx = jnp.where(here, idx - first, counted)
        w = jnp.where(here, w, 0.0)
    ones = 1 if valid is None else jnp.broadcast_to(
        valid[:, None], idx.shape).reshape(-1).astype(jnp.int32)
    counts = jnp.zeros(counted, jnp.int32).at[idx.reshape(-1)].add(ones)
    return idx, w, counts


def _expert(h, gate_up, down):
    """One expert's SwiGLU on h [T, hidden]: float32 accumulation, the
    activation in the activations' dtype."""
    gu = jnp.dot(h, gate_up, preferred_element_type=jnp.float32)
    gate, up = jnp.split(gu, 2, axis=-1)
    act = (jax.nn.silu(gate) * up).astype(h.dtype)
    return jnp.dot(act, down, preferred_element_type=jnp.float32)


def _by_assignment(h, idx, w, gate_up, down):
    """positions x k passes, each reading one expert out of the stack."""
    t, k = idx.shape

    def body(a, acc):
        row, e = a // k, idx.reshape(-1)[a]
        x = jax.lax.dynamic_slice_in_dim(h, row, 1, axis=0)
        y = _expert(x, gate_up[e], down[e]) * w.reshape(-1)[a]
        return jax.lax.dynamic_update_slice_in_dim(
            acc, jax.lax.dynamic_slice_in_dim(acc, row, 1, axis=0) + y,
            row, axis=0)

    return jax.lax.fori_loop(0, t * k, body,
                             jnp.zeros(h.shape, jnp.float32))


def _by_expert(h, idx, w, gate_up, down):
    """E passes, each expert over every position, weight 0 where the
    position did not choose it."""
    n = gate_up.shape[0]
    # [T, E]: the chosen experts' weights scattered over all experts
    dense = jnp.zeros((h.shape[0], n), jnp.float32).at[
        jnp.arange(h.shape[0])[:, None], idx].set(w)

    def body(acc, x):
        gu, dn, col = x
        return acc + _expert(h, gu, dn) * col[:, None], None

    acc, _ = jax.lax.scan(body, jnp.zeros(h.shape, jnp.float32),
                          (gate_up, down, dense.T))
    return acc


def experts_read(positions, top_k, num_experts):
    """How many experts' weights the schedule `apply_experts` picks for
    `positions` positions reads out of a layer's stack."""
    return min(positions * top_k, num_experts)


@jax.custom_batching.custom_vmap
def apply_experts(h, idx, w, gate_up, down):
    """The chosen experts applied to h [T, hidden]: float32 [T, hidden],
    sum over a position's k experts `idx` [T, k] of their SwiGLU weighted
    by `w` [T, k]."""
    few = idx.size <= gate_up.shape[0]
    return (_by_assignment if few else _by_expert)(h, idx, w, gate_up, down)


@apply_experts.def_vmap
def _fold_batch(axis_size, in_batched, h, idx, w, gate_up, down):
    """Batched positions over shared weights are more positions."""
    if list(in_batched) != [True, True, True, False, False]:
        # nobody serves so (a stack of experts a sequence, say): nothing
        # is shared, each sequence on its own
        axes = [0 if b else None for b in in_batched]
        return jax.vmap(apply_experts.fun, in_axes=axes)(
            h, idx, w, gate_up, down), True

    def fold(a):
        return a.reshape((-1,) + a.shape[2:])

    y = apply_experts(fold(h), fold(idx), fold(w), gate_up, down)
    return y.reshape(h.shape), True


def _sparse_experts_impl(x, router_w, gate_up, down, *extra, top_k,
                         norm_topk, has_bias=False, valid=False, **routing):
    """`extra`: the router's selection bias [E] (`has_bias`), then how many
    of x's positions (its axis -2) are real (`valid`)."""
    extra = list(extra)
    bias = extra.pop(0) if has_bias else None
    shape = x.shape
    h = x.reshape(-1, shape[-1])
    live = None
    if valid:
        live = jnp.broadcast_to(jnp.arange(shape[-2]) < extra.pop(0),
                                shape[:-1]).reshape(-1)
    idx, w, counts = route(h, router_w, top_k, norm_topk, bias=bias,
                           valid=live, **routing)
    y = apply_experts(h, idx, w, gate_up, down)
    return y.astype(x.dtype).reshape(shape), counts


class SparseExperts(nn.Layer):
    """SwiGLU experts of width `moe_intermediate_size`,
    `num_experts_per_tok` a position out of the router's `num_experts`, no
    bias in any projection. The router is a softmax over the experts or,
    by `cfg.moe_score_function`, their sigmoids, with a selection bias
    (`moe_router_bias`), a limit to the best `moe_topk_group` of
    `moe_n_group` groups and a `routed_scaling_factor`; one shared expert
    of `moe_shared_expert_intermediate_size` is added to every position.

    `cfg.experts_held = (first, count)` makes the layer one chip's share of
    an expert-parallel deployment: it routes over all `num_experts`, holds
    the weights of its own `count` and returns their part of the result
    (plus the shared expert, which every chip computes alike). What the
    absent experts would add is added by nobody here: no exchange is run
    and none is stood in for."""

    def __init__(self, cfg):
        super().__init__()
        h, m, n = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
        if not 1 <= cfg.num_experts_per_tok <= n or m < 1:
            raise ValueError(
                f"sparse experts need 1 <= num_experts_per_tok "
                f"({cfg.num_experts_per_tok}) <= num_experts ({n}) and a "
                f"moe_intermediate_size ({m})")
        std = cfg.initializer_range
        self.top_k = int(cfg.num_experts_per_tok)
        self.norm_topk = bool(cfg.norm_topk_prob)
        # what `route` is told beyond the softmax top-k: empty for a layer
        # that uses none of it, whose traced program stays as it was
        self.routing = {}
        if cfg.moe_score_function != "softmax":
            self.routing["score"] = cfg.moe_score_function
        if cfg.moe_n_group > 1:
            self.routing.update(n_group=int(cfg.moe_n_group),
                                topk_group=int(cfg.moe_topk_group))
        if cfg.routed_scaling_factor != 1.0:
            self.routing["scale"] = float(cfg.routed_scaling_factor)
        self.held = tuple(cfg.experts_held) or None
        if self.held:
            self.routing["held"] = self.held
        held = self.held[1] if self.held else n
        normal = nn.initializer.Normal
        self.router = nn.Linear(h, n, bias_attr=False, weight_attr=nn.ParamAttr(
            initializer=normal(0.0, std)))
        self.router_bias = self.create_parameter(
            [n], default_initializer=nn.initializer.Constant(0.0)) \
            if cfg.moe_router_bias else None
        self.experts_gate_up = self.create_parameter(
            [held, h, 2 * m], default_initializer=normal(0.0, std))
        self.experts_down = self.create_parameter(
            [held, m, h], default_initializer=normal(
                0.0, std / math.sqrt(2 * cfg.num_layers)))
        self.shared = None
        if cfg.moe_shared_expert_intermediate_size:
            from .gpt import GPTMLP

            self.shared = GPTMLP(cfg, cfg.moe_shared_expert_intermediate_size)

    def forward(self, x, valid_len=None):
        """`valid_len`: how many of x's positions are real (a prompt
        chunk's bucket padding is routed like any position and counted
        nowhere)."""
        args = [x, self.router.weight, self.experts_gate_up,
                self.experts_down]
        statics = {"top_k": self.top_k, "norm_topk": self.norm_topk,
                   **self.routing}
        if self.router_bias is not None:
            args.append(self.router_bias)
            statics["has_bias"] = True
        if valid_len is not None:
            args.append(valid_len)
            statics["valid"] = True
        y, counts = apply("sparse_experts", _sparse_experts_impl, args,
                          statics)
        sink = getattr(_TLS, "counts", None)
        if sink is not None:
            sink.append(counts._value)
        return y if self.shared is None else y + self.shared(x)
