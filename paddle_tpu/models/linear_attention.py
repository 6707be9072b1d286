"""Gated delta-rule linear attention: a layer whose cache is a fixed-size
recurrent state, not a row a position.

Per head h (d_k keys, d_v values), with x the layer's input and no bias:

    q~, k~, v~ = W_q x, W_k x, W_v x      each channel through a causal
                                           depthwise convolution over the
                                           last K positions, then SiLU
    q_t = q~ / |q~|_2 * d_k^-1/2           k_t = k~ / |k~|_2
    beta_t  = sigmoid(W_b x)               (x 2 with `allow_neg_eigval`)
    g_t     = -exp(A_log) * softplus(W_a x + dt_bias)      one a head, or
              bound * sigmoid(exp(A_log) * (W_a x + dt_bias))   one a KEY
              CHANNEL in (bound, 0) (`linear_gate_channels` with
              `linear_gate_lower_bound` < 0: the per-channel rule of Kimi
              Delta Attention, arXiv 2510.26692)
    S'  = S_{t-1} Diag(exp g_t)
    S_t = S' + beta_t (v_t - S' k_t) k_t^T
    o_t = S_t q_t                          S in R^{d_v x d_k}, S_0 = 0
    y   = W_o [ RMSNorm_{d_v}(o_t) * gate(W_g x) ]   gate SiLU or sigmoid

The log-decay `g` is `[.., H, d_k]` everywhere below; one decay a head is
the broadcast of it over d_k, and every form takes `[.., H]` as that.

The recurrence comes in three forms that compute the same thing
(`tests/test_linear_attention.py` holds them to each other and to the plain
reference):

* `delta_rule_recurrent`: position by position over a whole sequence (the
  uncached forward);
* `delta_rule_chunked`: a prompt chunk from a carried state. Inside a block
  of `CHUNK` positions the rule is a unit lower-triangular system: with
  G = cumsum(g) (a vector over d_k a position), the pseudo-values U solve
  (I + A) U = beta (V - (K * exp G) S_0^T),
  A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc) for j < i, and then
  O = (Q * exp G) S_0^T + P U with P_ij = sum_c q_ic k_jc exp(G_ic - G_jc)
  for j <= i, S_C = S_0 Diag(exp G_C) + U^T (K * exp(G_C - G)). The
  products `exp(G_i - G_j)` are never factored as exp(G_i) exp(-G_j) over
  the block: float32 holds exp(x) only for |x| < 88, and a gate bounded by
  -5 passes that in 18 positions. Inside a 16 x 16 tile on the diagonal the
  difference is taken channel by channel before the exponential (vector
  unit, exact for any gate); a tile below the diagonal is one matrix product
  of (k_i exp(G_i - R)) with (k_j exp(R - G_j)), R the running sum at the
  row tile's first position, so that both exponents are <= 0 and the worst
  that happens is an underflow to the 0 the true product rounds to.
  (I + A)^-1 is built for every block of the chunk at once (forward
  substitution on the 16 x 16 diagonal tiles, merged pairwise), so only the
  state's hand-over from block to block is sequential;
* `delta_rule_step`: one position (decode).

What a sequence carries between dispatches is `(window, state)`: the last
K - 1 inputs of the convolution (`[K-1, channels]`, the activations' dtype)
and S (`[heads, d_v, d_k]`, float32). Positions at or past `valid_len` (a
prompt chunk's bucket padding) leave both untouched: alpha 1, beta 0, and
the window is cut at `valid_len`.

All of the rule runs in float32: the state is an accumulator over the whole
sequence, and the triangular system loses its meaning in bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import nn
from .. import ops
from ..core.dispatch import apply
from ..nn import functional as F

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
#: positions a block of the chunked form solves together
CHUNK = 64
_BASE = 16
#: eps of the keys' and queries' L2 norm (x / sqrt(sum x^2 + eps))
L2_EPS = 1e-6


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HI, preferred_element_type=F32)


def causal_conv_silu(x, window, w):
    """x [s, C] new inputs, window [K-1, C] the inputs before them, w [K, C]:
    y_t = silu(sum_j w[j] * x_{t-K+1+j}) in float32, and the inputs laid end
    to end `[K-1+s, C]` (the next window is a slice of it)."""
    k, s = w.shape[0], x.shape[0]
    full = jnp.concatenate([window.astype(x.dtype), x], axis=0)
    y = sum(full[j:j + s].astype(F32) * w[j].astype(F32) for j in range(k))
    return jax.nn.silu(y), full


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _channels(g, k):
    """The log-decay a key channel `[.., H, dk]`: `g` as it is, or one a
    head `[.., H]` spread over the channels of `k`."""
    return g if g.ndim == k.ndim else jnp.broadcast_to(g[..., None], k.shape)


def delta_rule_step(q, k, v, g, beta, state):
    """One position: q, k [H, dk], v [H, dv], g the log-decay [H, dk] (or
    [H]), beta [H], state [H, dv, dk]. Returns (o [H, dv], the new state).
    Products and sums on the vector unit: exact float32, no matrix unit's
    rounding."""
    state = state * jnp.exp(_channels(g, k))[:, None, :]
    sk = jnp.sum(state * k[:, None, :], axis=-1)
    state = state + (beta[:, None] * (v - sk))[:, :, None] * k[:, None, :]
    return jnp.sum(state * q[:, None, :], axis=-1), state


def delta_rule_recurrent(q, k, v, g, beta, state):
    """The rule position by position over q, k [s, H, dk], v [s, H, dv],
    g [s, H, dk] (or [s, H]), beta [s, H] from `state`. Returns
    (o [s, H, dv], the final state)."""
    def body(st, x):
        o, st = delta_rule_step(*x, st)
        return st, o

    state, o = jax.lax.scan(body, state, (q, k, v, _channels(g, k), beta))
    return o, state


def _unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower-triangular a [..., n, n], n a multiple
    of 16 times a power of two: forward substitution row by row on the
    16 x 16 diagonal blocks (all of them at once), then pairs of blocks
    merged by inv([[P, 0], [R, Q]]) = [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]."""
    n = a.shape[-1]
    nb = n // _BASE
    diag = jnp.stack([a[..., i * _BASE:(i + 1) * _BASE,
                        i * _BASE:(i + 1) * _BASE] for i in range(nb)],
                     axis=-3)                        # [..., nb, 16, 16]
    eye = jnp.eye(_BASE, dtype=F32)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (_BASE,))]
    for i in range(1, _BASE):
        prev = jnp.stack(rows, axis=-2)              # [..., nb, i, 16]
        rows.append(eye[i] - jnp.sum(diag[..., i, :i, None] * prev,
                                     axis=-2))
    inv = jnp.stack(rows, axis=-2)
    blocks = [inv[..., i, :, :] for i in range(nb)]
    m = _BASE
    while len(blocks) > 1:
        merged = []
        for p in range(0, len(blocks), 2):
            top, bot = blocks[p], blocks[p + 1]
            r0, c0 = (p + 1) * m, p * m
            low = -_mm("...ij,...jk->...ik",
                       _mm("...ij,...jk->...ik", bot,
                           a[..., r0:r0 + m, c0:c0 + m]), top)
            merged.append(jnp.concatenate([
                jnp.concatenate([top, jnp.zeros_like(top)], axis=-1),
                jnp.concatenate([low, bot], axis=-1)], axis=-2))
        blocks, m = merged, 2 * m
    return blocks[0]


def _decayed_products(x, k, gc):
    """P_ij = sum_c x_ic k_jc exp(G_ic - G_jc) for j <= i, 0 above the
    diagonal: x, k, gc (the running sum G of the log-decay) [..., C, dk],
    C a multiple of 16. Tiles of 16 x 16: on the diagonal the difference
    of the sums goes through the exponential channel by channel; below it
    the two factors are taken from the row tile's first position, both
    exponents <= 0."""
    c, dk = x.shape[-2:]
    nb = c // _BASE
    lead = x.shape[:-2]
    xt, kt, gt = (t.reshape(lead + (nb, _BASE, dk)) for t in (x, k, gc))
    idx = jnp.arange(_BASE)
    seen = (idx[:, None] >= idx[None, :])[..., None]             # j <= i
    diff = gt[..., :, None, :] - gt[..., None, :, :]      # [.., nb,16,16,dk]
    diag = jnp.sum(xt[..., :, None, :] * kt[..., None, :, :]
                   * jnp.exp(jnp.where(seen, diff, -jnp.inf)), axis=-1)
    out = (diag[..., :, :, None, :]
           * jnp.eye(nb, dtype=F32)[:, None, :, None]).reshape(lead + (c, c))
    if nb > 1:
        start = gt[..., :1, :]                        # R: [.., nb, 1, dk]
        rows = xt * jnp.exp(gt - start)
        before = (jnp.arange(c)[None, :]
                  < (jnp.arange(nb) * _BASE)[:, None])[..., None]  # [nb,C,1]
        cols = k[..., None, :, :] * jnp.exp(jnp.where(
            before, start - gc[..., None, :, :], -jnp.inf))  # [.., nb,C,dk]
        out = out + _mm("...tid,...tjd->...tij", rows, cols).reshape(
            lead + (c, c))
    return out


def delta_rule_chunked(q, k, v, g, beta, state, chunk=CHUNK):
    """The rule over q, k [s, H, dk], v [s, H, dv], g [s, H, dk] (or
    [s, H]), beta [s, H] from `state`, in blocks of `chunk` positions (s is
    padded up to whole blocks with decay 1, beta 0, which leave the state
    as it is). Returns (o [s, H, dv], the final state)."""
    s = q.shape[0]
    g = _channels(g, k)
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1))
                            for t in (q, k, v, g, beta))
    n = (s + pad) // chunk

    def blocks(t):                       # [s, H, ...] -> [n, H, chunk, ...]
        return jnp.swapaxes(t.reshape((n, chunk) + t.shape[1:]), 1, 2)

    q, k, v, g, beta = (blocks(t) for t in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-2)                              # [n, H, C, dk]
    idx = jnp.arange(chunk)
    a = beta[..., :, None] * _decayed_products(k, k, gc) \
        * (idx[:, None] > idx[None, :])
    solve = _unit_lower_inverse(a)                           # (I + A)^-1
    qk = _decayed_products(q, k, gc)
    eg = jnp.exp(gc)
    kin, qin = k * eg, q * eg                  # what meets the old state
    kout = k * jnp.exp(gc[..., -1:, :] - gc)   # what reaches the new one
    last = eg[..., -1, :]                                    # [n, H, dk]

    def body(st, x):
        kin_c, qin_c, kout_c, vc, bc, last_c, solve_c, qk_c = x
        u = _mm("hij,hjv->hiv", solve_c,
                bc[..., None] * (vc - _mm("hid,hvd->hiv", kin_c, st)))
        o = _mm("hid,hvd->hiv", qin_c, st) + _mm("hij,hjv->hiv", qk_c, u)
        st = last_c[:, None, :] * st + _mm("hiv,hid->hvd", u, kout_c)
        return st, o

    state, o = jax.lax.scan(body, state,
                            (kin, qin, kout, v, beta, last, solve, qk))
    o = jnp.swapaxes(o, 1, 2)                        # [n, chunk, H, dv]
    o = o.reshape((n * chunk,) + o.shape[2:])
    return o[:s], state


def _split(qkv, ab, a_log, dt_bias, *, heads, dk, dv, neg_eigval,
           gate_channels=False, lower_bound=0.0):
    """The rule's operands of one sequence from the convolved projections
    qkv [s, H*(2dk+dv)] (float32) and the gates' projections ab: the
    decay's input a (one a head, or with `gate_channels` one a key channel)
    then beta's input b [s, H]. `lower_bound` < 0 bounds the log-decay:
    bound * sigmoid(A (a + dt_bias)); 0 is -A softplus(a + dt_bias)."""
    s = qkv.shape[0]
    q, k, v = jnp.split(qkv, [heads * dk, 2 * heads * dk], axis=-1)
    q = _l2norm(q.reshape(s, heads, dk)) * (1.0 / math.sqrt(dk))
    k = _l2norm(k.reshape(s, heads, dk))
    v = v.reshape(s, heads, dv)
    a, b = jnp.split(ab.astype(F32), [ab.shape[-1] - heads], axis=-1)
    beta = jax.nn.sigmoid(b) * (2.0 if neg_eigval else 1.0)
    rate = jnp.exp(a_log.astype(F32))
    a = a + dt_bias.astype(F32)
    if gate_channels:
        a, rate = a.reshape(s, heads, dk), rate[:, None]
    g = lower_bound * jax.nn.sigmoid(rate * a) if lower_bound < 0 \
        else -rate * jax.nn.softplus(a)
    return q, k, v, g, beta


def _uncached_impl(qkv, ab, conv_w, a_log, dt_bias, *, heads, dk, dv,
                   **gates):
    """Whole sequences [B, s, ...] from a zero state: o [B, s, H*dv]."""
    def one(qkv, ab):
        window = jnp.zeros((conv_w.shape[0] - 1, qkv.shape[-1]), qkv.dtype)
        y, _ = causal_conv_silu(qkv, window, conv_w)
        o, _ = delta_rule_recurrent(
            *_split(y, ab, a_log, dt_bias, heads=heads, dk=dk, dv=dv,
                    **gates),
            jnp.zeros((heads, dv, dk), F32))
        return o.reshape(o.shape[0], heads * dv).astype(qkv.dtype)

    return jax.vmap(one)(qkv, ab)


def _cached_impl(qkv, ab, conv_w, a_log, dt_bias, window, state, valid_len,
                 *, heads, dk, dv, **gates):
    """A chunk [B, s, ...] from the carried (window [B, K-1, C], state
    [B, H, dv, dk]); positions >= valid_len change neither. Returns (o
    [B, s, H*dv], the new window, the new state). One position takes the
    step form, more the chunked form."""
    s = qkv.shape[1]
    live = jnp.arange(s) < valid_len

    def one(qkv, ab, window, state):
        y, full = causal_conv_silu(qkv, window, conv_w)
        q, k, v, g, beta = _split(y, ab, a_log, dt_bias, heads=heads, dk=dk,
                                  dv=dv, **gates)
        g = jnp.where(live.reshape((s,) + (1,) * (g.ndim - 1)), g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
        st = state.astype(F32)
        if s == 1:
            o, st = delta_rule_step(q[0], k[0], v[0], g[0], beta[0], st)
            o = o[None]
        else:
            o, st = delta_rule_chunked(q, k, v, g, beta, st)
        new_window = jax.lax.dynamic_slice_in_dim(
            full, valid_len, window.shape[0], axis=0)
        return (o.reshape(s, heads * dv).astype(qkv.dtype),
                new_window.astype(window.dtype), st.astype(state.dtype))

    return jax.vmap(one)(qkv, ab, window, state)


class GatedDeltaNet(nn.Layer):
    """The mixer of a linear-attention layer. `forward(x)` runs whole
    sequences from a zero state; `forward(x, cache=(window, state),
    valid_len=n)` runs a chunk from a carried state and returns
    `(y, (window, state))`."""

    def __init__(self, cfg):
        super().__init__()
        h = cfg.hidden_size
        self.heads = nh = cfg.linear_num_heads
        self.dk, self.dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        self.neg_eigval = bool(cfg.linear_allow_neg_eigval)
        kc = cfg.linear_conv_kernel_dim
        std = cfg.initializer_range
        channels = nh * (2 * self.dk + self.dv)

        def normal(s):
            return nn.ParamAttr(initializer=nn.initializer.Normal(0.0, s))

        # q, k and v fused along the output axis, as the attention's
        self.qkv_proj = nn.Linear(h, channels, weight_attr=normal(std),
                                  bias_attr=False)
        self.conv_weight = self.create_parameter(
            [kc, channels],
            default_initializer=nn.initializer.Normal(0.0, 1.0 / kc))
        # the gates: the decay's input a (one a head, or one a key channel),
        # then beta's input b (one a head)
        self.gate_channels = bool(cfg.linear_gate_channels)
        self.lower_bound = float(cfg.linear_gate_lower_bound)
        self.sigmoid_gate = cfg.linear_output_gate == "sigmoid"
        na = nh * self.dk if self.gate_channels else nh
        self.ab_proj = nn.Linear(h, na + nh, weight_attr=normal(std),
                                 bias_attr=False)
        # A log-uniform on [1, 16) a head; dt_bias puts the decay of a zero
        # input a step log-uniform on [1e-3, 1e-1) under the rate 1 (the
        # state-space family's initialisation: the inverse softplus of the
        # step, or under a bounded gate the logit of step / |bound|), here
        # at the quantiles of those ranges (no generator at construction)
        quant = (jnp.arange(nh, dtype=F32) + 0.5) / nh
        self.A_log = self.create_parameter(
            [nh], default_initializer=nn.initializer.Assign(
                jnp.log(1.0 + 15.0 * quant)))
        quant = (jnp.arange(na, dtype=F32) + 0.5) / na
        dt = jnp.exp(math.log(1e-3) + quant * (math.log(1e-1)
                                               - math.log(1e-3)))
        if self.lower_bound < 0:
            share = dt / -self.lower_bound
            bias = jnp.log(share) - jnp.log1p(-share)
        else:
            bias = dt + jnp.log(-jnp.expm1(-dt))
        self.dt_bias = self.create_parameter(
            [na], default_initializer=nn.initializer.Assign(bias))
        self.g_proj = nn.Linear(h, nh * self.dv, weight_attr=normal(std),
                                bias_attr=False)
        self.o_norm = nn.RMSNorm(self.dv, epsilon=cfg.layer_norm_epsilon)
        self.out_proj = nn.Linear(
            nh * self.dv, h, bias_attr=False,
            weight_attr=normal(std / math.sqrt(2 * cfg.num_layers)))

    def forward(self, x, cache=None, valid_len=None):
        b, s = x.shape[0], x.shape[1]
        statics = {"heads": self.heads, "dk": self.dk, "dv": self.dv,
                   "neg_eigval": self.neg_eigval}
        if self.gate_channels or self.lower_bound < 0:
            statics.update(gate_channels=self.gate_channels,
                           lower_bound=self.lower_bound)
        weights = [self.conv_weight, self.A_log, self.dt_bias]
        qkv, ab = self.qkv_proj(x), self.ab_proj(x)
        new_cache = None
        if cache is None:
            o = apply("gated_delta_rule", _uncached_impl,
                      [qkv, ab, *weights], statics)
        else:
            window, state = cache
            if valid_len is None:
                valid_len = ops.full([], s, dtype="int32")
            o, new_window, new_state = apply(
                "gated_delta_rule_cached", _cached_impl,
                [qkv, ab, *weights, window, state, valid_len], statics)
            new_cache = (new_window, new_state)
        o = self.o_norm(ops.reshape(o, [b, s, self.heads, self.dv]))
        gate = ops.reshape(self.g_proj(x), [b, s, self.heads, self.dv])
        gate = F.sigmoid(gate) if self.sigmoid_gate else F.silu(gate)
        y = self.out_proj(ops.reshape(o * gate,
                                      [b, s, self.heads * self.dv]))
        return y if cache is None else (y, new_cache)
