"""Flagship decoder-only transformer LM (GPT/ERNIE/LLaMA-class).

Reference analogs: PaddleNLP GPT/LLaMA model zoo driven by the reference's
nn stack (python/paddle/nn/layer/transformer.py provides the generic
blocks; the fused path uses phi fusion kernels, e.g.
paddle/phi/kernels/fusion/gpu/flash_attn_kernel.cu and fused rope).

TPU-native design:
- One dense MXU-friendly stack: big [hidden, 3*hidden] fused QKV matmuls,
  bf16-ready, static shapes, no data-dependent control flow — the whole
  forward traces to a single XLA program.
- Parameter names follow a stable `layers.<i>.<block>.<w>` scheme so the
  distributed engine (paddle_tpu.distributed) can apply Megatron-style
  tensor-parallel sharding rules by name pattern (column-shard qkv/mlp-in,
  row-shard proj/mlp-out, vocab-shard embedding).
- Rotary or learned positions; pre-LN; GELU or SwiGLU MLP — covers the
  GPT-3-1.3B and LLaMA-2 configs of BASELINE.md (configs 4, 5).
- Layers of more than one kind (`GPTConfig.layer_pattern`): full attention
  beside the gated delta rule of `linear_attention.py`, whose cache entry
  is a recurrent state and not rows, and the latent attention of
  `latent_attention.py`, whose cache entry is one compressed row a token;
  the norm before a sublayer or after it (`norm_after`); no positions at
  all (`learned_positions=False`); leading layers whose feed-forward is
  dense before the expert layers begin (`first_k_dense`).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np
import jax.numpy as jnp

from .. import nn
from .. import ops
from ..core.dispatch import apply
from ..nn import functional as F


FULL, LINEAR, LATENT = ("full_attention", "linear_attention",
                        "latent_attention")
LAYER_KINDS = (FULL, LINEAR, LATENT)


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: int = 0            # 0 → = num_heads (MHA); >0 → GQA
    intermediate_size: int = 0       # 0 → 4*hidden (gelu) or 8/3*hidden (swiglu)
    max_position_embeddings: int = 1024
    rope: bool = False               # rotary (LLaMA) vs learned positions (GPT)
    rope_theta: float = 10000.0
    swiglu: bool = False             # LLaMA MLP
    rms_norm: bool = False           # LLaMA norm
    tie_word_embeddings: bool = True
    dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    dtype: str = "float32"
    head_dim: int = 0                # 0 → hidden_size // num_heads
    qk_norm: bool = False            # RMSNorm over each head of q and of k
    #                                  before the rotation (Qwen3-class)
    num_experts: int = 0             # >0 → the MLP is a sparse-expert layer
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0   # width of one expert
    norm_topk_prob: bool = True      # chosen experts' weights sum to 1
    block_attention: int = 0         # B > 1 → causal over blocks of B
    #                                  positions, full inside one
    # -- layers of more than one kind (models/linear_attention.py) --------
    layer_pattern: tuple = ()        # the kinds of one period, repeated over
    #                                  the depth: "full_attention" |
    #                                  "linear_attention"; () → all full
    norm_after: bool = False         # x + norm(sublayer(x)): the norm after
    #                                  the sublayer, not before it
    qk_norm_whole: bool = False      # RMSNorm over the whole projected q and
    #                                  the whole projected k (all heads)
    learned_positions: bool = True   # without rope: a position table; False
    #                                  → no positions at all
    linear_num_heads: int = 0        # gated delta-rule heads (keys = values)
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = False   # beta in (0, 2), not (0, 1)
    linear_gate_channels: bool = False  # a log-decay a KEY CHANNEL, not one
    #                                     a head (Kimi Delta Attention)
    linear_gate_lower_bound: float = 0.0    # b < 0: log-decay = b *
    #                                     sigmoid(A (a + dt_bias)) in (b, 0);
    #                                     0: -A softplus(a + dt_bias)
    linear_output_gate: str = "silu"    # | "sigmoid": the output norm's gate
    # -- latent attention (models/latent_attention.py) ---------------------
    kv_lora_rank: int = 0            # the latent a token leaves in the cache
    qk_nope_head_dim: int = 0        # a head's query/key part w/o positions
    qk_rope_head_dim: int = 0        # ... and its rotated part (one key
    #                                  shared by the heads)
    v_head_dim: int = 0
    attn_head_gate: bool = False     # o_h *= sigmoid(w_h . x), one a head
    # -- the expert layers beyond softmax top-k (models/moe.py) ------------
    first_k_dense: int = 0           # leading layers keep the dense MLP
    experts_held: tuple = ()         # (first, count): the experts THIS chip
    #                                  holds of the router's num_experts; ()
    #                                  -> all of them
    moe_score_function: str = "softmax"     # | "sigmoid"
    moe_router_bias: bool = False    # a selection bias an expert: chosen by
    #                                  score + bias, weighed by the score
    moe_n_group: int = 0             # experts in n groups, of which the
    moe_topk_group: int = 0          # ... best k may be chosen from
    routed_scaling_factor: float = 1.0
    moe_shared_expert_intermediate_size: int = 0   # >0: one shared expert

    def __post_init__(self):
        self.layer_pattern = tuple(self.layer_pattern)
        self.experts_held = tuple(self.experts_held)
        unknown = set(self.layer_pattern) - set(LAYER_KINDS)
        if unknown or (self.layer_pattern
                       and self.num_layers % len(self.layer_pattern)):
            raise ValueError(
                f"layer_pattern {self.layer_pattern}: kinds are "
                f"{LAYER_KINDS} and a period divides num_layers "
                f"({self.num_layers})")
        if LINEAR in self.layer_pattern and not (
                self.linear_num_heads and self.linear_key_head_dim
                and self.linear_value_head_dim):
            raise ValueError(
                "a linear_attention layer needs linear_num_heads, "
                "linear_key_head_dim and linear_value_head_dim")
        if LATENT in self.layer_pattern and not (
                self.kv_lora_rank and self.qk_nope_head_dim
                and self.qk_rope_head_dim and self.v_head_dim
                and self.rope):
            raise ValueError(
                "a latent_attention layer needs kv_lora_rank, "
                "qk_nope_head_dim, qk_rope_head_dim, v_head_dim and rope")
        if self.experts_held and not (
                len(self.experts_held) == 2 and 0 <= self.experts_held[0]
                and self.experts_held[1] >= 1
                and sum(self.experts_held) <= self.num_experts):
            raise ValueError(
                f"experts_held {self.experts_held} is (first, count) inside "
                f"the router's num_experts ({self.num_experts})")
        if self.moe_n_group > 1 and (
                self.num_experts % self.moe_n_group
                or not 1 <= self.moe_topk_group <= self.moe_n_group
                or self.num_experts // self.moe_n_group < 2):
            raise ValueError(
                f"moe_n_group {self.moe_n_group} has to divide num_experts "
                f"({self.num_experts}) into groups of two experts or more, "
                f"and moe_topk_group ({self.moe_topk_group}) lie in "
                f"1..moe_n_group")
        if self.num_kv_heads == 0:
            self.num_kv_heads = self.num_heads
        if self.head_dim == 0:
            self.head_dim = self.hidden_size // self.num_heads
        if self.intermediate_size == 0:
            if self.swiglu:
                # LLaMA sizing: 2/3 * 4h rounded to multiple of 128 (lane width)
                self.intermediate_size = int(
                    128 * math.ceil(8 * self.hidden_size / 3 / 128))
            else:
                self.intermediate_size = 4 * self.hidden_size

    def layer_kinds(self):
        """The kind of every layer, in order."""
        period = self.layer_pattern or (FULL,)
        return tuple(period[i % len(period)]
                     for i in range(self.num_layers))


# Named configs matching BASELINE.md workloads.
CONFIGS = {
    # test-size
    "gpt_tiny": dict(vocab_size=256, hidden_size=64, num_layers=2,
                     num_heads=4, max_position_embeddings=128),
    # ERNIE-3.0-base / BERT-base class decoder (north-star tokens/sec shape)
    "gpt_base": dict(vocab_size=50304, hidden_size=768, num_layers=12,
                     num_heads=12, max_position_embeddings=1024),
    # BASELINE config 4: GPT-3 1.3B
    "gpt3_1p3b": dict(vocab_size=50304, hidden_size=2048, num_layers=24,
                      num_heads=32, max_position_embeddings=2048),
    # BASELINE config 5: LLaMA-2-7B
    "llama2_7b": dict(vocab_size=32000, hidden_size=4096, num_layers=32,
                      num_heads=32, intermediate_size=11008,
                      max_position_embeddings=4096, rope=True, swiglu=True,
                      rms_norm=True, tie_word_embeddings=False),
}


class CacheQuantError(ValueError):
    """Unknown KV-cache quantization mode. Subclasses ValueError so
    pre-existing `except ValueError` callers keep working; raised (never
    silently ignored) for any unrecognized `quant=` argument or
    `cache_quant` attribute."""


#: spellings that mean "no quantization — plain parameter-dtype cache"
#: ("bf16" is the documented name of the unquantized layout, so an
#: explicit quant="bf16" OVERRIDES a model-level cache_quant attribute)
_NO_QUANT = (None, "", "none", "bf16")


def _normal_attr(std):
    return nn.ParamAttr(initializer=nn.initializer.Normal(0.0, std))


def _make_norm(cfg):
    if cfg.rms_norm:
        return nn.RMSNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
    return nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)


class GPTAttention(nn.Layer):
    """Fused-QKV causal self-attention (flash-attention path)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        h, hd = cfg.hidden_size, cfg.head_dim
        q_out = cfg.num_heads * hd
        kv_out = cfg.num_kv_heads * hd
        std = cfg.initializer_range
        bias = not cfg.rms_norm  # LLaMA-style stacks drop biases
        self.qkv_proj = nn.Linear(h, q_out + 2 * kv_out,
                                  weight_attr=_normal_attr(std),
                                  bias_attr=None if bias else False)
        self.out_proj = nn.Linear(q_out, h,
                                  weight_attr=_normal_attr(
                                      std / math.sqrt(2 * cfg.num_layers)),
                                  bias_attr=None if bias else False)
        if cfg.qk_norm:
            # one learned weight of head_dim, shared by a projection's heads
            self.q_norm = nn.RMSNorm(hd, epsilon=cfg.layer_norm_epsilon)
            self.k_norm = nn.RMSNorm(hd, epsilon=cfg.layer_norm_epsilon)
        elif cfg.qk_norm_whole:
            # one weight a channel of the projection, the mean over all heads
            self.q_norm = nn.RMSNorm(q_out, epsilon=cfg.layer_norm_epsilon)
            self.k_norm = nn.RMSNorm(kv_out, epsilon=cfg.layer_norm_epsilon)
        self.dropout = nn.Dropout(cfg.dropout)

    def forward(self, x, position_ids=None, cache=None):
        cfg = self.cfg
        b = x.shape[0]
        s = x.shape[1]
        hd = cfg.head_dim
        qkv = self.qkv_proj(x)
        q_sz = cfg.num_heads * hd
        kv_sz = cfg.num_kv_heads * hd
        q, k, v = ops.split(qkv, [q_sz, kv_sz, kv_sz], axis=-1)
        if cfg.qk_norm_whole and not cfg.qk_norm:
            q, k = self.q_norm(q), self.k_norm(k)
        q = ops.reshape(q, [b, s, cfg.num_heads, hd])
        k = ops.reshape(k, [b, s, cfg.num_kv_heads, hd])
        v = ops.reshape(v, [b, s, cfg.num_kv_heads, hd])
        if cfg.qk_norm:
            q, k = self.q_norm(q), self.k_norm(k)
        if cfg.rope:
            q, k = F.apply_rotary_pos_emb(q, k, position_ids,
                                          theta=cfg.rope_theta)
        if cache is not None:
            # KV-cached decode (reference: the cached inference path of the
            # LLM families): write this chunk's K/V at `pos`, attend over
            # the whole static-length cache with a position mask.
            # A 5-tuple cache entry is the int8-quantized layout
            # (kq, k_scale, vq, v_scale, pos) — see init_cache(quant=).
            if len(cache) == 5:
                kq_c, ks_c, vq_c, vs_c, pos = cache
                out, nkq, nks, nvq, nvs = apply(
                    "cached_attn_int8", _cached_attn_int8_impl,
                    [q, k, v, kq_c, ks_c, vq_c, vs_c, pos],
                    {"num_heads": cfg.num_heads,
                     "block": cfg.block_attention})
                out = ops.reshape(out, [b, s, q_sz])
                return self.out_proj(out), (nkq, nks, nvq, nvs)
            k_cache, v_cache, pos = cache
            out, new_k, new_v = apply(
                "cached_attn", _cached_attn_impl,
                [q, k, v, k_cache, v_cache, pos],
                {"num_heads": cfg.num_heads, "block": cfg.block_attention})
            out = ops.reshape(out, [b, s, q_sz])
            return self.out_proj(out), (new_k, new_v)
        if cfg.block_attention > 1:
            # no flash kernel takes the block mask: the plain masked core
            # of the cached path, over this sequence's own keys
            out = apply("block_attn", _block_attn_impl, [q, k, v],
                        {"num_heads": cfg.num_heads,
                         "block": cfg.block_attention})
            out = ops.reshape(out, [b, s, q_sz])
            return self.dropout(self.out_proj(out))
        if cfg.num_kv_heads != cfg.num_heads:
            rep = cfg.num_heads // cfg.num_kv_heads
            k = ops.repeat_interleave(k, rep, axis=2)
            v = ops.repeat_interleave(v, rep, axis=2)
        out, _ = F.flash_attention(q, k, v, dropout=cfg.dropout, causal=True,
                                   training=self.training)
        out = ops.reshape(out, [b, s, q_sz])
        return self.dropout(self.out_proj(out))


def _cached_attn_core(q, kk, vv, pos, num_heads, k_scale=None,
                      v_scale=None, block=0):
    """Shared cached-attention core: GQA repeat, causal mask over global
    positions, softmax, PV. Optional per-(position, head) scales fold into
    score/prob space (the int8-cache path). `block` B > 1 makes the mask
    causal over blocks of B positions and full inside one: query i sees
    key j when floor(j / B) <= floor(i / B)."""
    import jax

    b, s, hkv, t = q.shape[0], q.shape[1], kk.shape[2], kk.shape[1]
    rep = num_heads // hkv
    q_pos = jnp.arange(s)
    if rep > 1:
        # grouped-query: the `rep` query heads of a KV head attend as
        # `rep` more query rows of that head, so K and V (and their
        # scales) are read as stored and never copied a query head
        q = q.reshape(b, s, hkv, rep, -1).transpose(0, 1, 3, 2, 4) \
            .reshape(b, s * rep, hkv, -1)
        q_pos = jnp.repeat(q_pos, rep)
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32)
    if k_scale is not None:   # [B,T,H] -> [B,H,1,T]
        scores = scores * jnp.transpose(k_scale, (0, 2, 1))[:, :, None, :]
    scores = scores * scale
    q_idx = pos + q_pos[:, None]
    k_idx = jnp.arange(t)[None, :]
    if block > 1:
        q_idx, k_idx = q_idx // block, k_idx // block
    mask = k_idx <= q_idx  # causal over global positions (or blocks)
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    if v_scale is not None:   # fold into [B,H,q,T] probs before PV
        probs = probs * jnp.transpose(v_scale, (0, 2, 1))[:, :, None, :]
    probs = probs.astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
    if rep > 1:
        out = out.reshape(b, s, rep, hkv, -1).transpose(0, 1, 3, 2, 4) \
            .reshape(b, s, num_heads, -1)
    return out


def _write_rows(cache, new, pos):
    """Write this chunk's [B,s,Hkv,D] values at `pos` into a cache of flat
    rows [B,T,Hkv*D] (the stored layout, see `init_cache`)."""
    import jax

    rows = new.reshape(new.shape[:2] + (-1,)).astype(cache.dtype)
    return jax.lax.dynamic_update_slice_in_dim(cache, rows, pos, axis=1)


def _heads(cache, like):
    """View flat cache rows [B,T,Hkv*D] as [B,T,Hkv,D], the head geometry
    taken from this chunk's own K/V `like` [B,s,Hkv,D]."""
    return cache.reshape(cache.shape[:2] + like.shape[2:])


def _cached_attn_impl(q, k_new, v_new, k_cache, v_cache, pos, *, num_heads,
                      block=0):
    """q [B,s,H,D]; k/v_new [B,s,Hkv,D]; caches [B,T,Hkv*D] flat rows; pos
    scalar global offset of this chunk. Returns (out, new_k_cache,
    new_v_cache), the caches flat as they came."""
    k_cache = _write_rows(k_cache, k_new, pos)
    v_cache = _write_rows(v_cache, v_new, pos)
    out = _cached_attn_core(q, _heads(k_cache, k_new), _heads(v_cache, v_new),
                            pos, num_heads, block=block)
    return out, k_cache, v_cache


def _block_attn_impl(q, k, v, *, num_heads, block):
    """Uncached attention under the block mask: q [B,S,H,D], k/v
    [B,S,Hkv,D], every query at its own position."""
    return _cached_attn_core(q, k, v, 0, num_heads, block=block)


def _quant_kv(x):
    """Per-(batch, position, head) symmetric int8: scale = amax/127 over
    the head dim (decode accuracy workhorse; reference analog: the LLM
    cachekv int8 path of the PaddleNLP inference stack)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale[..., 0]


def _cached_attn_int8_impl(q, k_new, v_new, kq_c, ks_c, vq_c, vs_c, pos, *,
                           num_heads, block=0):
    """int8 KV cache decode: caches store int8 values + f32 per-position
    scales ([B,T,Hkv*D] int8 flat rows + [B,T,Hkv] f32 — half the
    decode-loop HBM read of a bf16 cache). New K/V are quantized at write;
    the dequant multiply fuses into the attention matmul's operand read."""
    knq, kns = _quant_kv(k_new)
    vnq, vns = _quant_kv(v_new)
    kq_c = _write_rows(kq_c, knq, pos)
    ks_c = _write_rows(ks_c, kns, pos)
    vq_c = _write_rows(vq_c, vnq, pos)
    vs_c = _write_rows(vs_c, vns, pos)

    # Scales fold into SCORE space ([B,H,q,T] — tiny at decode q=1) rather
    # than dequantizing the cache: a broadcast-multiply dequant would
    # materialize a full bf16 cache copy every step (measured SLOWER than
    # a bf16 cache, docs/decode_perf.md round-4 addendum).
    out = _cached_attn_core(q, _heads(kq_c, k_new).astype(q.dtype),
                            _heads(vq_c, v_new).astype(q.dtype),
                            pos, num_heads, k_scale=ks_c, v_scale=vs_c,
                            block=block)
    return out, kq_c, ks_c, vq_c, vs_c


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig, width: int = 0):
        super().__init__()
        h, m = cfg.hidden_size, width or cfg.intermediate_size
        std = cfg.initializer_range
        bias = not cfg.rms_norm
        self.swiglu = cfg.swiglu
        if cfg.swiglu:
            # fused gate+up as one column-shardable matmul
            self.gate_up_proj = nn.Linear(h, 2 * m,
                                          weight_attr=_normal_attr(std),
                                          bias_attr=False)
        else:
            self.up_proj = nn.Linear(h, m, weight_attr=_normal_attr(std),
                                     bias_attr=None if bias else False)
        self.down_proj = nn.Linear(m, h,
                                   weight_attr=_normal_attr(
                                       std / math.sqrt(2 * cfg.num_layers)),
                                   bias_attr=None if bias else False)
        self.dropout = nn.Dropout(cfg.dropout)

    def forward(self, x):
        if self.swiglu:
            gu = self.gate_up_proj(x)
            gate, up = ops.chunk(gu, 2, axis=-1)
            x = F.silu(gate) * up
        else:
            x = F.gelu(self.up_proj(x), approximate=True)
        return self.dropout(self.down_proj(x))


class GPTBlock(nn.Layer):
    """One decoder layer of `kind`: full attention or latent attention
    (`attn`) or the gated delta rule (`lin`, whose cache is a state and not
    rows), the norms before the sublayers or, with `norm_after`, after
    them; the feed-forward dense, or with `experts` the sparse experts."""

    def __init__(self, cfg: GPTConfig, kind: str = FULL, experts=None):
        super().__init__()
        self.kind = kind
        self.norm_after = cfg.norm_after
        self.ln_1 = _make_norm(cfg)
        if kind == LINEAR:
            from .linear_attention import GatedDeltaNet

            self.lin = GatedDeltaNet(cfg)
        elif kind == LATENT:
            from .latent_attention import LatentAttention

            self.attn = LatentAttention(cfg)
        else:
            self.attn = GPTAttention(cfg)
        self.ln_2 = _make_norm(cfg)
        self.experts = bool(cfg.num_experts) if experts is None else experts
        if self.experts:
            from .moe import SparseExperts

            self.mlp = SparseExperts(cfg)
        else:
            self.mlp = GPTMLP(cfg)

    def _mix(self, x, position_ids, cache, pos, valid_len):
        """The layer's mixer on x; with a cache entry `(out, new entry)`.
        Attention takes the chunk's offset beside its rows, the delta rule
        how many of the chunk's positions are real."""
        if self.kind == LINEAR:
            return self.lin(x, cache=cache, valid_len=valid_len)
        if cache is None:
            return self.attn(x, position_ids)
        return self.attn(x, position_ids, (*cache, pos))

    def forward(self, x, position_ids=None, cache=None, pos=None,
                valid_len=None):
        """`cache`: the layer's entry as `init_cache` lays it out, with the
        chunk's offset `pos` (and for the delta rule `valid_len`) beside
        it; returns `(x, new entry)` then."""
        mixed = self._mix(x if self.norm_after else self.ln_1(x),
                          position_ids, cache, pos, valid_len)
        new_cache = None
        if cache is not None:
            mixed, new_cache = mixed
        # the expert layer is told which positions are a bucket's padding,
        # for its counts alone
        told = (valid_len,) if self.experts and valid_len is not None else ()
        if self.norm_after:
            x = x + self.ln_1(mixed)
            x = x + self.ln_2(self.mlp(x, *told))
        else:
            x = x + mixed
            x = x + self.mlp(self.ln_2(x), *told)
        return x if cache is None else (x, new_cache)


class GPTModel(nn.Layer):
    """Decoder-only LM trunk: embeddings + N blocks + final norm."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        std = cfg.initializer_range
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                weight_attr=_normal_attr(std))
        self.learned_positions = not cfg.rope and cfg.learned_positions
        if self.learned_positions:
            self.wpe = nn.Embedding(cfg.max_position_embeddings,
                                    cfg.hidden_size,
                                    weight_attr=_normal_attr(std))
        self.drop = nn.Dropout(cfg.dropout)
        self.layers = nn.LayerList([
            GPTBlock(cfg, kind, bool(cfg.num_experts)
                     and i >= cfg.first_k_dense)
            for i, kind in enumerate(cfg.layer_kinds())])
        self.ln_f = _make_norm(cfg)

    def forward(self, input_ids, position_ids=None):
        s = input_ids.shape[1]
        if position_ids is None:
            position_ids = ops.expand(
                ops.unsqueeze(ops.arange(s, dtype="int32"), 0),
                [input_ids.shape[0], s])
        x = self.wte(input_ids)
        if self.learned_positions:
            x = x + self.wpe(position_ids)
        x = self.drop(x)
        for blk in self.layers:
            x = blk(x, position_ids)
        return self.ln_f(x)

    def forward_step(self, input_ids, caches, pos, valid_len=None):
        """Cached decode: input_ids [B, s] at global positions
        [pos, pos+s); caches = one entry a layer as `init_cache` lays
        them out: (k, v) flat rows [B, T, Hkv*D] for an attention layer,
        (window, state) for a delta-rule layer. `valid_len` (a scalar,
        default s) says how many of the s positions are real: the rest
        are a bucket's padding, which rows tolerate (they are overwritten
        before they are attended) and a recurrent state does not. Returns
        (hidden, new_caches)."""
        b, s = input_ids.shape
        position_ids = ops.unsqueeze(
            ops.arange(s, dtype="int32"), 0) + pos
        position_ids = ops.expand(position_ids, [b, s])
        x = self.wte(input_ids)
        if self.learned_positions:
            x = x + self.wpe(position_ids)
        new_caches = []
        for blk, entry in zip(self.layers, caches):
            # entry: (k, v) bf16 cache, (kq, ks, vq, vs) int8 cache, or a
            # delta-rule layer's (window, state)
            x, nc = blk(x, position_ids, cache=tuple(entry), pos=pos,
                        valid_len=valid_len)
            new_caches.append(nc)
        return self.ln_f(x), new_caches


class GPTForCausalLM(nn.Layer):
    """LM head on the trunk; `forward` returns logits, `loss` the next-token
    cross entropy (labels shifted internally)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.transformer = GPTModel(cfg)
        if cfg.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     weight_attr=_normal_attr(
                                         cfg.initializer_range),
                                     bias_attr=False)

    def _project(self, hidden):
        """Vocab projection (tied embedding transpose or separate head)."""
        if self.lm_head is None:
            return ops.matmul(hidden, self.transformer.wte.weight,
                              transpose_y=True)
        return self.lm_head(hidden)

    def forward(self, input_ids, position_ids=None):
        return self._project(self.transformer(input_ids, position_ids))

    def decode_signature(self):
        """What shapes a compiled decode program beyond the parameters'
        shapes, for the engine's compile-cache key: "" for a configuration
        that uses none of it (keys as they were), else the values. A
        grouped-query model names its attention's form too: its query
        heads attend as rows of their KV head (`_cached_attn_core`), a
        program of its own since the K/V repeat went, which an executable
        cached before must not serve."""
        cfg = self.cfg
        gqa = "gqa-rows" if cfg.num_kv_heads != cfg.num_heads else ""
        hybrid = ""
        if cfg.layer_pattern or cfg.norm_after or cfg.qk_norm_whole \
                or not cfg.learned_positions:
            # the layers' kinds, the residual form, the q/k norm's reach,
            # the positions and the delta rule's geometry: none of them is
            # a parameter's shape alone
            hybrid = (f"kinds{','.join(k[0] for k in cfg.layer_kinds())}:"
                      f"after{int(cfg.norm_after)}:"
                      f"qkwhole{int(cfg.qk_norm_whole)}:"
                      f"pos{int(cfg.learned_positions)}:"
                      f"lin{cfg.linear_num_heads}x{cfg.linear_key_head_dim}"
                      f"x{cfg.linear_value_head_dim}"
                      f"c{cfg.linear_conv_kernel_dim}"
                      f"neg{int(cfg.linear_allow_neg_eigval)}:"
                      f"eps{cfg.layer_norm_epsilon}")
            if LINEAR in cfg.layer_pattern:
                # the delta rule's decay a key channel and its chunked
                # form by tiles: a program of its own since then, which an
                # executable cached before must not serve
                hybrid += ":rule-tiles16"
            if cfg.linear_gate_channels or cfg.linear_gate_lower_bound \
                    or cfg.linear_output_gate != "silu":
                hybrid += (f":gate{int(cfg.linear_gate_channels)}"
                           f"b{cfg.linear_gate_lower_bound}"
                           f"{cfg.linear_output_gate}")
            if LATENT in cfg.layer_pattern:
                # "linear" and "latent" share their first letter above
                hybrid += (f":mla@{','.join(str(i) for i, k in enumerate(cfg.layer_kinds()) if k == LATENT)}"
                           f":{cfg.kv_lora_rank}+{cfg.qk_rope_head_dim}"
                           f"n{cfg.qk_nope_head_dim}v{cfg.v_head_dim}"
                           f"g{int(cfg.attn_head_gate)}")
        if not (cfg.block_attention > 1 or cfg.num_experts or cfg.qk_norm):
            return ":".join(x for x in (gqa, hybrid) if x)
        experts = ""
        if cfg.first_k_dense or cfg.experts_held or cfg.moe_router_bias \
                or cfg.moe_score_function != "softmax" or cfg.moe_n_group \
                or cfg.routed_scaling_factor != 1.0 \
                or cfg.moe_shared_expert_intermediate_size:
            # the router's rule and the share held are no parameter's shape
            experts = (f":dense{cfg.first_k_dense}:held{cfg.experts_held}:"
                       f"{cfg.moe_score_function}"
                       f"b{int(cfg.moe_router_bias)}"
                       f"g{cfg.moe_n_group}/{cfg.moe_topk_group}"
                       f"x{cfg.routed_scaling_factor}"
                       f"s{cfg.moe_shared_expert_intermediate_size}")
        return (f"block{cfg.block_attention}:top{cfg.num_experts_per_tok}:"
                f"norm{int(cfg.norm_topk_prob)}:theta{cfg.rope_theta}:"
                f"eps{cfg.layer_norm_epsilon}" + (":" + gqa if gqa else "")
                + (":" + hybrid if hybrid else "") + experts)

    def recurrent_layers(self):
        """How many layers keep a recurrent state and no rows (the decode
        engine gives each resident sequence one state slot for them, and
        refuses what needs a cache made of rows)."""
        return self.cfg.layer_kinds().count(LINEAR)

    def expert_layers(self):
        """How many layers' feed-forward is the sparse experts."""
        cfg = self.cfg
        return max(0, cfg.num_layers - cfg.first_k_dense) \
            if cfg.num_experts else 0

    def latent_layers(self):
        """How many layers cache one compressed latent row a token."""
        return self.cfg.layer_kinds().count(LATENT)

    def _latent_row(self):
        cfg = self.cfg
        return cfg.kv_lora_rank + cfg.qk_rope_head_dim

    def _resolve_cache_quant(self, quant):
        """Resolve the KV-cache quantization mode with a documented
        precedence: an explicit `quant=` ARGUMENT always wins over the
        model-level `cache_quant` attribute; only `quant=None` falls back
        to the attribute (so `generate()` and the decode engine pick up a
        model-wide default without API changes, while a caller can still
        force the bf16 layout with `quant="bf16"` on a model whose
        attribute says int8). Returns None (unquantized) or "int8";
        anything else raises `CacheQuantError` — an unknown spelling must
        never silently fall back to the bf16 layout."""
        if quant is None:
            quant = getattr(self, "cache_quant", None)
        key = quant.lower() if isinstance(quant, str) else quant
        if key in _NO_QUANT:
            return None
        if key == "int8":
            return "int8"
        raise CacheQuantError(
            f"unsupported cache quant {quant!r} (supported: 'int8', or "
            f"'bf16'/None for the unquantized layout)")

    def init_cache(self, batch_size, max_length, dtype=None, quant=None):
        """Zeroed per-layer KV caches [B, T, Hkv*D] for cached decode:
        one flat row a position, heads x head_dim side by side, so the
        minor dimension is a multiple of the chip's 128 lanes at any
        head size and the array keeps its plain row-major layout on the
        device (a [.., Hkv, 64] minor pair does not: PERF.md section 5).
        The cached attention views a row as [Hkv, D] where it needs
        heads. Cache dtype follows the parameters (bf16 params -> bf16
        cache: the KV read is the decode bandwidth bill).

        quant="int8" stores int8 values plus f32 per-position scales —
        half the per-token cache read (docs/decode_perf.md names the KV
        read as the biggest weight-independent term in the decode
        floor). Precedence: the `quant=` argument wins; `quant=None`
        falls back to the model's `cache_quant` attribute (so
        `generate()` picks it up without API changes) and `quant="bf16"`
        forces the unquantized layout even then. Unknown modes raise
        `CacheQuantError` (a ValueError). For the paged layout used by
        the continuous-batching decode engine, see `init_block_pool`."""
        cfg = self.cfg
        quant = self._resolve_cache_quant(quant)
        if dtype is None:
            dtype = self.transformer.wte.weight.dtype
        shape = (batch_size, int(max_length),
                 cfg.num_kv_heads * cfg.head_dim)
        from ..core.tensor import Tensor

        def entry(kind):
            if kind == LINEAR:
                # a delta-rule layer: the convolution's window and the
                # state, whatever the length (and never quantized)
                return tuple(Tensor(jnp.zeros((batch_size,) + suffix, dt))
                             for suffix, dt in self._state_spec(dtype))
            if kind == LATENT:
                # a latent-attention layer: one row a token, [c ; rot(k_r)]
                self._no_latent_quant(quant)
                return (Tensor(jnp.zeros(shape[:2] + (self._latent_row(),),
                                         dtype)),)
            if quant == "int8":
                sshape = shape[:2] + (cfg.num_kv_heads,)
                return (Tensor(jnp.zeros(shape, jnp.int8)),
                        Tensor(jnp.zeros(sshape, jnp.float32)),
                        Tensor(jnp.zeros(shape, jnp.int8)),
                        Tensor(jnp.zeros(sshape, jnp.float32)))
            return (Tensor(jnp.zeros(shape, dtype)),
                    Tensor(jnp.zeros(shape, dtype)))

        return [entry(kind) for kind in cfg.layer_kinds()]

    @staticmethod
    def _no_latent_quant(quant):
        if quant is not None:
            raise CacheQuantError(
                f"cache quant {quant!r} is for rows of keys and values: a "
                f"latent-attention layer's row has no int8 layout")

    def _state_spec(self, dtype):
        """(suffix shape, dtype) of a delta-rule layer's cache tensors,
        one sequence's: the convolution's last K - 1 inputs in the
        parameters' dtype, the state in float32 (an accumulator over the
        whole sequence)."""
        cfg = self.cfg
        nh, dk, dv = (cfg.linear_num_heads, cfg.linear_key_head_dim,
                      cfg.linear_value_head_dim)
        return (((cfg.linear_conv_kernel_dim - 1, nh * (2 * dk + dv)),
                 dtype), ((nh, dv, dk), jnp.float32))

    def init_block_pool(self, num_blocks, block_size, dtype=None,
                        quant=None, name=None, num_slots=0):
        """Paged twin of `init_cache`: a `BlockKVCache` whose per-layer
        pool tensors use exactly this model's cache-entry order and
        dtypes — `(k, v)` blocks of the parameter dtype, or int8
        `(kq, ks, vq, vs)` quads ([N, bs, Hkv*D] int8 values +
        [N, bs, Hkv] f32 scales). A block holds the same flat rows as
        `init_cache`, so a pool tensor is [N, bs, Hkv*D] and the chip
        stores it as it is: with [N, bs, Hkv, D] at D = 64 the runtime
        puts N on the lanes, and every step and every prompt chunk
        relayouts the whole pool on its way in and again on its way out
        (PERF.md section 5). Each entry spec also names its head count,
        which is what `BlockKVCache.shard_` splits over. Quant precedence
        and error semantics are shared with `init_cache`
        (`_resolve_cache_quant`). The
        continuous-batching `DecodeEngine` calls this so cache geometry
        is owned by the model, not the scheduler; with speculative
        decoding on, the engine calls it on BOTH the target and the
        draft model (`name` tags whose pool is whose — each model owns
        its own layer count / head geometry). A model with delta-rule
        layers gives those layers `(window, state)` entries of
        `num_slots` slots (one a resident sequence) in the same pool:
        `BlockKVCache.slot_layers` says which layers they are."""
        from ..inference.decode.block_pool import BlockKVCache

        cfg = self.cfg
        quant = self._resolve_cache_quant(quant)
        if dtype is None:
            dtype = self.transformer.wte.weight.dtype
        hkv = cfg.num_kv_heads
        rows = (hkv * cfg.head_dim,)
        if quant == "int8":
            layer = ((rows, jnp.int8, hkv), ((hkv,), jnp.float32, hkv),
                     (rows, jnp.int8, hkv), ((hkv,), jnp.float32, hkv))
        else:
            layer = ((rows, dtype, hkv), (rows, dtype, hkv))
        kinds = cfg.layer_kinds()
        # a latent-attention layer's entry is one tensor of rows, a block
        # of them like any other's; one "head" as far as sharding goes
        by_kind = {FULL: layer}
        if LATENT in kinds:
            self._no_latent_quant(quant)
            by_kind[LATENT] = (((self._latent_row(),), dtype, 1),)
        if LINEAR not in kinds:
            return BlockKVCache(num_blocks, block_size,
                                [by_kind[k] for k in kinds], quant=quant,
                                name=name)
        # a delta-rule layer's entry is one slot a sequence, not blocks of
        # rows: the pool holds `num_slots` of them beside the blocks
        by_kind[LINEAR] = tuple(self._state_spec(dtype))
        return BlockKVCache(num_blocks, block_size,
                            [by_kind[k] for k in kinds],
                            quant=quant, name=name,
                            slot_layers=[k == LINEAR for k in kinds],
                            num_slots=num_slots)

    def decode_step(self, input_ids, caches, pos, valid_len=None):
        """Cached decode step: logits for input_ids at global offset pos
        plus updated caches (the generation fast path). `valid_len`: how
        many of the positions are real (`forward_step`)."""
        hidden, new_caches = self.transformer.forward_step(
            input_ids, caches, pos, valid_len)
        return self._project(hidden), new_caches

    def loss(self, input_ids, labels=None, position_ids=None):
        """Causal LM loss. labels defaults to input_ids (shift happens here).

        The shift slices the *hidden* states before the vocab projection:
        slicing logits afterwards would force a copy of the full [B,S,V]
        logits (1.6 GB at the flagship shape) that the projection of the
        sliced hidden never materializes."""
        if labels is None:
            labels = input_ids
        hidden = self.transformer(input_ids, position_ids)[:, :-1, :]
        shift_logits = self._project(hidden)
        shift_labels = labels[:, 1:]
        return F.cross_entropy(
            ops.reshape(shift_logits, [-1, self.cfg.vocab_size]),
            ops.reshape(shift_labels, [-1]),
            reduction="mean")


def gpt(name="gpt_base", **overrides):
    d = dict(CONFIGS[name])
    d.update(overrides)
    return GPTForCausalLM(GPTConfig(**d))

