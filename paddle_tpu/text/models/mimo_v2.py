"""MiMo-V2-Flash decoder (`model_type: mimo_v2_flash`): pre-norm blocks of
grouped-query attention whose keys are deeper than its values (192 over
128), assembled from `hybrid_layer_pattern` — a full layer, then five
`sliding` layers that see the last `sliding_window` tokens, over and over —
with a head count of their own for each kind (4 | 8 key-value heads), a
rotary theta of their own, and in the sliding layers a learned SINK logit
a query head that joins the softmax's denominator and has no value; a
SwiGLU feed-forward part that is dense where `moe_layer_freq` is 0 and a
sparse expert layer WITHOUT a shared expert everywhere else; RMSNorm, an
untied output head. Served by `inference/serving.ServeLoop` through
`laguna.WindowDecoder` (the decoder the nets with window layers share);
`paddle_tpu/text/models/reference/mimo_v2.py` is the same mathematics in
plain float32 `jax.numpy`.

Block:  h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h)).

Attn, a layer of kind full | sliding with n query heads over hk = 4 | 8
key-value heads:
        [q | k | v] = a W_qkv, q [n, 192], k [hk, 192], v [hk, 128] times
        `attention_value_scale`; rotary on q and k over the first
        int(192 * `partial_rotary_factor`) = 64 dims of a head, entry i
        paired with i + 32, theta `rope_theta` | `swa_rope_theta`; query
        head j reads key-value head j // (n / hk); scores q.k / sqrt(192);
        causal softmax in float32, for a sliding layer over the last
        `sliding_window` keys (itself included) AND the head's sink:
        p_t = exp(s_t) / (exp(sink_j) + sum_t' exp(s_t'));
        y = concat_j(o_j) W_o, o_j 128 wide.
        What a TOKEN caches in a full layer: 4 keys of 192 and 4 values of
        128 (`PagedKVCache`: a K arena and a V arena of different depth).
        What a SLOT caches in a sliding layer, whatever the stream's
        length: the last `sliding_window` keys (8 x 192) and values
        (8 x 128), a ring (`WindowKVCache`) of ONE block where the window
        is the pool's block.

The two computation paths are `laguna.GroupedAttention.attend`'s: a chunk
attends within itself in tiles (`laguna._gqa_chunk_attention`, the sinks
where a row's softmax starts), one token a slot goes through `write_kv` +
`paged_attention` (full) or `window_write` + `window_attention` (sliding):
the grouped-query form of the paged Pallas kernel at both call sites, 16
or 8 query heads the rows of one product, the sliding layers' with sinks.
Expert layers are `nn.RoutedExperts` as it is (sigmoid scores, a selection
bias, the chosen renormalised, factor 1.0, `shared_width=0`), told which
experts they hold. Inference only.

Not built: the source's three multi-token-prediction layers (no key of
the catalog's `config` names them; ROADMAP R-list: a step that yields
more than one token). `attention_chunk_size` is carried and read by
nothing.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax.numpy as jnp

from ... import nn
from ...nn import initializer as I
from .decoder import F32, FULL, DenseFFN
from .laguna import (SLIDING, GroupedAttention, WindowBlock, WindowDecoder,
                     _rotary, window_cache_spec)

__all__ = ["MiMoV2Flash", "MiMoV2Config"]


def _published_pattern():
    """48 layers: a full layer, four sliding, then periods of a full layer
    and five sliding (0 = full, 1 = sliding), the last layer full."""
    return [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0]


@dataclass
class MiMoV2Config:
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384       # the dense layers' FFN
    moe_intermediate_size: int = 2048    # one expert's width
    num_hidden_layers: int = 48
    # the per-layer lists are read up to `num_hidden_layers`
    hybrid_layer_pattern: list = field(default_factory=_published_pattern)
    moe_layer_freq: list = field(default_factory=lambda: [0] + [1] * 47)
    num_attention_heads: int = 64        # full layers
    num_key_value_heads: int = 4
    swa_num_attention_heads: int = 64    # sliding layers
    swa_num_key_value_heads: int = 8
    head_dim: int = 192                  # keys and queries, both kinds
    v_head_dim: int = 128                # values
    sliding_window: int = 128
    ring_block: int = 128                # tokens a block of a slot's ring
    rope_theta: float = 5000000.0
    swa_rope_theta: float = 10000.0
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    n_routed_experts: int = 256          # the router's width
    experts_held: tuple = None           # (first, count); None = all
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = None  # the source's null: 1.0
    layernorm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    dtype: str = "float32"               # parameters are BORN in it
    init_std: float = 0.02
    # a sink is born N(mean, std): a trained sink holds a large part of a
    # head's mass (at log 32 about a fifth of a full 128-key window)
    sink_init: tuple = (math.log(32.0), 0.5)
    # the benchmark's control, set by no deployment: keys and values
    # through this dtype on their way into the cache
    kv_round_to: str = None

    def __post_init__(self):
        n = int(self.num_hidden_layers)
        self.hybrid_layer_pattern = [
            int(x) for x in self.hybrid_layer_pattern][:n]
        self.moe_layer_freq = [int(x) for x in self.moe_layer_freq][:n]
        if len(self.hybrid_layer_pattern) != n \
                or len(self.moe_layer_freq) != n:
            raise ValueError(f"{n} layers need {n} entries of "
                             "hybrid_layer_pattern and moe_layer_freq")
        for heads, kv in ((self.num_attention_heads,
                           self.num_key_value_heads),
                          (self.swa_num_attention_heads,
                           self.swa_num_key_value_heads)):
            if heads % kv:
                raise ValueError(f"{heads} query heads over {kv}")

    # what `laguna.WindowDecoder`, `_rotary` and `ServeLoop` read, under
    # their names
    @property
    def num_layers(self):
        return int(self.num_hidden_layers)

    @property
    def max_seq_len(self):               # what `ServeConfig` caps a stream at
        return int(self.max_position_embeddings)

    @property
    def layer_types(self):
        return [SLIDING if x else FULL for x in self.hybrid_layer_pattern]

    @property
    def rms_norm_eps(self):
        return self.layernorm_epsilon

    @property
    def rope_parameters(self):
        """Plain rotary over the first int(head_dim * factor) dims, each
        kind at its own theta (`_rotary` rounds: 64.128 -> 64)."""
        return {kind: {"rope_type": "default", "rope_theta": theta,
                       "partial_rotary_factor": self.partial_rotary_factor}
                for kind, theta in ((FULL, self.rope_theta),
                                    (SLIDING, self.swa_rope_theta))}

    def heads(self, kind):
        """(query heads, key-value heads) of a layer of `kind`."""
        return (self.num_attention_heads, self.num_key_value_heads) \
            if kind == FULL else (self.swa_num_attention_heads,
                                  self.swa_num_key_value_heads)

    def has_sinks(self, kind):
        return bool(self.add_full_attention_sink_bias if kind == FULL
                    else self.add_swa_attention_sink_bias)

    @staticmethod
    def tiny(**kw):
        """The published shape in small: keys of 24 over values of 16,
        4 | 8 key-value heads under 16 query heads (G = 4 | 2), a window
        of one ring block, the first seven layers F | S S S S F S, layer 0
        dense."""
        cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                   moe_intermediate_size=32, num_hidden_layers=7,
                   num_attention_heads=16, num_key_value_heads=4,
                   swa_num_attention_heads=16, swa_num_key_value_heads=8,
                   head_dim=24, v_head_dim=16, sliding_window=8,
                   ring_block=8, rope_theta=10000.0, swa_rope_theta=100.0,
                   partial_rotary_factor=0.334, n_routed_experts=16,
                   num_experts_per_tok=3, max_position_embeddings=256,
                   sink_init=(math.log(4.0), 0.5))
        cfg.update(kw)
        return MiMoV2Config(**cfg)


class SinkGroupedAttention(GroupedAttention):
    """Grouped-query attention with keys deeper than values and, where the
    configuration says so, a sink logit a query head, in the three stages
    a block runs: `project` and `output` row by row, `mix` across the
    rows."""

    def __init__(self, cfg: MiMoV2Config, kind):
        super().__init__(cfg)
        H, d, dv = cfg.hidden_size, cfg.head_dim, cfg.v_head_dim
        self.heads, self.kv = cfg.heads(kind)
        self.head_dim, self.v_head_dim = d, dv
        self.window = cfg.sliding_window if kind == SLIDING else None
        self.rot = _rotary(cfg, kind)[0]
        self.value_scale = float(cfg.attention_value_scale)
        self.round_to = cfg.kv_round_to
        self.qkv = self.matrix(H, (self.heads + self.kv) * d + self.kv * dv)
        self.o = self.matrix(self.heads * dv, H)
        if cfg.has_sinks(kind):     # float32 always, like a selection bias
            self.sinks = self.create_parameter(
                [self.heads], dtype="float32",
                default_initializer=I.Normal(*cfg.sink_init))

    def sink_logits(self):
        if "sinks" not in self._parameters:
            return None
        return self.sinks._value

    def project(self, a, cos, sin):
        """Row by row: the normed stream a [b, t, H] float32 -> q [b, t,
        n, d] and k [b, t, kv, d] rotated, v [b, t, kv, d_v] scaled, all
        in the parameters' dtype (keys and values through `kv_round_to`
        where a control sets it)."""
        return self.heads_of(a.astype(self.qkv._value.dtype), cos, sin,
                             self.v_head_dim, self.value_scale)

    def mix(self, q, k, v, cache, rows):
        """`laguna.GroupedAttention.attend` -> ((out [b, s, n d_v],), new
        cache or None)."""
        out, cache = self.attend(q, k, v, cache, rows)
        return (out,), cache

    def output(self, out):
        """Row by row: the output projection -> y [b, t, H] float32."""
        return jnp.dot(out.astype(self.o._value.dtype), self.o._value,
                       preferred_element_type=F32)


class MiMoV2Block(WindowBlock):
    def __init__(self, cfg: MiMoV2Config, index):
        kind = cfg.layer_types[index]
        sparse = bool(cfg.moe_layer_freq[index])
        # no shared expert (`n_shared_experts` null); sigmoid scores, the
        # selection bias, renormalised weights: the layer's defaults
        super().__init__(
            cfg, kind, SinkGroupedAttention(cfg, kind),
            nn.RoutedExperts(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                held=cfg.experts_held,
                routed_scaling_factor=cfg.routed_scaling_factor or 1.0,
                shared_width=0, dtype=cfg.dtype, init_std=cfg.init_std,
                norm_topk_prob=cfg.norm_topk_prob) if sparse
            else DenseFFN(cfg), sparse)


class MiMoV2Flash(WindowDecoder):
    def __init__(self, config: MiMoV2Config = None):
        super().__init__(config or MiMoV2Config(), MiMoV2Block)

    def paged_cache_spec(self):
        """`laguna.window_cache_spec`: a full layer pages 4 keys of 192
        and 4 values of 128 a token (two arenas of different depth), a
        sliding layer keeps a ring of `sliding_window` tokens a slot, 8
        keys of 192 and 8 values of 128 each: at the published window
        and a pool block of 128, a ring of ONE block."""
        cfg = self.config
        keys = {kind: (cfg.heads(kind)[1], cfg.head_dim)
                for kind in (FULL, SLIDING)}
        values = {kind: (cfg.heads(kind)[1], cfg.v_head_dim)
                  for kind in (FULL, SLIDING)}
        return window_cache_spec(cfg.layer_types, cfg.sliding_window,
                                 cfg.ring_block, keys, values)
