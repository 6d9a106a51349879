"""LongCat-Flash style decoder (`LongcatFlashForCausalLM`): a layer is a
shortcut-connected expert block (ScMoE) — TWO latent attentions and two
dense SwiGLU feed-forwards in sequence, and ONE sparse expert layer that
is fed from the first sublayer and added after the second:

    for i in (0, 1):
        h = x + MLA_i(RMSNorm(x))
        f = RMSNorm(h)
        if i == 0:  m = Experts(f)         # fed from the FIRST sublayer
        x = h + SwiGLU_i(f)
    x = x + m                              # added after the SECOND

so that nothing of sublayer 1 depends on `m`: on an expert-parallel
deployment that is where the exchange between chips hides; on one chip it
leaves XLA the order. The serving model of `inference/serving.ServeLoop`;
`paddle_tpu/text/models/reference/longcat_flash.py` is the same
mathematics in plain float32 `jax.numpy`.

MLA is `kimi_k2.LatentAttention` (one class in the tree, both paths: a
chunk decompressed, a decode step absorbed over the paged latent cache)
with the family's two factors: the queries times (hidden / q_lora_rank)^1/2
and the normed latent c_kv times (hidden / kv_lora_rank)^1/2, which is
cached scaled (`mla_scale_q_lora`, `mla_scale_kv_lora`). Plain RoPE, no
scaling. A layer caches TWO latents a token: `paged_cache_spec()` yields
two `PagedLatentCache` entries a layer, in layer order. Embedding, norm,
head and `ServeLoop`'s protocol are `decoder.PagedDecoder`'s, and a
sublayer up to its dense FFN is `kimi_k2._sublayer`: a bucketed prefill
works tile by tile over the tiles that hold a token, the expert layer
once over the bucket.

Experts are `nn.RoutedExperts`: a softmax router over the routed experts
AND `zero_experts` zero-compute experts (the identity on the layer's
input), selection on score + bias, weight = routed_scaling_factor x score,
not renormalised over the chosen; told which routed experts it holds.
Inference only: the forward passes are array code under no tape.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax

from ... import nn
from .decoder import MOE_STATS, DenseFFN, _Weights, moe_counters
from .kimi_k2 import LatentAttention, _LatentDecoder, _sublayer

__all__ = ["LongCatFlash", "LongCatFlashConfig", "SCMOE_STATS"]

# beside `decoder.MOE_STATS`, under the same names: how many of the
# routed tokens' pairs fell on routed experts (held here or not), how
# many on zero-compute experts, and the sum over (token, layer) of a
# token's routed pairs squared (the spread of compute a token)
SCMOE_STATS = MOE_STATS + tuple(
    f"moe_{kind}_pairs_{what}" for kind in ("decode", "prefill")
    for what in ("real", "zero", "real_sq"))


@dataclass
class LongCatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    num_layers: int = 28                 # each: two sublayers + experts
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    intermediate_size: int = 12288       # the source's ffn_hidden_size
    moe_intermediate_size: int = 2048    # its expert_ffn_hidden_size
    num_experts: int = 512               # routed experts the router scores
    zero_experts: int = 256              # zero-compute (identity) experts
    experts_held: tuple = None           # (first, count); None = all
    num_experts_per_tok: int = 12        # moe_topk
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    max_seq_len: int = 131072
    dtype: str = "float32"               # parameters are BORN in it
    init_std: float = 0.02

    @staticmethod
    def tiny(**kw):
        cfg = dict(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
            moe_intermediate_size=32, num_experts=16, zero_experts=8,
            num_experts_per_tok=6, max_seq_len=256)
        cfg.update(kw)
        return LongCatFlashConfig(**cfg)


class _Sublayer(_Weights):
    """One of a layer's two: norm, latent attention, norm, dense FFN."""

    def __init__(self, cfg):
        super().__init__(cfg)
        H = cfg.hidden_size
        self.attn_norm = self.ones(H)
        self.attn = LatentAttention(
            cfg,
            q_scale=(H / cfg.q_lora_rank) ** 0.5
            if cfg.mla_scale_q_lora else 1.0,
            kv_scale=(H / cfg.kv_lora_rank) ** 0.5
            if cfg.mla_scale_kv_lora else 1.0)
        self.ffn_norm = self.ones(H)
        self.ffn = DenseFFN(cfg)


class LongCatFlashBlock(_Weights):
    def __init__(self, cfg: LongCatFlashConfig):
        super().__init__(cfg)
        self.eps = cfg.rms_norm_eps
        self.sub = nn.LayerList([_Sublayer(cfg), _Sublayer(cfg)])
        self.experts = nn.RoutedExperts(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, held=cfg.experts_held,
            routed_scaling_factor=cfg.routed_scaling_factor,
            score_func="softmax", norm_topk_prob=False,
            zero_experts=cfg.zero_experts, dtype=cfg.dtype,
            init_std=cfg.init_std)

    def forward(self, x, rope, cache0, cache1, rows):
        """-> (y, the two new caches, (pairs per held expert [count] i32,
        [3] i32: routed pairs, zero pairs, routed pairs squared)). `rows`:
        `kimi_k2._sublayer`'s; the expert layer runs once over all the
        rows."""
        b, s, H = x.shape
        new = []
        for i, (sub, cache) in enumerate(zip(self.sub, (cache0, cache1))):
            with jax.named_scope(f"sublayer{i}"):
                x, f, cache = _sublayer(
                    sub.attn, sub.attn_norm, sub.ffn_norm, sub.ffn, self.eps,
                    x, *rope, cache, rows)
            if i == 0:
                with jax.named_scope("experts"):
                    m, counts, pairs = self.experts.routed(
                        f.reshape(b * s, H), None if rows.valid is None
                        else rows.valid.reshape(b * s))
            new.append(cache)
        with jax.named_scope("experts"):
            return x + m.reshape(b, s, H), new, (counts, pairs)


class LongCatFlash(_LatentDecoder):
    SERVE_STATS = SCMOE_STATS
    LAYER_CACHES = 2

    def __init__(self, config: LongCatFlashConfig = None):
        cfg = config or LongCatFlashConfig()
        super().__init__(cfg, lambda i: LongCatFlashBlock(cfg))  # all alike

    def paged_cache_spec(self):
        """TWO `CacheSpec`s a layer, in layer order: each sublayer's
        `PagedLatentCache` over an arena of its own, kv_lora_rank +
        qk_rope_head_dim wide."""
        from ...nn.kv_pool import CacheSpec, PagedLatentCache
        cfg = self.config
        latent = (1, cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        return [CacheSpec(PagedLatentCache, (latent,))] * (2 * cfg.num_layers)

    def serve_counters(self, kind, counted, n_tokens):
        """`counted` is what the blocks counted, stacked: the pairs each
        held expert got [layers, held] and [layers, 3]: routed pairs, zero
        pairs, routed pairs squared."""
        import numpy as np
        out = moe_counters(kind, counted[0], n_tokens)
        real, zero, real_sq = np.asarray(counted[1]).sum(axis=0)
        out.update({f"moe_{kind}_pairs_real": int(real),
                    f"moe_{kind}_pairs_zero": int(zero),
                    f"moe_{kind}_pairs_real_sq": int(real_sq)})
        return out
