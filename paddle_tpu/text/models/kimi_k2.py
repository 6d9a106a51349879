"""Kimi-K2 / DeepSeek-V3 style decoder (`model_type: kimi_k2`): pre-norm
blocks of multi-head latent attention (MLA) and a SwiGLU feed-forward
that is dense in the leading layers and a sparse expert layer with a
shared expert after them; RMSNorm, YaRN-scaled RoPE on a slice of every
head, an untied output head. The serving model of
`inference/serving.ServeLoop`; `paddle_tpu/text/models/reference/
kimi_k2.py` is the same mathematics in plain float32 `jax.numpy`.

Block:  h = x + MLA(RMSNorm(x));  y = h + FFN(RMSNorm(h)).

MLA:    c_q = RMSNorm(x W_qa);  q = c_q W_qb, per head [q_nope | q_rope];
        x W_kva = [c | r]:  c_kv = RMSNorm(c),  k_r = RoPE(r), ONE for
        all heads;  q_r = RoPE(q_rope);  c_kv W_kvb = per head
        [k_nope | v].  scores = (q_nope·k_nope + q_r·k_r) * scale,
        causal, softmax in float32; context over v; W_o.
        What a token caches is `[c_kv | k_r]`, kv_lora_rank +
        qk_rope_head_dim values a layer (`paged_cache_spec`), against
        heads * (192 + 128) for decompressed keys and values.

Two computation paths, the same mathematics:
- a chunk of s > 1 tokens (a prefill) decompresses k and v for the chunk
  and attends as multi-head attention, the queries in blocks so that the
  scores of a 2048-token prompt never exist at once. It attends WITHIN
  the chunk: a prefill starts an empty slot, which is how ServeLoop
  prefills (a chunk appended to a non-empty cache is not supported);
- one token a slot (a decode step) absorbs W_kvb's key half into the
  query and its value half into the output, so all heads attend over the
  one cached vector a token: q~_h = q_nope,h W_kvb,h^K (512 wide),
  scores q~_h·c_kv + q_r·k_r, context over c_kv, then W_kvb,h^V.

Expert layers are `nn.RoutedExperts`, told which experts they hold.
Inference only: the forward passes are array code under no tape.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ... import nn
from ...nn import initializer as I
from ...nn.layer.experts import _swiglu

__all__ = ["KimiK2", "KimiK2Config", "MOE_STATS", "yarn_inv_freq",
           "yarn_mscale"]

# what the expert layers count for `ServeLoop.stats()`: tokens routed,
# (token, expert) pairs that fell on a held expert, held experts that got
# at least one pair and the most pairs on one expert, the last two summed
# over layer-steps; decode beats and prefills apart,
# `moe_decode_layer_steps` to divide the decode sums by
MOE_STATS = tuple(f"moe_{kind}_{what}" for kind in ("decode", "prefill")
                  for what in ("tokens", "pairs_held", "experts_touched",
                               "peak_pairs")) + ("moe_decode_layer_steps",)


def moe_counters(kind, pairs_held, n_tokens):
    """`MOE_STATS`' increments from one settled serve program's pairs a
    held expert [expert layers, held]."""
    import numpy as np
    pairs = np.asarray(pairs_held)
    out = {f"moe_{kind}_tokens": int(n_tokens),
           f"moe_{kind}_pairs_held": int(pairs.sum()),
           f"moe_{kind}_experts_touched": int((pairs > 0).sum()),
           f"moe_{kind}_peak_pairs":
               int(pairs.max(axis=1).sum()) if pairs.size else 0}
    if kind == "decode":
        out["moe_decode_layer_steps"] = int(pairs.shape[0])
    return out


@dataclass
class KimiK2Config:
    vocab_size: int = 163840
    hidden_size: int = 7168
    num_layers: int = 61
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 18432       # the leading dense layers' FFN
    moe_intermediate_size: int = 2048    # one expert's width
    first_k_dense_replace: int = 1       # leading dense layers
    num_experts: int = 384               # the router's width
    experts_held: tuple = None           # (first, count); None = all
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.827
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_scaling: dict = None            # the source's yarn dict, or None
    max_seq_len: int = 262144
    dtype: str = "float32"               # parameters are BORN in it
    init_std: float = 0.02

    @staticmethod
    def tiny(**kw):
        cfg = dict(
            vocab_size=256, hidden_size=64, num_layers=3, num_heads=4,
            q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
            moe_intermediate_size=32, num_experts=16,
            num_experts_per_tok=4, max_seq_len=256,
            rope_scaling={"type": "yarn", "factor": 4.0,
                          "original_max_position_embeddings": 32,
                          "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                          "mscale_all_dim": 1})
        cfg.update(kw)
        return KimiK2Config(**cfg)


def yarn_mscale(factor, mscale=1.0):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, theta, scaling):
    """The `dim // 2` rotary frequencies. Without scaling theta^(-2i/dim);
    with YaRN the blend of those (extrapolation) and the same divided by
    `factor` (interpolation) along the linear ramp between the correction
    dimensions of beta_fast and beta_slow. -> (inv_freq [dim/2] f32, the
    factor cos and sin are scaled by)."""
    freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if not scaling:
        return freq, 1.0
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(scaling["beta_slow"]))),
               dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    inv_freq = freq / factor * ramp + freq * (1.0 - ramp)
    attention_factor = yarn_mscale(factor, scaling.get("mscale", 1)) \
        / yarn_mscale(factor, scaling.get("mscale_all_dim", 0))
    return inv_freq, attention_factor


def _rms(x, weight, eps, scale=1.0):
    """RMSNorm in float32; `scale` multiplies the normed value before it
    is rounded to x's dtype (1.0: nothing is multiplied)."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    y = y * weight.astype(jnp.float32)
    return (y if scale == 1.0 else y * scale).astype(x.dtype)


def _rope(x, cos, sin):
    """Rotate the pairs (i, i + d/2) of the last axis (the half-split
    pairing; the published checkpoints pair (2i, 2i+1), a fixed
    permutation of the projections' columns). cos, sin broadcast to x."""
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    rot = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos + rot * sin).astype(x.dtype)


def _cos_sin(cfg, pos):
    """cos and sin [..., qk_rope_head_dim] of the positions `pos`, for
    `_rope`'s half-split pairing, under the configuration's scaling."""
    inv_freq, factor = yarn_inv_freq(cfg.qk_rope_head_dim,
                                     float(cfg.rope_theta),
                                     getattr(cfg, "rope_scaling", None))
    ang = pos.astype(jnp.float32)[..., None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


# jitted under a name of its own, so that a device trace can tell the
# latent attention from the rest of a serve program
@functools.partial(jax.jit, static_argnames=("scale", "q_block"))
def _mla_chunk_attention(q_nope, q_r, k_nope, k_r, v, *, scale,
                         q_block=512):
    """Causal attention within a chunk, decompressed: q_nope/k_nope
    [b, s, h, dn], q_r [b, s, h, dr], k_r [b, s, dr] (one for all heads),
    v [b, s, h, dv] -> [b, s, h, dv]. Queries go `q_block` at a time, so
    the float32 scores are [b, h, q_block, s] and never [b, h, s, s]."""
    b, s, h, _ = q_nope.shape
    qb = min(q_block, s)
    if s % qb:
        raise ValueError(f"chunk of {s} tokens is no multiple of {qb}")
    col = jnp.arange(s, dtype=jnp.int32)

    def one_block(i):
        start = i * qb
        qn = jax.lax.dynamic_slice_in_dim(q_nope, start, qb, axis=1)
        qr = jax.lax.dynamic_slice_in_dim(q_r, start, qb, axis=1)
        scores = (jnp.einsum("bqhd,bkhd->bhqk", qn, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhd,bkd->bhqk", qr, k_r,
                               preferred_element_type=jnp.float32)) * scale
        row = start + jnp.arange(qb, dtype=jnp.int32)
        scores = jnp.where(col[None, :] <= row[:, None], scores, -1e9)
        p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                          preferred_element_type=jnp.float32
                          ).astype(v.dtype)

    if qb == s:
        return one_block(jnp.int32(0))
    out = jax.lax.map(one_block, jnp.arange(s // qb, dtype=jnp.int32))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, v.shape[-1])


class _Weights(nn.Layer):
    """A layer of matrices born in the configuration's dtype."""

    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        self._normal = I.Normal(0.0, cfg.init_std)

    def matrix(self, *shape):
        return self.create_parameter(list(shape),
                                     default_initializer=self._normal)

    def ones(self, n):
        return self.create_parameter([n],
                                     default_initializer=I.Constant(1.0))


class LatentAttention(_Weights):
    """Multi-head latent attention of any configuration that names
    KimiK2Config's attention fields (text/models/longcat_flash.py shares
    it). `q_scale` multiplies the queries and `kv_scale` the normed
    latent c_kv, which is cached scaled (LongCat-Flash's
    `mla_scale_q_lora` / `mla_scale_kv_lora`: (hidden / rank)^1/2); at
    1.0, Kimi-K2's, nothing is multiplied."""

    def __init__(self, cfg, q_scale=1.0, kv_scale=1.0):
        super().__init__(cfg)
        H, h = cfg.hidden_size, cfg.num_heads
        self.heads, self.eps = h, cfg.rms_norm_eps
        self.q_scale, self.kv_scale = float(q_scale), float(kv_scale)
        self.dn, self.dr, self.dv = (cfg.qk_nope_head_dim,
                                     cfg.qk_rope_head_dim, cfg.v_head_dim)
        self.rank = cfg.kv_lora_rank
        self.q_a = self.matrix(H, cfg.q_lora_rank)
        self.q_norm = self.ones(cfg.q_lora_rank)
        self.q_b = self.matrix(cfg.q_lora_rank, h * (self.dn + self.dr))
        self.kv_a = self.matrix(H, self.rank + self.dr)
        self.kv_norm = self.ones(self.rank)
        self.kv_b = self.matrix(self.rank, h * (self.dn + self.dv))
        self.o = self.matrix(h * self.dv, H)
        scaling = getattr(cfg, "rope_scaling", None) or {}
        m = yarn_mscale(float(scaling.get("factor", 1.0)),
                        scaling.get("mscale_all_dim", 0)) if scaling else 1.0
        self.scale = (self.dn + self.dr) ** -0.5 * m * m

    def _project(self, x, cos, sin):
        """x [b, s, H] -> q_nope [b, s, h, dn], q_r [b, s, h, dr] rotated,
        latent [b, s, rank + dr] = [c_kv | k_r], what the layer caches."""
        b, s, _ = x.shape
        c_q = _rms(x @ self.q_a._value, self.q_norm._value, self.eps)
        q = (c_q @ self.q_b._value).reshape(b, s, self.heads,
                                            self.dn + self.dr)
        if self.q_scale != 1.0:
            q = (q.astype(jnp.float32) * self.q_scale).astype(q.dtype)
        q_r = _rope(q[..., self.dn:], cos[:, :, None], sin[:, :, None])
        kva = x @ self.kv_a._value
        c_kv = _rms(kva[..., :self.rank], self.kv_norm._value, self.eps,
                    self.kv_scale)
        k_r = _rope(kva[..., self.rank:], cos, sin)
        return q[..., :self.dn], q_r, jnp.concatenate([c_kv, k_r], axis=-1)

    def _kv_b(self):
        """W_kvb as [rank, h, dn + dv]: per head its key and value half."""
        return self.kv_b._value.reshape(self.rank, self.heads,
                                        self.dn + self.dv)

    def _chunk(self, q_nope, q_r, latent):
        b, s, _ = latent.shape
        kv = jnp.einsum("bsc,chd->bshd", latent[..., :self.rank],
                        self._kv_b())
        out = _mla_chunk_attention(
            q_nope, q_r, kv[..., :self.dn], latent[..., self.rank:],
            kv[..., self.dn:], scale=self.scale)
        return out.reshape(b, s, self.heads * self.dv) @ self.o._value

    def _absorbed(self, q_nope, q_r, cache):
        from ...nn.kv_pool import latent_paged_attention
        b, s = q_nope.shape[:2]
        w = self._kv_b()
        q_abs = jnp.einsum("bshd,chd->bhsc", q_nope, w[..., :self.dn])
        q = jnp.concatenate([q_abs, jnp.swapaxes(q_r, 1, 2)], axis=-1)
        ctx = latent_paged_attention(q, cache.kv, cache.block_tables,
                                     cache.lengths, self.scale, self.rank)
        out = jnp.einsum("bhsc,chd->bshd", ctx, w[..., self.dn:])
        return out.reshape(b, s, self.heads * self.dv) @ self.o._value

    def forward(self, x, cos, sin, cache=None):
        """Arrays in, arrays out. Without a cache: causal attention over
        x. With a `PagedLatentCache`: the chunk's latents are written,
        then s > 1 attends within the chunk (decompressed) and s == 1
        over the slot's cache (absorbed). -> (out, new cache or None)."""
        from ...nn.kv_pool import PagedLatentCache, write_kv
        with jax.named_scope("attn"):
            q_nope, q_r, latent = self._project(x, cos, sin)
            if cache is None:
                return self._chunk(q_nope, q_r, latent), None
            lens = jnp.asarray(cache.lengths, jnp.int32)
            latent = latent.astype(cache.kv.dtype)  # attend to what is cached
            cache = PagedLatentCache(
                write_kv(cache.kv, cache.block_tables, lens,
                         latent[:, :, None, :]), cache.block_tables, lens)
            out = self._chunk(q_nope, q_r, latent) if x.shape[1] > 1 \
                else self._absorbed(q_nope, q_r, cache)
            return out, cache._replace(
                lengths=lens + jnp.int32(x.shape[1]))


class DenseFFN(_Weights):
    def __init__(self, cfg):
        super().__init__(cfg)
        H, W = cfg.hidden_size, cfg.intermediate_size
        self.gate, self.up = self.matrix(H, W), self.matrix(H, W)
        self.down = self.matrix(W, H)

    def forward(self, x):
        with jax.named_scope("ffn"):
            return _swiglu(x, self.gate._value, self.up._value,
                           self.down._value).astype(x.dtype)


class KimiK2Block(_Weights):
    def __init__(self, cfg: KimiK2Config, index):
        super().__init__(cfg)
        self.eps = cfg.rms_norm_eps
        self.attn_norm = self.ones(cfg.hidden_size)
        self.attn = LatentAttention(cfg)
        self.ffn_norm = self.ones(cfg.hidden_size)
        self.sparse = index >= cfg.first_k_dense_replace
        self.ffn = nn.RoutedExperts(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, held=cfg.experts_held,
            routed_scaling_factor=cfg.routed_scaling_factor,
            shared_width=cfg.n_shared_experts * cfg.moe_intermediate_size,
            dtype=cfg.dtype, init_std=cfg.init_std) if self.sparse \
            else DenseFFN(cfg)

    def forward(self, x, cos, sin, cache=None, valid=None):
        """-> (y, new cache, pairs per held expert [count] i32, or None
        from a dense layer)."""
        with jax.named_scope("attn"):
            a, cache = self.attn(_rms(x, self.attn_norm._value, self.eps),
                                 cos, sin, cache)
            h = x + a
        with jax.named_scope("ffn"):   # `routed` names its own parts
            f = _rms(h, self.ffn_norm._value, self.eps)
            if not self.sparse:
                return h + self.ffn(f), cache, None
            b, s, H = f.shape
            y, counts, _ = self.ffn.routed(
                f.reshape(b * s, H),
                None if valid is None else valid.reshape(b * s))
            return h + y.reshape(b, s, H), cache, counts


class _LatentDecoder(_Weights):
    """What the latent-attention decoders share (this file's and
    text/models/longcat_flash.py's): embedding, a stack of blocks, the
    final norm, an untied head, and `ServeLoop`'s protocol over what a
    subclass writes beside `paged_cache_spec`: `_block(i)`, layer i of
    the stack, and `_blocks`: (ids, pos, caches in spec order or None for
    a pass without a cache, valid) -> (x, new caches, what the expert
    layers counted: a tuple of arrays)."""

    def __init__(self, cfg):
        super().__init__(cfg)
        self.config = cfg
        self.embed = self.matrix(cfg.vocab_size, cfg.hidden_size)
        self.blocks = nn.LayerList(
            [self._block(i) for i in range(cfg.num_layers)])
        self.norm = self.ones(cfg.hidden_size)
        self.head = self.matrix(cfg.hidden_size, cfg.vocab_size)

    def _logits(self, h):
        with jax.named_scope("head"):
            h = _rms(h, self.norm._value, self.config.rms_norm_eps)
            return jnp.dot(h, self.head._value,
                           preferred_element_type=jnp.float32)

    def forward(self, input_ids):
        """Logits [b, s, vocab] (float32) of a whole sequence, no cache."""
        from ...core import tape
        from ...core.tensor import Tensor
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        with tape.no_grad():
            pos = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
            x, *_ = self._blocks(ids.astype(jnp.int32), pos, None, None)
            return Tensor(self._logits(x), _internal=True)

    def _forward_paged(self, input_ids, caches, last_index=None):
        """One paged prefill/decode pass, `GPT._forward_paged`'s contract
        over `PagedLatentCache`s, plus what the expert layers counted:
        -> (logits [b, V] float32, new caches, then `_blocks`' counts:
        first the pairs per held expert [expert layers, held] i32). Rows
        that no request owns (a slot whose table starts at the trash
        block, a prompt's padding past `last_index`) are cached into the
        trash block like GPT's and are routed to no expert."""
        from ...core.tensor import Tensor
        from ...nn.kv_pool import TRASH_BLOCK
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        b, s = ids.shape
        lens = jnp.asarray(caches[0].lengths, jnp.int32)
        step = jnp.arange(s, dtype=jnp.int32)[None]
        valid = jnp.broadcast_to(
            (caches[0].block_tables[:, :1] != TRASH_BLOCK), (b, s))
        if last_index is not None:
            last = jnp.asarray(last_index, jnp.int32).reshape(-1)
            valid = valid & (step <= last[:, None])
        x, new_caches, counted = self._blocks(
            ids.astype(jnp.int32), lens[:, None] + step, caches, valid)
        h = x[:, -1] if last_index is None else jnp.take_along_axis(
            x, last[:, None, None], axis=1)[:, 0]
        return (self._logits(h), new_caches, *counted)


class KimiK2(_LatentDecoder):
    SERVE_STATS = MOE_STATS

    def __init__(self, config: KimiK2Config = None):
        super().__init__(config or KimiK2Config())

    def _block(self, i):
        return KimiK2Block(self.config, i)

    def paged_cache_spec(self):
        """One `CacheSpec` a layer: a `PagedLatentCache` over one arena,
        kv_lora_rank + qk_rope_head_dim wide."""
        from ...nn.kv_pool import CacheSpec, PagedLatentCache
        cfg = self.config
        latent = (1, cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        return [CacheSpec(PagedLatentCache, (latent,))] * cfg.num_layers

    def serve_counters(self, kind, counted, n_tokens):
        """{`ServeLoop.stats()` name: increment} for one settled serve
        program (`kind` "decode" or "prefill") that ran `n_tokens` live
        tokens: `counted` is what `_forward_paged` returned past its
        caches, the pairs each held expert got [expert layers, held]."""
        return moe_counters(kind, counted[0], n_tokens)

    def _blocks(self, ids, pos, caches, valid):
        with jax.named_scope("embed"):
            x = jnp.take(self.embed._value, ids, axis=0)
            cos, sin = _cos_sin(self.config, pos)
        new_caches, counts = [], []
        for i, (blk, c) in enumerate(zip(
                self.blocks, caches or [None] * len(self.blocks))):
            with jax.named_scope(f"layer{i}"):
                x, c, n = blk(x, cos, sin, c, valid)
            new_caches.append(c)
            if n is not None:
                counts.append(n)
        counts = jnp.stack(counts) if counts \
            else jnp.zeros((0, 0), jnp.int32)
        return x, new_caches, (counts,)
