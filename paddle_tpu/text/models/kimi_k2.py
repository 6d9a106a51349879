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
  and attends as multi-head attention, a tile of queries against the
  keys up to its own tile, so that the scores of a 2048-token prompt never
  exist at once and nothing above the diagonal's tiles is computed. It
  attends WITHIN the chunk: a prefill starts an empty slot, which is how
  ServeLoop prefills (a chunk appended to a non-empty cache is not
  supported). A bucket that `decoder.PagedDecoder.prefill_tile` cuts works
  tile by tile over the tiles that hold a token (`_live_rows`: projections,
  decompression, output projection, dense FFN, norms and residuals; the
  queries' tiles) and leaves the rest of the bucket zero; the expert layer
  runs once over the bucket, since its cost is its weights';
- one token a slot (a decode step) absorbs W_kvb's key half into the
  query and its value half into the output, so all heads attend over the
  one cached vector a token: q~_h = q_nope,h W_kvb,h^K (512 wide),
  scores q~_h·c_kv + q_r·k_r, context over c_kv, then W_kvb,h^V.

Expert layers are `nn.RoutedExperts`, told which experts they hold.
Inference only: the forward passes are array code under no tape.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ... import nn
from .decoder import (MOE_STATS, DenseFFN, PagedDecoder, Rows, _live_rows,
                      _rms, _rope, _rotary_tables, _Weights, moe_counters,
                      yarn_inv_freq, yarn_mscale)

__all__ = ["KimiK2", "KimiK2Config"]


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


def _cos_sin(cfg, pos):
    """cos and sin [..., qk_rope_head_dim] of the positions `pos`, for
    `_rope`'s half-split pairing, under the configuration's scaling."""
    return _rotary_tables(pos, *yarn_inv_freq(
        cfg.qk_rope_head_dim, float(cfg.rope_theta),
        getattr(cfg, "rope_scaling", None)))


# jitted under a name of its own, so that a device trace can tell the
# latent attention from the rest of a serve program
@functools.partial(jax.jit, static_argnames=("scale", "q_block"))
def _mla_chunk_attention(q_nope, q_r, k_nope, k_r, v, live=None, *, scale,
                         q_block):
    """Causal attention within a chunk, decompressed: q_nope/k_nope
    [b, s, h, dn], q_r [b, s, h, dr], k_r [b, s, dr] (one for all heads),
    v [b, s, h, dv] -> [b, s, h, dv]. Queries go a tile of `q_block` at a
    time and tile i reads the keys of tiles 0..i, the others being masked
    for every one of its rows: the float32 scores are [b, h, q_block,
    (i + 1) q_block] and never [b, h, s, s]. With `live` (a traced count)
    only the first `live` tiles of queries are computed and the others
    come out zero."""
    s = q_nope.shape[1]
    qb = min(q_block, s)
    if s % qb:
        raise ValueError(f"chunk of {s} tokens is no multiple of {qb}")

    def one_tile(i):
        rows, keys = slice(i * qb, (i + 1) * qb), slice(0, (i + 1) * qb)
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope[:, rows],
                             k_nope[:, keys],
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhd,bkd->bhqk", q_r[:, rows], k_r[:, keys],
                               preferred_element_type=jnp.float32)) * scale
        row = jnp.arange(rows.start, rows.stop, dtype=jnp.int32)
        col = jnp.arange(keys.stop, dtype=jnp.int32)
        scores = jnp.where(col[None, :] <= row[:, None], scores, -1e9)
        p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v[:, keys],
                          preferred_element_type=jnp.float32
                          ).astype(v.dtype)

    tiles = [one_tile(0)]                  # the first tile holds a token
    for i in range(1, s // qb):
        tiles.append(one_tile(i) if live is None else jax.lax.cond(
            i < live, functools.partial(one_tile, i),
            lambda: jnp.zeros_like(tiles[0])))
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


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

    def project(self, x, cos, sin, cache=None):
        """Row by row: `_project`, the latent rounded to what `cache`
        keeps, and for a chunk (more rows than one, or no cache) the keys
        and values it decompresses to, k_nope [b, s, h, dn] and v [b, s,
        h, dv] -> (q_nope, q_r, latent[, k_nope, v])."""
        q_nope, q_r, latent = self._project(x, cos, sin)
        if cache is not None:
            latent = latent.astype(cache.kv.dtype)  # attend to what is cached
            if x.shape[1] == 1:
                return q_nope, q_r, latent
        kv = jnp.einsum("bsc,chd->bshd", latent[..., :self.rank],
                        self._kv_b())
        return q_nope, q_r, latent, kv[..., :self.dn], kv[..., self.dn:]

    def mix(self, q_nope, q_r, latent, k_nope=None, v=None, cache=None,
            rows=Rows()):
        """Across the rows, from `project`'s parts: the latents written
        to a `PagedLatentCache`, then a chunk attends within itself
        (decompressed; `rows.live` tiles of queries of it, None: all) and
        one token over its slot's cache (absorbed) -> (context [b, s,
        h dv], new cache or None)."""
        from ...nn.kv_pool import PagedLatentCache, write_kv
        b, s = q_nope.shape[:2]
        if cache is not None:
            lens = jnp.asarray(cache.lengths, jnp.int32)
            cache = PagedLatentCache(
                write_kv(cache.kv, cache.block_tables, lens,
                         latent[:, :, None, :]), cache.block_tables, lens)
        if k_nope is not None:
            ctx = _mla_chunk_attention(
                q_nope, q_r, k_nope, latent[..., self.rank:], v, rows.live,
                scale=self.scale, q_block=rows.tile)
        else:
            ctx = self._absorbed(q_nope, q_r, cache)
        if cache is not None:
            cache = cache._replace(lengths=lens + jnp.int32(s))
        return ctx.reshape(b, s, self.heads * self.dv), cache

    def _absorbed(self, q_nope, q_r, cache):
        from ...nn.kv_pool import latent_paged_attention
        w = self._kv_b()
        q_abs = jnp.einsum("bshd,chd->bhsc", q_nope, w[..., :self.dn])
        q = jnp.concatenate([q_abs, jnp.swapaxes(q_r, 1, 2)], axis=-1)
        ctx = latent_paged_attention(q, cache.kv, cache.block_tables,
                                     cache.lengths, self.scale, self.rank)
        return jnp.einsum("bhsc,chd->bshd", ctx, w[..., self.dn:])

    def output(self, ctx):
        """Row by row: the output projection."""
        return ctx @ self.o._value

    def forward(self, x, cos, sin, cache=None):
        """Arrays in, arrays out: `project`, `mix` and `output` over all
        the rows at once. Without a cache: causal attention over x. With a
        `PagedLatentCache`: the chunk's latents are written, then s > 1
        attends within the chunk and s == 1 over the slot's cache.
        -> (out, new cache or None)."""
        with jax.named_scope("attn"):
            ctx, cache = self.mix(*self.project(x, cos, sin, cache),
                                  cache=cache)
            return self.output(ctx), cache


def _sublayer(attn, attn_norm, ffn_norm, ffn, eps, x, cos, sin, cache, rows):
    """A latent attention and what follows it row by row: h = x +
    MLA(RMSNorm(x)), f = RMSNorm(h) -> (h + ffn(f), or h where `ffn` is
    None and an expert layer takes f; f; new cache). `rows.live`: the
    tiles of `rows.tile` rows that hold a token (`_live_rows`), None for
    all: the norms, `project` and `output`, the residuals and `ffn` run
    over those, `mix` across the rows."""
    def before(x, cos, sin):
        with jax.named_scope("attn"):
            return attn.project(_rms(x, attn_norm._value, eps), cos, sin,
                                cache)

    def after(x, ctx):
        with jax.named_scope("attn"):
            h = x + attn.output(ctx)
        with jax.named_scope("ffn"):
            f = _rms(h, ffn_norm._value, eps)
            return (h if ffn is None else h + ffn(f)), f

    parts = _live_rows(before, rows.live, rows.tile, x, cos, sin)
    with jax.named_scope("attn"):
        ctx, cache = attn.mix(*parts, cache=cache, rows=rows)
    y, f = _live_rows(after, rows.live, rows.tile, x, ctx)
    return y, f, cache


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

    def forward(self, x, rope, cache, rows):
        """-> (y, (new cache,), (pairs per held expert [count] i32, or
        None from a dense layer,)). `rows`: `_sublayer`'s; the expert
        layer runs once over all the rows, its cost being its weights'."""
        y, f, cache = _sublayer(
            self.attn, self.attn_norm, self.ffn_norm,
            None if self.sparse else self.ffn, self.eps, x, *rope, cache,
            rows)
        if not self.sparse:
            return y, (cache,), (None,)
        with jax.named_scope("ffn"):   # `routed` names its own parts
            b, s, H = f.shape
            m, counts, _ = self.ffn.routed(
                f.reshape(b * s, H),
                None if rows.valid is None else rows.valid.reshape(b * s))
            return y + m.reshape(b, s, H), (cache,), (counts,)


class _LatentDecoder(PagedDecoder):
    """What the latent-attention decoders share past `PagedDecoder`
    (this file's and text/models/longcat_flash.py's): the residual stream
    stays in the parameters' dtype, and one rotary table serves every
    layer."""

    def _embed(self, ids, pos):
        return (jnp.take(self.embed._value, ids, axis=0),
                _cos_sin(self.config, pos))


class KimiK2(_LatentDecoder):
    SERVE_STATS = MOE_STATS

    def __init__(self, config: KimiK2Config = None):
        cfg = config or KimiK2Config()
        super().__init__(cfg, lambda i: KimiK2Block(cfg, i))

    def paged_cache_spec(self):
        """One `CacheSpec` a layer: a `PagedLatentCache` over one arena,
        kv_lora_rank + qk_rope_head_dim wide."""
        from ...nn.kv_pool import CacheSpec, PagedLatentCache
        cfg = self.config
        latent = (1, cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        return [CacheSpec(PagedLatentCache, (latent,))] * cfg.num_layers

    def serve_counters(self, kind, counted, n_tokens):
        """`counted`: the pairs each held expert got [expert layers,
        held] i32."""
        return moe_counters(kind, counted[0], n_tokens)
