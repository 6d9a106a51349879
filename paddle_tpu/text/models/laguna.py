"""Laguna decoder (`model_type: laguna`): pre-norm blocks of grouped-query
attention with a sigmoid gate a head on its output, assembled from
`layer_types` — one `full_attention` layer, then three `sliding_attention`
layers that see the last `sliding_window` tokens, over and over — and a
SwiGLU feed-forward part that is dense in `mlp_only_layers` and a sparse
expert layer with a shared expert everywhere else; RMSNorm, an untied
output head. Served by `inference/serving.ServeLoop`;
`paddle_tpu/text/models/reference/laguna.py` is the same mathematics in
plain float32 `jax.numpy`.

Block:  h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h)).

Attn, layer l with n_l = `num_attention_heads_per_layer[l]` query heads
over `num_kv_heads` key-value heads of `head_dim`:
        [q | k | v] = a W_qkv,  g = sigmoid(a W_g)  (one gate a query
        head); rotary on q and k over the first r dims of a head, entry
        i paired with i + r/2 — full layers r = head_dim *
        `partial_rotary_factor` under YaRN, sliding layers all of the head
        at their own theta, unscaled; query head j reads key-value head
        j // (n_l / kv); causal softmax in float32, for a sliding layer
        over the last `sliding_window` keys (itself included);
        y = concat_j(g_j o_j) W_o.
        What a TOKEN caches in a full layer: keys and values a key-value
        head (`PagedKVCache`, paged by token). What a SLOT caches in a
        sliding layer, whatever the stream's length: the last
        `sliding_window` keys and values, a ring (`WindowKVCache`).

Two computation paths, the same mathematics:
- a chunk of s > 1 tokens (a prefill) starts an EMPTY slot: the full
  layers write the chunk's keys and values into the slot's blocks, the
  sliding layers leave its last `sliding_window` in the ring, and both
  attend within the chunk, a tile of queries (the group's heads folded
  into the rows) against the key tiles it may see: those up to its own,
  or for a sliding layer those that meet the band. A bucket that
  `decoder.PagedDecoder.prefill_tile` cuts works tile by tile over the
  tiles that hold a token (`_live_rows`: norms, projections, rotary,
  gate, output projection, dense FFN, residuals; the queries' tiles) and
  leaves the rest zero; the expert layer runs once over the bucket;
- one token a slot (a decode step): `write_kv` + `paged_attention` over
  the pool's arenas (full) or `window_write` + `window_attention` over
  the slot's ring (sliding) — the grouped-query form of the paged Pallas
  kernel at both call sites.
Expert layers are `nn.RoutedExperts` (softmax router, the chosen
renormalised and scaled), told which experts they hold.
Inference only: the forward passes are array code under no tape.

What another net with window layers shares (`mimo_v2.py`: keys deeper
than values, head counts by kind, sink logits) is here under its own
name: `_gqa_chunk_attention` (sinks and a value width as arguments),
`GroupedAttention.attend`, `WindowBlock`, `WindowDecoder`,
`window_cache_spec`.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ... import nn
from ...nn.layer.experts import _swiglu
from .decoder import (F32, FULL, MOE_STATS, DenseFFN, PagedDecoder,
                      _live_rows, _rms, _rope, _rotary_tables, _Weights,
                      moe_counters, yarn_inv_freq)

__all__ = ["Laguna", "LagunaConfig", "ATTN_STATS"]

SLIDING = "sliding_attention"

# what the attention layers count for `ServeLoop.stats()`: cached tokens
# the decode steps attended to, summed over slots and layer-steps (a full
# layer reads a slot's whole stream, a sliding layer at most its window),
# and the bytes of every sliding layer's rings (a gauge)
ATTN_STATS = ("attn_full_decode_tokens_read",
              "attn_window_decode_tokens_read", "window_ring_bytes")


def _published_rope():
    return {FULL: {"rope_theta": 500000.0, "rope_type": "yarn",
                   "factor": 128.0,
                   "original_max_position_embeddings": 8192,
                   "beta_slow": 1, "beta_fast": 32,
                   "attention_factor": 1.4852030263919618,
                   "partial_rotary_factor": 0.5},
            SLIDING: {"rope_type": "default", "rope_theta": 10000.0,
                      "partial_rotary_factor": 1}}


@dataclass
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288       # the dense layers' FFN
    moe_intermediate_size: int = 1024    # one expert's width
    shared_expert_intermediate_size: int = 1024
    num_layers: int = 48
    # the per-layer lists are read up to `num_layers`
    layer_types: list = field(
        default_factory=lambda: [FULL, SLIDING, SLIDING, SLIDING] * 12)
    num_attention_heads_per_layer: list = field(
        default_factory=lambda: [48, 72, 72, 72] * 12)
    mlp_only_layers: tuple = (0,)
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    ring_block: int = 128                # tokens a block of a slot's ring
    rope_parameters: dict = field(default_factory=_published_rope)
    num_experts: int = 256               # the router's width
    experts_held: tuple = None           # (first, count); None = all
    num_experts_per_tok: int = 10
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 1048576
    dtype: str = "float32"               # parameters are BORN in it
    init_std: float = 0.02
    # keys and values pass through this dtype on their way into the
    # cache (None: cached as computed); the benchmark's control of a
    # cache one precision down sets it, no deployment does
    kv_round_to: str = None

    def __post_init__(self):
        n = int(self.num_layers)
        self.layer_types = list(self.layer_types)[:n]
        self.num_attention_heads_per_layer = [
            int(h) for h in self.num_attention_heads_per_layer][:n]
        if len(self.layer_types) != n or \
                len(self.num_attention_heads_per_layer) != n:
            raise ValueError(f"{n} layers need {n} layer types and head "
                             "counts")
        for kind, heads in zip(self.layer_types,
                               self.num_attention_heads_per_layer):
            if kind not in (FULL, SLIDING) or heads % self.num_kv_heads:
                raise ValueError(f"layer type {kind!r} with {heads} query "
                                 f"heads over {self.num_kv_heads}")

    @staticmethod
    def tiny(**kw):
        rope = {FULL: {"rope_theta": 10000.0, "rope_type": "yarn",
                       "factor": 4.0,
                       "original_max_position_embeddings": 32,
                       "beta_slow": 1, "beta_fast": 32,
                       "attention_factor": 1.1386294361119891,
                       "partial_rotary_factor": 0.5},
                SLIDING: {"rope_type": "default", "rope_theta": 100.0,
                          "partial_rotary_factor": 1}}
        cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                   moe_intermediate_size=32,
                   shared_expert_intermediate_size=32, num_layers=5,
                   layer_types=[FULL, SLIDING, SLIDING, SLIDING] * 2,
                   num_attention_heads_per_layer=[4, 6, 6, 6] * 2,
                   num_kv_heads=2, head_dim=16, sliding_window=8,
                   ring_block=4, rope_parameters=rope, num_experts=16,
                   num_experts_per_tok=3, max_seq_len=256)
        cfg.update(kw)
        return LagunaConfig(**cfg)


def _rotary(cfg, kind):
    """(r, inv_freq [r/2], the factor cos and sin are scaled by) of the
    layers of `kind`: r = head_dim * partial_rotary_factor rotated dims,
    YaRN where `rope_type` says so (`decoder.yarn_inv_freq` computes the
    frequencies; the published `attention_factor` is taken as given)."""
    p = cfg.rope_parameters[kind]
    r = int(round(cfg.head_dim * float(p.get("partial_rotary_factor", 1))))
    scaling = p if p.get("rope_type") == "yarn" else None
    inv_freq, factor = yarn_inv_freq(r, float(p["rope_theta"]), scaling)
    return r, inv_freq, float(p.get("attention_factor", factor))


def _cos_sin(cfg, kind, pos):
    """cos and sin [..., r] of the positions `pos` for `_rope`'s
    half-split pairing."""
    return _rotary_tables(pos, *_rotary(cfg, kind)[1:])


# jitted under a name of its own, so that a device trace can tell the
# chunk's attention from the rest of a prefill
@functools.partial(jax.jit, static_argnames=("scale", "window", "q_block"))
def _gqa_chunk_attention(q, k, v, live=None, sinks=None, *, scale,
                         window=None, q_block):
    """Causal grouped-query attention within a chunk: q [b, s, G hk, d],
    k [b, s, hk, d], v [b, s, hk, d_v] -> [b, s, G hk, d_v]; query head j
    reads key-value head j // G; with `window` a query sees the last
    `window` keys, itself included. A tile of `q_block` queries, the G
    heads of a key-value head folded into its rows, meets the key tiles it
    may see one at a time under an online softmax: tiles 0..i, or for a
    window those that meet the band (a window of 128 under tiles of 256:
    the tile before and its own), so the float32 scores are [b, hk, G
    q_block, q_block] and nothing outside the mask's tiles is computed.
    With `live` (a traced count) only the first `live` tiles of queries
    are computed and the others come out zero. With `sinks` [G hk]
    float32 a row's softmax starts from its head's sink logit (m = sink,
    l = 1, no value): one more term of the denominator."""
    b, s, hq, d = q.shape
    hk, dv = k.shape[2], v.shape[3]
    g = hq // hk
    qb = q_block if s % q_block == 0 else s

    def start():
        """(m, l) a tile's rows start from; a row is (head of the group,
        query of the tile)."""
        if sinks is None:
            return (jnp.full((b, hk, g * qb, 1), -1e9, F32),
                    jnp.zeros((b, hk, g * qb, 1), F32))
        m = jnp.repeat(sinks.astype(F32).reshape(hk, g), qb, axis=1)
        return (jnp.broadcast_to(m[None, :, :, None], (b, hk, g * qb, 1)),
                jnp.ones((b, hk, g * qb, 1), F32))
    qh = jnp.transpose(q.reshape(b, s, hk, g, d), (0, 2, 3, 1, 4))
    kh, vh = (jnp.transpose(t, (0, 2, 1, 3)) for t in (k, v))
    step = jnp.tile(jnp.arange(qb, dtype=jnp.int32), g)         # [g qb]
    back = 0 if window is None else window - 1                  # keys behind

    def one_tile(i, out):
        i = jnp.asarray(i, jnp.int32)
        rows = jax.lax.dynamic_slice_in_dim(qh, i * qb, qb, axis=3) \
            .reshape(b, hk, g * qb, d)
        row = i * qb + step

        def one_key_tile(j, carry):
            m, l, acc = carry
            j = jnp.asarray(j, jnp.int32)
            kt = jax.lax.dynamic_slice_in_dim(kh, j * qb, qb, axis=2)
            vt = jax.lax.dynamic_slice_in_dim(vh, j * qb, qb, axis=2)
            sc = jnp.einsum("bkrd,bktd->bkrt", rows, kt,
                            preferred_element_type=F32) * scale
            col = j * qb + jnp.arange(qb, dtype=jnp.int32)
            ok = col[None, :] <= row[:, None]
            if window is not None:
                ok = ok & (col[None, :] > row[:, None] - window)
            sc = jnp.where(ok, sc, -1e9)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum(
                "bkrt,bktd->bkrd", p.astype(vt.dtype), vt,
                preferred_element_type=F32)
            return m_new, l, acc

        first = jnp.int32(0) if window is None \
            else jnp.maximum(i * qb - back, 0) // qb
        # a tile's own key tile comes last: every row has its diagonal
        # there, which wipes out what a tile of masked keys left (alpha 0)
        _, l, acc = jax.lax.fori_loop(
            first, i + 1, one_key_tile,
            (*start(), jnp.zeros((b, hk, g * qb, dv), F32)))
        tile = (acc / l).astype(v.dtype).reshape(b, hk, g, qb, dv)
        return jax.lax.dynamic_update_slice_in_dim(out, tile, i * qb, axis=3)

    out = jax.lax.fori_loop(
        0, np.int32(s // qb) if live is None else live, one_tile,
        jnp.zeros((b, hk, g, s, dv), v.dtype))
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, s, hq, dv)


class GroupedAttention(_Weights):
    """What the grouped-query attentions of the nets with window layers
    share (this file's and `mimo_v2.py`'s): the rotary over a head's
    first dims, the fused projection's heads (`heads_of`) and `attend`,
    across the rows. A subclass sets `heads`, `kv`, `head_dim`, `window`
    (None in a full layer), `rot`, `round_to` and the matrix `qkv`, and
    brings the row-wise stages `project` and `output` and a `mix` that
    wraps `attend`."""

    def sink_logits(self):
        """[heads] float32, a learned sink logit a query head that joins
        its softmax's denominator, or None: this family has none."""
        return None

    def _rotate(self, x, cos, sin):
        """Rotary over the first `rot` dims of every head of x [b, t, n,
        d]; the rest passes through."""
        if self.rot == x.shape[-1]:
            return _rope(x, cos[:, :, None], sin[:, :, None])
        return jnp.concatenate(
            [_rope(x[..., :self.rot], cos[:, :, None], sin[:, :, None]),
             x[..., self.rot:]], axis=-1)

    def heads_of(self, a, cos, sin, value_dim=None, value_scale=1.0):
        """Row by row: a [b, t, H] in the parameters' dtype through the
        fused `qkv` matrix -> q [b, t, n, d] and k [b, t, kv, d] rotated,
        v [b, t, kv, `value_dim` or d] times `value_scale`, all in a's
        dtype (keys and values through `round_to` where a control sets
        it)."""
        b, t, _ = a.shape
        n, kv, d = self.heads, self.kv, self.head_dim
        qkv = jnp.dot(a, self.qkv._value, preferred_element_type=F32)
        q = self._rotate(qkv[..., :n * d].reshape(b, t, n, d), cos, sin)
        k = self._rotate(qkv[..., n * d:(n + kv) * d].reshape(b, t, kv, d),
                         cos, sin)
        v = qkv[..., (n + kv) * d:].reshape(b, t, kv, value_dim or d)
        if value_scale != 1.0:
            v = v * value_scale
        q, k, v = (x.astype(a.dtype) for x in (q, k, v))
        if self.round_to:
            k, v = (x.astype(self.round_to).astype(a.dtype) for x in (k, v))
        return q, k, v

    def attend(self, q, k, v, cache, rows):
        """Across the rows: keys and values cached (a full layer's in the
        slot's blocks, a sliding layer's in its ring), the attention ->
        (out [b, s, n d_v], new cache or None). q [b, s, n, d], k [b, s,
        kv, d], v [b, s, kv, d_v]. A chunk attends within itself,
        `rows.live` tiles of queries of it (None: all); one token attends
        over its slot's cache."""
        from ...nn.kv_pool import (paged_attention, window_attention,
                                   window_fill, window_write, write_kv)
        b, s, n, d = q.shape
        scale = d ** -0.5
        sinks = self.sink_logits()
        chunk = s > 1 or cache is None        # a prefill starts an empty slot
        if cache is not None:
            lens = jnp.asarray(cache.lengths, jnp.int32)
            k, v = k.astype(cache.k.dtype), v.astype(cache.v.dtype)
            if self.window is None:
                cache = cache._replace(
                    k=write_kv(cache.k, cache.block_tables, lens, k),
                    v=write_kv(cache.v, cache.block_tables, lens, v))
            elif chunk:
                count = (s if rows.last is None else rows.last[0] + 1)
                cache = cache._replace(k=window_fill(cache.k, k, count),
                                       v=window_fill(cache.v, v, count))
            else:
                cache = cache._replace(k=window_write(cache.k, lens, k),
                                       v=window_write(cache.v, lens, v))
        if chunk:
            out = _gqa_chunk_attention(q.astype(k.dtype), k, v, rows.live,
                                       sinks, scale=scale,
                                       window=self.window, q_block=rows.tile)
        elif self.window is None:
            out = jnp.swapaxes(paged_attention(
                jnp.swapaxes(q, 1, 2), cache.k, cache.v,
                cache.block_tables, lens, scale, sinks=sinks), 1, 2)
        else:
            out = jnp.swapaxes(window_attention(
                jnp.swapaxes(q, 1, 2), cache.k, cache.v, lens, scale,
                sinks), 1, 2)
        if cache is not None:
            cache = cache._replace(lengths=lens + jnp.int32(s))
        return out.reshape(b, s, -1).astype(q.dtype), cache


class GatedGroupedAttention(GroupedAttention):
    """Grouped-query attention with a sigmoid gate a query head, in the
    three stages a block runs: `project` and `output` row by row, `mix`
    across the rows."""

    def __init__(self, cfg: LagunaConfig, kind, heads):
        super().__init__(cfg)
        H, d = cfg.hidden_size, cfg.head_dim
        self.heads, self.kv = int(heads), cfg.num_kv_heads
        self.head_dim = d
        self.window = cfg.sliding_window if kind == SLIDING else None
        self.rot = _rotary(cfg, kind)[0]
        self.round_to = cfg.kv_round_to
        self.qkv = self.matrix(H, (self.heads + 2 * self.kv) * d)
        self.g = self.matrix(H, self.heads)
        self.o = self.matrix(self.heads * d, H)

    def project(self, a, cos, sin):
        """Row by row: the normed stream a [b, t, H] float32 -> q [b, t,
        n, d] and k [b, t, kv, d] rotated, v [b, t, kv, d], all in the
        parameters' dtype (keys and values through `kv_round_to` where a
        control sets it), and the gates [b, t, n] float32."""
        a = a.astype(self.qkv._value.dtype)
        gate = jax.nn.sigmoid(jnp.dot(a, self.g._value,
                                      preferred_element_type=F32))
        return (*self.heads_of(a, cos, sin), gate)

    def mix(self, q, k, v, gate, cache, rows):
        """`attend`, the gates carried beside it to `output` -> ((out,
        gate), new cache or None)."""
        out, cache = self.attend(q, k, v, cache, rows)
        return (out, gate), cache

    def output(self, out, gate):
        """Row by row: each head's output times its gate, the output
        projection -> y [b, t, H] float32."""
        b, t, _ = out.shape
        gated = out.reshape(b, t, self.heads, self.head_dim).astype(F32) \
            * gate[..., None]
        return jnp.dot(gated.reshape(b, t, -1).astype(self.o._value.dtype),
                       self.o._value, preferred_element_type=F32)


class WindowBlock(_Weights):
    """A pre-norm block of a net with window layers: the three stages of
    its attention around the rows' tiles, then a dense or a sparse FFN.
    `__init__` takes what a family builds: `kind` (FULL | SLIDING), `attn`
    (a `GroupedAttention`), `ffn` (`DenseFFN`, or `nn.RoutedExperts` when
    `sparse`)."""

    def __init__(self, cfg, kind, attn, ffn, sparse):
        super().__init__(cfg)
        self.eps = cfg.rms_norm_eps
        self.kind = kind
        self.attn_norm = self.ones(cfg.hidden_size)
        self.attn = attn
        self.ffn_norm = self.ones(cfg.hidden_size)
        self.sparse = sparse
        self.ffn = ffn

    def forward(self, x, rope, cache, rows):
        """x [b, s, H] float32: the residual stream stays float32; what a
        matrix multiplies is rounded to the parameters' dtype. `rope`: cos
        and sin by layer kind. -> (y, (new cache,), (pairs per held expert
        [count] i32, or None from a dense layer,)). `rows.live`: the tiles of
        `rows.tile` rows that hold a token (`_live_rows`), None for all;
        the expert layer runs once over all the rows, its cost being its
        weights'."""
        dtype = self.attn.o._value.dtype
        attn, ffn = self.attn, self.ffn
        cos, sin = rope[self.kind]
        valid, live, tile = rows.valid, rows.live, rows.tile
        word = "attn" if self.kind == FULL else "window_attn"

        def before(x, cos, sin):
            with jax.named_scope(word):
                return attn.project(_rms(x, self.attn_norm._value, self.eps),
                                    cos, sin)

        def after(x, *mixed):
            with jax.named_scope(word):
                h = x + attn.output(*mixed)
            with jax.named_scope("ffn"):
                f = _rms(h, self.ffn_norm._value, self.eps).astype(dtype)
                if self.sparse:
                    return h, f
                return h + _swiglu(f, ffn.gate._value, ffn.up._value,
                                   ffn.down._value), f

        parts = _live_rows(before, live, tile, x, cos, sin)
        with jax.named_scope(word):
            mixed, cache = attn.mix(*parts, cache, rows)
        y, f = _live_rows(after, live, tile, x, *mixed)
        if not self.sparse:
            return y, (cache,), (None,)
        with jax.named_scope("ffn"):   # `routed` names its own parts
            b, s, H = f.shape
            m, counts, _ = ffn.routed(
                f.reshape(b * s, H),
                None if valid is None else valid.reshape(b * s))
            return y + m.reshape(b, s, H).astype(F32), (cache,), (counts,)


class LagunaBlock(WindowBlock):
    def __init__(self, cfg: LagunaConfig, index):
        kind = cfg.layer_types[index]
        sparse = index not in tuple(cfg.mlp_only_layers)
        super().__init__(
            cfg, kind, GatedGroupedAttention(
                cfg, kind, cfg.num_attention_heads_per_layer[index]),
            nn.RoutedExperts(
                cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_tok, held=cfg.experts_held,
                routed_scaling_factor=cfg.routed_scaling_factor,
                shared_width=cfg.shared_expert_intermediate_size,
                dtype=cfg.dtype, init_std=cfg.init_std, score_func="softmax",
                norm_topk_prob=cfg.norm_topk_prob) if sparse
            else DenseFFN(cfg), sparse)


def window_cache_spec(layer_types, window, ring_block, keys, values):
    """One `CacheSpec` a layer, by its kind: a full layer pages keys and
    values by token (`PagedKVCache`), a sliding layer keeps a ring of
    `window` tokens a slot (`WindowKVCache`: two per-slot arrays, no
    arena, no block of the pool). `keys` and `values` map a kind to the
    (heads, dim) of what a token caches there."""
    from ...nn.kv_pool import (CacheSpec, PagedKVCache, WindowKVCache,
                               window_ring_shape)
    return [CacheSpec(PagedKVCache, (keys[kind], values[kind]))
            if kind == FULL else CacheSpec(WindowKVCache, (), tuple(
                (window_ring_shape(window, ring_block, *per_head), None)
                for per_head in (keys[kind], values[kind])))
            for kind in layer_types]


class WindowDecoder(PagedDecoder):
    """What the nets with window layers share past `PagedDecoder` and
    their blocks: the residual stream in float32, a rotary table a layer
    kind, and the attention's counts. A family's `__init__` names its
    block (`block(cfg, index)`); its config gives, beside
    `PagedDecoder`'s, `layer_types`, `sliding_window`, `rope_parameters`
    (`_rotary`) and `head_dim`; it brings `paged_cache_spec`."""

    SERVE_STATS = MOE_STATS + ATTN_STATS
    SERVE_GAUGES = ("window_ring_bytes",)
    SLOT_COUNTS = True

    def __init__(self, cfg, block):
        super().__init__(cfg, lambda i: block(cfg, i))

    def _embed(self, ids, pos):
        cfg = self.config
        # the kinds in the layers' order, not a set's: the order the two
        # tables are traced in is part of the program's text, which keys
        # the compile cache, and a set's changes with the hash seed
        return (jnp.take(self.embed._value, ids, axis=0).astype(F32),
                {kind: _cos_sin(cfg, kind, pos)
                 for kind in dict.fromkeys(cfg.layer_types)})

    def _counted(self, caches, rows):
        """[cached tokens the full layers attended to, the sliding layers,
        the rings' KiB]."""
        from ...nn.kv_pool import WindowKVCache
        cfg = self.config
        # what one token a slot attends to, this step's token included
        seen = jnp.where(rows.owned, rows.lens + 1, 0)
        n_full = sum(kind == FULL for kind in cfg.layer_types)
        rings = [c for c in caches if isinstance(c, WindowKVCache)]
        read = jnp.stack([
            n_full * jnp.sum(seen),
            len(rings) * jnp.sum(jnp.minimum(seen, cfg.sliding_window)),
            jnp.int32(sum(c.k.nbytes + c.v.nbytes for c in rings) // 1024)])
        return (read,)

    def serve_counters(self, kind, counted, n_tokens):
        """`counted`: the pairs each held expert got [expert layers, held]
        and `_counted`'s three."""
        out = moe_counters(kind, counted[0], n_tokens)
        if kind == "decode":
            full, window, ring_kib = (int(x) for x in np.asarray(counted[1]))
            out.update(attn_full_decode_tokens_read=full,
                       attn_window_decode_tokens_read=window,
                       window_ring_bytes=ring_kib * 1024)
        return out


class Laguna(WindowDecoder):
    def __init__(self, config: LagunaConfig = None):
        super().__init__(config or LagunaConfig(), LagunaBlock)

    def paged_cache_spec(self):
        """`window_cache_spec`: keys and values alike, a key-value head's
        width, in pages and in rings."""
        cfg = self.config
        per_head = dict.fromkeys((FULL, SLIDING),
                                 (cfg.num_kv_heads, cfg.head_dim))
        return window_cache_spec(cfg.layer_types, cfg.sliding_window,
                                 cfg.ring_block, per_head, per_head)
