"""Transformer layers.

Analog of reference python/paddle/nn/layer/transformer.py
(MultiHeadAttention at :85, TransformerEncoderLayer :443, TransformerEncoder
:575, TransformerDecoderLayer :642, TransformerDecoder :791, Transformer
:967). TPU design deltas:
  - the attention core routes through F.scaled_dot_product_attention so a
    single site swaps in the Pallas flash-attention kernel / ring attention
    (paddle_tpu.distributed.ring_attention) for long sequences;
  - projections are single fused matmuls ([d, 3d] qkv when self-attention)
    to keep the MXU busy;
  - tensor-parallel presets shard num_heads / ffn hidden via
    paddle_tpu.distributed.sharding rules keyed on parameter names.
"""
from __future__ import annotations

import typing

import jax

from ... import ops
from .. import functional as F
from .. import initializer as I
from .common import Dropout, Linear
from .container import LayerList
from .layers import Layer
from .norm import LayerNorm

__all__ = ["MultiHeadAttention", "StaticKVCache", "TransformerEncoderLayer",
           "TransformerEncoder", "TransformerDecoderLayer",
           "TransformerDecoder", "Transformer"]


class StaticKVCache(typing.NamedTuple):
    """Preallocated KV cache for incremental decoding — the TPU redesign of
    the reference's Cache/StaticCache tuples (reference
    python/paddle/nn/layer/transformer.py:85 MultiHeadAttention.Cache).

    The reference grows its cache by concat each step, which on XLA means a
    new shape — and a fresh compilation — per generated token. Here k/v are
    fixed [b, heads, max_len, head_dim] buffers written in place with
    lax.dynamic_update_slice at `index` (an i32 scalar = tokens filled), so
    the decode step keeps ONE static shape: jit once, O(1) work per token,
    scan-able. Fields are raw jnp arrays (a pytree — usable as a lax.scan
    carry)."""

    k: object    # [b, h, max_len, head_dim]
    v: object    # [b, h, max_len, head_dim]
    index: object  # i32 scalar: number of valid positions


def _static_cache_attention(q, kc, vc, index, scale, dropout_p, training):
    """Attention of q [b,h,s,d] over a partially-filled cache [b,h,L,d]:
    position p = index + row attends to cache cols <= p (causal within the
    new chunk, everything before it unconditionally)."""
    import jax.numpy as jnp
    s, L = q.shape[2], kc.shape[2]
    row = index + jnp.arange(s, dtype=jnp.int32)[:, None]      # [s, 1]
    col = jnp.arange(L, dtype=jnp.int32)[None, :]              # [1, L]
    live = col <= row                                          # [s, L]
    scores = jnp.einsum("bhsd,bhld->bhsl", q, kc,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(live[None, None], scores, -1e9)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if dropout_p and training:
        from ...core import rng as _rng
        keep = 1.0 - dropout_p
        p = p * jax.random.bernoulli(_rng.next_key(), keep, p.shape) / keep
    return jnp.einsum("bhsl,bhld->bhsd", p, vc)


def _decode_kernel_eligible(q, kc, training):
    """Gate for the Pallas decode-attention kernel on the StaticKVCache
    path (ops/pallas/decode_attention.py). Every rejection is counted as
    pallas.gate_reject.decode_attention.{reason} so bench output can say
    why the cache path ran on jnp."""
    from ...core import flags as _flags
    from ...ops.pallas import gate_reject
    if not _flags.flag("FLAGS_use_decode_attention"):
        return gate_reject("decode_attention", "flag_off")
    from .. import functional as F
    if not F._pallas_backend_ok():
        return gate_reject("decode_attention", "backend")
    if training:
        # the kernel is eval-only (no dropout, no vjp — differentiating
        # the pallas_call would fail); training-time cache attention
        # stays on the jnp path even at dropout=0
        return gate_reject("decode_attention", "training")
    from ...ops.pallas.decode_attention import supported
    if not supported(tuple(q.shape), tuple(kc.shape)):
        return gate_reject("decode_attention", "shape")
    return True


def _convert_attention_mask(attn_mask, dtype):
    if attn_mask is None:
        return None
    if attn_mask.dtype == ops.zeros([1], "bool").dtype:
        return attn_mask
    return attn_mask


class MultiHeadAttention(Layer):
    def __init__(self, embed_dim, num_heads, dropout=0.0, kdim=None,
                 vdim=None, need_weights=False, weight_attr=None,
                 bias_attr=None, fuse_qkv=True):
        super().__init__()
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        assert self.head_dim * num_heads == embed_dim
        self.dropout = dropout
        self.need_weights = need_weights
        self.kdim = kdim or embed_dim
        self.vdim = vdim or embed_dim
        self._fuse_qkv = fuse_qkv and self.kdim == embed_dim \
            and self.vdim == embed_dim
        if self._fuse_qkv:
            self.qkv_proj = Linear(embed_dim, 3 * embed_dim,
                                   weight_attr=weight_attr,
                                   bias_attr=bias_attr)
        else:
            self.q_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)
            self.k_proj = Linear(self.kdim, embed_dim, weight_attr, bias_attr)
            self.v_proj = Linear(self.vdim, embed_dim, weight_attr, bias_attr)
        self.out_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)

    def _split_heads(self, x):
        b, s = x.shape[0], x.shape[1]
        x = ops.reshape(x, [b, s, self.num_heads, self.head_dim])
        return ops.transpose(x, [0, 2, 1, 3])

    def forward(self, query, key=None, value=None, attn_mask=None,
                cache=None, is_causal=False):
        key = query if key is None else key
        value = query if value is None else value
        self_attn = key is query and value is query
        if self._fuse_qkv and self_attn:
            qkv = self.qkv_proj(query)
            q, k, v = ops.split(qkv, 3, axis=-1)
        elif self._fuse_qkv:
            w = self.qkv_proj.weight
            bvec = self.qkv_proj.bias
            wq, wk, wv = ops.split(w, 3, axis=-1)
            bq, bk, bv = ops.split(bvec, 3, axis=-1)
            q = F.linear(query, wq, bq)
            k = F.linear(key, wk, bk)
            v = F.linear(value, wv, bv)
        else:
            q, k, v = self.q_proj(query), self.k_proj(key), self.v_proj(value)

        q, k, v = self._split_heads(q), self._split_heads(k), self._split_heads(v)
        if cache is not None:
            from ..kv_pool import PagedKVCache
        if cache is not None and isinstance(cache, PagedKVCache):
            # paged (block-table) decode path: the serving tier's shared
            # arena (nn/kv_pool.py). Same contract as StaticKVCache —
            # write the chunk's k/v, attend with causality from the
            # per-slot fill counts — but the cache is a physical block
            # arena shared across requests, indirected per slot.
            if attn_mask is not None:
                raise ValueError(
                    "attn_mask is not supported with a PagedKVCache: "
                    "causality comes from the per-slot lengths.")
            from ..kv_pool import paged_write_attend
            from ...core.tensor import Tensor
            import jax.numpy as jnp
            # the serve programs' `attn` (core/program_map.SCOPES): the
            # cache write and the attention (one kernel for a decode
            # step's token where kv_pool's gate admits, else the writer,
            # then the kernel or its jnp form), the projection out
            with jax.named_scope("attn"):
                kj = ops.transpose(k, [0, 2, 1, 3])._value  # [b, s, h, d]
                vj = ops.transpose(v, [0, 2, 1, 3])._value
                lens = jnp.asarray(cache.lengths, jnp.int32)
                qv = q._value
                out, kc, vc = paged_write_attend(
                    qv, cache.k, cache.v, cache.block_tables, lens, kj, vj,
                    self.head_dim ** -0.5, training=self.training)
                out = ops.transpose(Tensor(out, _internal=True),
                                    [0, 2, 1, 3])
                b, s = out.shape[0], out.shape[1]
                out = self.out_proj(ops.reshape(out,
                                                [b, s, self.embed_dim]))
            new_cache = PagedKVCache(kc, vc, cache.block_tables,
                                     lens + jnp.int32(qv.shape[2]))
            return out, new_cache
        if isinstance(cache, StaticKVCache):
            if attn_mask is not None:
                raise ValueError(
                    "attn_mask is not supported with a StaticKVCache: "
                    "causality comes from the cache index, and a padding "
                    "mask would be silently dropped. Left-trim padding or "
                    "use the dynamic (list) cache instead.")
            import jax.numpy as jnp
            kj, vj = k._value.astype(cache.k.dtype), \
                v._value.astype(cache.v.dtype)
            idx = jnp.asarray(cache.index, jnp.int32)
            zero = jnp.int32(0)
            kc = jax.lax.dynamic_update_slice(cache.k, kj,
                                              (zero, zero, idx, zero))
            vc = jax.lax.dynamic_update_slice(cache.v, vj,
                                              (zero, zero, idx, zero))
            qv = q._value
            scale = self.head_dim ** -0.5
            if _decode_kernel_eligible(qv, kc, self.training):
                from ...ops.pallas import decode_attention, run_guarded
                out = run_guarded(
                    "decode_attention",
                    lambda: decode_attention(qv, kc, vc, idx, scale))
            else:
                out = _static_cache_attention(
                    qv, kc, vc, idx, scale, self.dropout, self.training)
            from ...core.tensor import Tensor
            out = ops.transpose(Tensor(out, _internal=True), [0, 2, 1, 3])
            b, s = out.shape[0], out.shape[1]
            out = self.out_proj(ops.reshape(out, [b, s, self.embed_dim]))
            new_cache = StaticKVCache(kc, vc, idx + jnp.int32(kj.shape[2]))
            return out, new_cache
        if cache is not None:
            k = ops.concat([cache[0], k], axis=2)
            v = ops.concat([cache[1], v], axis=2)
        mask = _convert_attention_mask(attn_mask, q.dtype)
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=mask, dropout_p=self.dropout,
            is_causal=is_causal, training=self.training)
        out = ops.transpose(out, [0, 2, 1, 3])
        b, s = out.shape[0], out.shape[1]
        out = ops.reshape(out, [b, s, self.embed_dim])
        out = self.out_proj(out)
        if cache is not None:
            return out, (k, v)
        return out

    def gen_cache(self, key, value=None, type=None):  # noqa: A002
        b = key.shape[0]
        k = ops.zeros([b, self.num_heads, 0, self.head_dim], "float32")
        return (k, k)

    def gen_static_cache(self, batch_size, max_len, dtype="float32"):
        """Preallocated O(1)-per-token decode cache (see StaticKVCache)."""
        import jax.numpy as jnp

        from ...core.dtype import to_jax_dtype
        shape = (batch_size, self.num_heads, max_len, self.head_dim)
        z = jnp.zeros(shape, to_jax_dtype(dtype))
        return StaticKVCache(z, z, jnp.int32(0))


class TransformerEncoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead,
                                            dropout=attn_dropout,
                                            weight_attr=weight_attr,
                                            bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.act_dropout = Dropout(act_dropout)
        self.activation = getattr(F, activation)

    def forward(self, src, src_mask=None, cache=None):
        residual = src
        if self.normalize_before:
            src = self.norm1(src)
        src = self.self_attn(src, attn_mask=src_mask)
        src = residual + self.dropout1(src)
        if not self.normalize_before:
            src = self.norm1(src)
        residual = src
        if self.normalize_before:
            src = self.norm2(src)
        src = self.linear2(self.act_dropout(self.activation(self.linear1(src))))
        src = residual + self.dropout2(src)
        if not self.normalize_before:
            src = self.norm2(src)
        return src


class TransformerEncoder(Layer):
    def __init__(self, encoder_layer, num_layers, norm=None):
        super().__init__()
        import copy
        self.layers = LayerList(
            [encoder_layer] + [copy.deepcopy(encoder_layer)
                               for _ in range(num_layers - 1)])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, src, src_mask=None):
        out = src
        for layer in self.layers:
            out = layer(out, src_mask=src_mask)
        if self.norm is not None:
            out = self.norm(out)
        return out


class TransformerDecoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                            weight_attr=weight_attr,
                                            bias_attr=bias_attr)
        self.cross_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                             weight_attr=weight_attr,
                                             bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.norm3 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.dropout3 = Dropout(dropout)
        self.act_dropout = Dropout(act_dropout)
        self.activation = getattr(F, activation)

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None,
                cache=None):
        """cache: optional StaticKVCache for the self-attention —
        incremental decoding (returns (out, new_cache)); the cache's
        position index supplies causality, so tgt_mask is not needed on
        the cached path (reference TransformerDecoderLayer cache=(Cache,
        StaticCache), redesigned static-shape — see StaticKVCache)."""
        residual = tgt
        if self.normalize_before:
            tgt = self.norm1(tgt)
        if isinstance(cache, StaticKVCache):
            tgt, new_cache = self.self_attn(tgt, cache=cache)
        else:
            tgt = self.self_attn(tgt, attn_mask=tgt_mask)
            new_cache = None
        tgt = residual + self.dropout1(tgt)
        if not self.normalize_before:
            tgt = self.norm1(tgt)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm2(tgt)
        tgt = self.cross_attn(tgt, memory, memory, attn_mask=memory_mask)
        tgt = residual + self.dropout2(tgt)
        if not self.normalize_before:
            tgt = self.norm2(tgt)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm3(tgt)
        tgt = self.linear2(self.act_dropout(self.activation(self.linear1(tgt))))
        tgt = residual + self.dropout3(tgt)
        if not self.normalize_before:
            tgt = self.norm3(tgt)
        if new_cache is not None:
            return tgt, new_cache
        return tgt

    def gen_static_cache(self, batch_size, max_len, dtype="float32"):
        return self.self_attn.gen_static_cache(batch_size, max_len, dtype)


class TransformerDecoder(Layer):
    def __init__(self, decoder_layer, num_layers, norm=None):
        super().__init__()
        import copy
        self.layers = LayerList(
            [decoder_layer] + [copy.deepcopy(decoder_layer)
                               for _ in range(num_layers - 1)])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None,
                cache=None):
        """cache: optional list of per-layer StaticKVCache (from
        gen_static_cache) — incremental decoding; returns (out,
        new_caches)."""
        out = tgt
        new_caches = [] if cache is not None else None
        for i, layer in enumerate(self.layers):
            if cache is not None:
                out, c = layer(out, memory, memory_mask=memory_mask,
                               cache=cache[i])
                new_caches.append(c)
            else:
                out = layer(out, memory, tgt_mask=tgt_mask,
                            memory_mask=memory_mask)
        if self.norm is not None:
            out = self.norm(out)
        if new_caches is not None:
            return out, new_caches
        return out

    def gen_static_cache(self, batch_size, max_len, dtype="float32"):
        """One StaticKVCache per layer (reference TransformerDecoder
        gen_cache), for O(1)-per-token decoding."""
        return [layer.gen_static_cache(batch_size, max_len, dtype)
                for layer in self.layers]


class Transformer(Layer):
    """Full encoder-decoder (reference nn/layer/transformer.py:967)."""

    def __init__(self, d_model=512, nhead=8, num_encoder_layers=6,
                 num_decoder_layers=6, dim_feedforward=2048, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None,
                 custom_encoder=None, custom_decoder=None):
        super().__init__()
        if custom_encoder is not None:
            self.encoder = custom_encoder
        else:
            enc_layer = TransformerEncoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr,
                bias_attr)
            enc_norm = LayerNorm(d_model) if normalize_before else None
            self.encoder = TransformerEncoder(enc_layer, num_encoder_layers,
                                              enc_norm)
        if custom_decoder is not None:
            self.decoder = custom_decoder
        else:
            dec_layer = TransformerDecoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr,
                bias_attr)
            dec_norm = LayerNorm(d_model) if normalize_before else None
            self.decoder = TransformerDecoder(dec_layer, num_decoder_layers,
                                              dec_norm)
        self.d_model = d_model
        self.nhead = nhead

    def forward(self, src, tgt, src_mask=None, tgt_mask=None,
                memory_mask=None):
        memory = self.encoder(src, src_mask=src_mask)
        return self.decoder(tgt, memory, tgt_mask=tgt_mask,
                            memory_mask=memory_mask)

    @staticmethod
    def generate_square_subsequent_mask(length):
        import jax.numpy as jnp
        from ...ops._dispatch import wrap
        m = jnp.where(jnp.tril(jnp.ones((length, length), bool)), 0.0,
                      float("-inf")).astype(jnp.float32)
        return wrap(m)
