"""OLMo-hybrid decoder (`model_type: olmo_hybrid`): a stack assembled
from `layer_types`, three gated delta-rule linear-attention layers and
then one full multi-head attention layer, over and over; every block has
a SwiGLU feed-forward part and RMSNorm on each sublayer's OUTPUT (the
OLMo 2 / 3 reordered norm), an untied output head. Served by
`inference/serving.ServeLoop`; `paddle_tpu/text/models/reference/
olmo_hybrid.py` is the same mathematics in plain float32 `jax.numpy`.

Block:  h = x + RMSNorm(Mixer(x));  y = h + RMSNorm(SwiGLU(h)).

`linear_attention` (Gated Delta Networks, arXiv:2412.06464), n heads:
        [q~ | k~ | v~] = x W_qkv through a causal depthwise convolution
        (width 4) and SiLU; q, k L2-normalised per head, q scaled by
        dk^-1/2; beta = 2 sigmoid(x W_b); alpha = exp(-exp(A_log)
        softplus(x W_a + dt_bias)); per head a state S [dk, dv]:
        S <- alpha S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q;
        y = (RMSNorm_dv(o) * SiLU(x W_g)) W_o.
        What a SLOT caches, whatever its length: S in float32 and the
        last 3 inputs of the convolution (`SlotStateCache`).
`full_attention`: q, k, v from W_qkv, RMSNorm over the whole width of q
        and of k, heads of hidden_size / num_heads, causal softmax, W_o;
        no rotary embedding (the source's `rope_theta` is null). What a
        TOKEN caches: keys and values a head (`PagedKVCache`).

Two computation paths, the same mathematics:
- a chunk of s > 1 tokens (a prefill) starts an EMPTY slot: the linear
  layers run the chunked scan from S = 0 (`ops/pallas/gated_delta.
  gdn_chunk_scan`), tokens past `last_index` masked so that they change
  nothing (alpha = 1, beta = 0), the convolution's cache taken from the 3
  tokens before it; the full layers write their keys and values and
  attend within the chunk, the queries in blocks. What works row by row
  (the projections, the feed-forward part, the queries' blocks) runs over
  the tiles of `OlmoHybrid.PREFILL_TILE` rows that hold a token and leaves
  the rest of the bucket zero (`_live_rows`): a prompt just over a
  bucket's half pays for its tiles, not for the bucket;
- one token a slot (a decode step): the linear layers update every
  slot's state in place (`gdn_step`), the full layers go through
  `write_kv` + `paged_attention` (the Pallas pair).
Inference only: the forward passes are array code under no tape.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ...nn import initializer as I
from ...nn.layer.experts import _swiglu
from .decoder import (F32, FULL, DenseFFN, PagedDecoder, _live_rows, _rms,
                      _Weights)

__all__ = ["OlmoHybrid", "OlmoHybridConfig", "LINEAR_STATS"]

LINEAR = "linear_attention"

# what the linear layers count for `ServeLoop.stats()`: prompt tokens
# their prefills scanned, the padding scanned beside them (bucket less
# prompt), and layer-steps of the decode state update
LINEAR_STATS = ("linear_prefill_tokens", "linear_prefill_pad_tokens",
                "linear_decode_layer_steps")


@dataclass
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_heads: int = 30
    layer_types: list = field(
        default_factory=lambda: [LINEAR, LINEAR, LINEAR, FULL] * 8)
    linear_num_heads: int = 30           # key heads = value heads
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True  # beta = 2 sigmoid(.)
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 65536
    dtype: str = "float32"               # parameters are BORN in it
    init_std: float = 0.02

    @property
    def num_layers(self):
        return len(self.layer_types)

    @staticmethod
    def tiny(**kw):
        cfg = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
                   num_heads=2, layer_types=[LINEAR, LINEAR, LINEAR, FULL] * 2,
                   linear_num_heads=2, linear_key_head_dim=8,
                   linear_value_head_dim=16, max_seq_len=256)
        cfg.update(kw)
        return OlmoHybridConfig(**cfg)


# jitted under a name of its own, so that a device trace can tell the
# chunk's attention from the rest of a prefill
@functools.partial(jax.jit, static_argnames=("scale", "q_block"))
def _chunk_attention(q, k, v, live=None, *, scale, q_block):
    """Causal attention within a chunk: q, k, v [b, s, h, d] -> [b, s, h,
    d]. Queries go `q_block` at a time, so the float32 scores are [b, h,
    q_block, s] and never [b, h, s, s] (2 GB at 30 heads of 4096); with
    `live` (a traced count) only the first `live` blocks of queries are
    computed and the others come out zero."""
    s = q.shape[1]
    qb = min(q_block, s)
    if s % qb:
        raise ValueError(f"chunk of {s} tokens is no multiple of {qb}")
    col = jnp.arange(s, dtype=jnp.int32)

    def one_block(i):
        start = i * qb
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk",
            jax.lax.dynamic_slice_in_dim(q, start, qb, axis=1), k,
            preferred_element_type=F32) * scale
        row = start + jnp.arange(qb, dtype=jnp.int32)
        scores = jnp.where(col[None, :] <= row[:, None], scores, -1e9)
        p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                          preferred_element_type=F32).astype(v.dtype)

    if qb == s:
        return one_block(jnp.int32(0))
    return jax.lax.fori_loop(
        0, np.int32(s // qb) if live is None else live,
        lambda i, out: jax.lax.dynamic_update_slice_in_dim(
            out, one_block(i), i * qb, axis=1), jnp.zeros_like(v))


def _l2_norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


class LinearAttention(_Weights):
    """The gated delta-rule mixer, in the three stages a block runs:
    `project` and `output` row by row, `mix` across the rows."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__(cfg)
        H, n = cfg.hidden_size, cfg.linear_num_heads
        self.heads, self.eps = n, cfg.rms_norm_eps
        self.dk, self.dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        self.taps = cfg.linear_conv_kernel_dim
        self.beta_scale = 2.0 if cfg.linear_allow_neg_eigval else 1.0
        self.channels = n * (2 * self.dk + self.dv)
        self.qkv = self.matrix(H, self.channels)
        self.conv = self.matrix(self.taps, self.channels)
        self.g = self.matrix(H, n * self.dv)
        self.b, self.a = self.matrix(H, n), self.matrix(H, n)
        # decay and write strength spread as a trained layer's (the
        # gated-delta-net layer's own initialiser), kept in float32
        self.A_log = self.create_parameter(
            [n], dtype="float32", default_initializer=I.Uniform(1.0, 16.0))
        self.A_log._value = jnp.log(self.A_log._value)
        self.dt_bias = self.create_parameter(
            [n], dtype="float32",
            default_initializer=I.Uniform(math.log(0.001), math.log(0.1)))
        dt = jnp.exp(self.dt_bias._value)
        self.dt_bias._value = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
        self.o_norm = self.ones(self.dv)
        self.o = self.matrix(n * self.dv, H)

    def state_spec(self):
        """((row shape, dtype or None for the pool's), ...) of what one
        slot caches: the state in the kernels' layout, float32, and the
        convolution's last inputs."""
        return (((self.dk, self.heads * self.dv), "float32"),
                ((self.taps - 1, self.channels), None))

    def _conv(self, proj, cache, last):
        """The causal depthwise convolution and SiLU over proj [b, s,
        channels], in float32 (the kernels take q, k and v as they come
        out), and the inputs the next token's window starts with (kept in
        the pool's dtype): a
        chunk starts an empty slot (zeros before it) and leaves the
        `taps - 1` inputs up to `last`; one token slides the cached
        window."""
        b, s, _ = proj.shape
        w = self.conv._value.astype(F32)
        if s == 1 and cache is not None:
            window = jnp.concatenate([cache.conv.astype(F32), proj],
                                     axis=1)                 # [b, taps, C]
            y = jnp.einsum("btc,tc->bc", window, w)[:, None]
            return jax.nn.silu(y), window[:, 1:].astype(cache.conv.dtype)
        hist = self.taps - 1
        padded = jnp.pad(proj, ((0, 0), (hist, 0), (0, 0)))
        y = sum(w[j] * padded[:, j:j + s].astype(F32)
                for j in range(self.taps))
        keep = None
        if cache is not None:
            # inputs last-hist+1..last sit at padded rows last+1..last+hist
            keep = jax.vmap(lambda p, at: jax.lax.dynamic_slice_in_dim(
                p, at + 1, hist, axis=0))(padded, last)
            keep = keep.astype(cache.conv.dtype)
        return jax.nn.silu(y), keep

    def project(self, x):
        """Row by row: x [b, t, H] float32 -> float32 (the two gates'
        pre-activations [b, t, 2 n], q | k | v before the convolution
        [b, t, channels], the output gate [b, t, n dv]). What a matrix
        multiplies is rounded to the parameters' dtype; everything between
        the products stays float32."""
        # the two gates read the float32 stream at full precision: 60
        # columns, and the decay's exponent multiplies what they carry by
        # up to exp(A_log) = 16
        gates = jnp.dot(x, jnp.concatenate(
            [self.b._value, self.a._value], axis=1).astype(F32),
            precision=jax.lax.Precision.HIGHEST)
        x = x.astype(self.qkv._value.dtype)
        proj = jnp.dot(x, self.qkv._value, preferred_element_type=F32)
        gate = jax.nn.silu(jnp.dot(x, self.g._value,
                                   preferred_element_type=F32))
        return gates, proj, gate

    def mix(self, gates, proj, gate, cache, rows):
        """Across the rows: the convolution and the recurrence, from
        `project`'s parts -> ((o [b, s, n dv] float32, gate), new cache or
        None). `rows.valid` [b, s] marks the tokens that exist; the others
        leave the state alone. `rows.last` [b] is the last valid index of
        a chunk."""
        from ...ops.pallas.gated_delta import (gdn_chunk_scan, gdn_step,
                                               state_layout)
        b, s, _ = proj.shape
        n, dk, dv = self.heads, self.dk, self.dv
        valid, last = rows.valid, rows.last
        if last is None:
            last = jnp.full((b,), s - 1, jnp.int32)
        qkv, conv = self._conv(proj, cache, last)
        q = _l2_norm(qkv[..., :n * dk].reshape(b, s, n, dk)) * dk ** -0.5
        k = _l2_norm(qkv[..., n * dk:2 * n * dk].reshape(b, s, n, dk))
        v = qkv[..., 2 * n * dk:].reshape(b, s, n, dv)
        beta = self.beta_scale * jax.nn.sigmoid(gates[..., :n])
        log_alpha = -jnp.exp(self.A_log._value) * jax.nn.softplus(
            gates[..., n:] + self.dt_bias._value)
        if valid is not None:
            beta = jnp.where(valid[..., None], beta, 0.0)
            log_alpha = jnp.where(valid[..., None], log_alpha, 0.0)
        if s == 1 and cache is not None:
            with jax.named_scope("gdn_step"):
                o, state = gdn_step(cache.state.astype(F32), q[:, 0],
                                    k[:, 0], v[:, 0],
                                    jnp.exp(log_alpha[:, 0]), beta[:, 0])
            o = o[:, None]
        else:
            with jax.named_scope("gdn_chunk"):
                o, state = gdn_chunk_scan(q, k, v, log_alpha, beta)
            state = state_layout(state)
        mixed = (o.reshape(b, s, n * dv), gate)
        if cache is None:
            return mixed, None
        # the recurrence is float32; the pool says what it is KEPT in
        return mixed, cache._replace(
            state=state.astype(cache.state.dtype), conv=conv)

    def output(self, o, gate):
        """Row by row: the heads' norm, the gate, the output projection
        -> y [b, t, H] float32."""
        b, t, _ = o.shape
        o = _rms(o.reshape(b, t, self.heads, self.dv), self.o_norm._value,
                 self.eps).reshape(b, t, -1)
        return jnp.dot((o * gate).astype(self.o._value.dtype),
                       self.o._value, preferred_element_type=F32)


class FullAttention(_Weights):
    """Multi-head attention with QK-norm over the whole width, no rotary
    embedding; `project`, `mix`, `output` as `LinearAttention`'s."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__(cfg)
        H = cfg.hidden_size
        self.heads, self.eps = cfg.num_heads, cfg.rms_norm_eps
        self.head_dim = H // cfg.num_heads
        self.qkv = self.matrix(H, 3 * H)
        self.q_norm, self.k_norm = self.ones(H), self.ones(H)
        self.o = self.matrix(H, H)

    def project(self, x):
        """Row by row: x [b, t, H] float32 -> q, k (normalised over the
        whole width), v, each [b, t, h, d] in the parameters' dtype."""
        b, t, H = x.shape
        x = x.astype(self.qkv._value.dtype)
        qkv = jnp.dot(x, self.qkv._value, preferred_element_type=F32)
        q = _rms(qkv[..., :H], self.q_norm._value, self.eps)
        k = _rms(qkv[..., H:2 * H], self.k_norm._value, self.eps)
        return tuple(t_.reshape(b, t, self.heads, self.head_dim)
                     .astype(x.dtype) for t_ in (q, k, qkv[..., 2 * H:]))

    def mix(self, q, k, v, cache, rows):
        """Across the rows: keys and values written, the attention ->
        ((out [b, s, H],), new cache or None). A chunk attends within
        itself, `rows.live` blocks of queries of it (None: all)."""
        b, s, h, d = q.shape
        scale = d ** -0.5
        if cache is not None:
            from ...nn.kv_pool import paged_write_attend, write_kv
            lens = jnp.asarray(cache.lengths, jnp.int32)
            k, v = k.astype(cache.k.dtype), v.astype(cache.v.dtype)
        if s > 1 and cache is not None:
            cache = cache._replace(
                k=write_kv(cache.k, cache.block_tables, lens, k),
                v=write_kv(cache.v, cache.block_tables, lens, v),
                lengths=lens + jnp.int32(s))
        if s > 1 or cache is None:   # a prefill starts an empty slot
            out = _chunk_attention(q.astype(k.dtype), k, v, rows.live,
                                   scale=scale, q_block=rows.tile)
        else:       # a token a slot: written and attended by one entry
            out, kc, vc = paged_write_attend(
                jnp.swapaxes(q, 1, 2), cache.k, cache.v,
                cache.block_tables, lens, k, v, scale)
            out = jnp.swapaxes(out, 1, 2)
            cache = cache._replace(k=kc, v=vc, lengths=lens + jnp.int32(s))
        return (out.reshape(b, s, h * d).astype(q.dtype),), cache

    def output(self, out):
        return jnp.dot(out, self.o._value, preferred_element_type=F32)


class OlmoHybridBlock(_Weights):
    def __init__(self, cfg: OlmoHybridConfig, kind):
        super().__init__(cfg)
        if kind not in (LINEAR, FULL):
            raise ValueError(f"unknown layer type {kind!r}")
        self.kind, self.eps = kind, cfg.rms_norm_eps
        self.mixer = LinearAttention(cfg) if kind == LINEAR \
            else FullAttention(cfg)
        self.mixer_norm = self.ones(cfg.hidden_size)
        self.ffn = DenseFFN(cfg)
        self.ffn_norm = self.ones(cfg.hidden_size)

    def forward(self, x, rope, cache, rows):
        """x [b, s, H] float32: the residual stream, and each sublayer's
        output up to its norm, stay in float32 (a few MB); what a matrix
        multiplies is rounded to the parameters' dtype. No rotary: `rope`
        is None. `rows.live`: the tiles of rows that hold a token
        (`_live_rows`), None for all. -> (y, (new cache,), ())."""
        dtype = self.ffn.gate._value.dtype
        mixer = self.mixer
        live, tile = rows.live, rows.tile
        word = "linear_attn" if self.kind == LINEAR else "attn"
        with jax.named_scope(word):
            mixed, cache = mixer.mix(
                *_live_rows(mixer.project, live, tile, x), cache, rows)

        def rest(x, *mixed):
            with jax.named_scope(word):
                h = x + _rms(mixer.output(*mixed), self.mixer_norm._value,
                             self.eps)
            with jax.named_scope("ffn"):
                f = _swiglu(h.astype(dtype), self.ffn.gate._value,
                            self.ffn.up._value, self.ffn.down._value)
                return (h + _rms(f, self.ffn_norm._value, self.eps),)

        return _live_rows(rest, live, tile, x, *mixed)[0], (cache,), ()


class OlmoHybrid(PagedDecoder):
    SERVE_STATS = LINEAR_STATS
    # rows of one step of a prefill's row-wise work: a bucket of up to 4096
    # rows holds a prompt of any length over its half, and what the rows
    # past the prompt compute is thrown away. Every bucket of more than a
    # tile is cut (ROADMAP S13 iv prices the latent nets' rule here)
    PREFILL_TILE = 512
    WHOLE_TILES = 1

    def __init__(self, config: OlmoHybridConfig = None):
        cfg = config or OlmoHybridConfig()
        super().__init__(
            cfg, lambda i: OlmoHybridBlock(cfg, cfg.layer_types[i]))

    def paged_cache_spec(self):
        """One `CacheSpec` a layer, by its kind: a full layer pages keys
        and values by token (`PagedKVCache`), a linear layer keeps one
        state and the convolution's inputs a slot (`SlotStateCache`)."""
        from ...nn.kv_pool import CacheSpec, PagedKVCache, SlotStateCache
        cfg = self.config
        per_head = (cfg.num_heads, cfg.hidden_size // cfg.num_heads)
        return [CacheSpec(SlotStateCache, (), blk.mixer.state_spec())
                if blk.kind == LINEAR
                else CacheSpec(PagedKVCache, (per_head, per_head))
                for blk in self.blocks]

    def _embed(self, ids, pos):
        return jnp.take(self.embed._value, ids, axis=0).astype(F32), None

    def _counted(self, caches, rows):
        """[tokens a row of the program, linear layers] i32."""
        n_linear = sum(blk.kind == LINEAR for blk in self.blocks)
        return (jnp.asarray([rows.valid.shape[1], n_linear], jnp.int32),)

    def serve_counters(self, kind, counted, n_tokens):
        width, layers = (int(x) for x in np.asarray(counted[0]))
        if kind == "decode":
            return {"linear_decode_layer_steps": layers}
        return {"linear_prefill_tokens": int(n_tokens),
                "linear_prefill_pad_tokens": width - int(n_tokens)}
