"""What a served decoder is: the scaffold `PagedDecoder` (embedding, a
stack of blocks, norm, an untied head, and the net's side of
`inference/serving.ServeLoop`'s protocol) and the parts the families'
blocks are made of. The families (`kimi_k2`, `longcat_flash`, `laguna`,
`mimo_v2`, `olmo_hybrid`) bring a configuration, their blocks, a cache
spec and the hooks `PagedDecoder` names. `gpt.GPT` is not under it: it
trains under the tape, in the `nn.Layer` / `Tensor` idiom (ROADMAP D3).
Inference only: the forward passes are array code under no tape.
"""
from __future__ import annotations

import math
import typing

import jax
import jax.numpy as jnp

from ... import nn
from ...nn import initializer as I
from ...nn.layer.experts import _swiglu

__all__ = ["PagedDecoder", "Rows", "MOE_STATS", "moe_counters",
           "yarn_inv_freq", "yarn_mscale"]

FULL = "full_attention"     # a layer type of the window nets and the hybrid
F32 = jnp.float32

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


def _rotary_tables(pos, inv_freq, factor):
    """cos and sin [..., 2 len(inv_freq)] of the positions `pos` for
    `_rope`'s half-split pairing, both times `factor`."""
    ang = pos.astype(F32)[..., None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def _tile_of(bucket, tile):
    """`tile` where a prefill over `bucket` rows works tile by tile, None
    where it runs whole: a bucket no larger than a tile, or no multiple."""
    return tile if bucket > tile and bucket % tile == 0 else None


def _live_rows(fn, live, tile, *xs):
    """`fn` over `xs` ([b, s, ...] each; `fn` works row by row and returns
    a tuple of [b, rows, ...]). `live` None: all of it at once. Else only
    the first `live` (a traced count) tiles of `tile` rows are computed,
    a tile a loop step; the rows of the others come out zero."""
    if live is None:
        return fn(*xs)
    s = xs[0].shape[1]
    like = jax.eval_shape(fn, *(x[:, :tile] for x in xs))
    outs = tuple(jnp.zeros((y.shape[0], s) + y.shape[2:], y.dtype)
                 for y in like)

    def one_tile(i, outs):
        ys = fn(*(jax.lax.dynamic_slice_in_dim(x, i * tile, tile, axis=1)
                  for x in xs))
        return tuple(jax.lax.dynamic_update_slice_in_dim(o, y, i * tile,
                                                         axis=1)
                     for o, y in zip(outs, ys))

    return jax.lax.fori_loop(0, live, one_tile, outs)


# rows of one step of a bucketed prefill's row-wise work, and of one tile
# of queries in its attention: a bucket holds a prompt of any length over
# its half, and what the rows past the prompt compute is thrown away
PREFILL_TILE = 256


class Rows(typing.NamedTuple):
    """What one pass knows of its slots and rows; `PagedDecoder` makes it
    and hands it to every block. The defaults are a pass without a cache:
    every row a token, all the rows at once."""

    lens: object = None    # [b] i32: tokens each slot had cached
    owned: object = None   # [b] bool: slots a request owns (`SLOT_COUNTS`)
    valid: object = None   # [b, s] bool: rows that hold a token
    last: object = None    # [b] i32: each prompt's last row (a prefill)
    live: object = None    # traced count of tiles that hold a token
    tile: int = PREFILL_TILE   # rows a tile (`_live_rows`, the queries' too)


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


def _ids(input_ids):
    from ...core.tensor import Tensor
    return input_ids._value if isinstance(input_ids, Tensor) \
        else jnp.asarray(input_ids)


class PagedDecoder(_Weights):
    """A decoder behind `ServeLoop`: `embed`, `blocks` (`block(i)` makes
    layer i), `norm` and an untied `head`, created in that order. The
    configuration gives `vocab_size`, `hidden_size`, `num_layers`,
    `rms_norm_eps`, `max_seq_len`.

    What `ServeLoop` asks of a net, all of it here:
    - `paged_cache_spec()`: the `CacheSpec`s, `LAYER_CACHES` a layer in
      layer order (the family's);
    - `_forward_paged(ids, caches, last_index=None)` -> (logits [b, V]
      float32, new caches in spec order, what the layers counted, what
      `_counted` adds): one token a slot (a decode step), or with
      `last_index` [b] prompts padded to a bucket, each starting an EMPTY
      slot and ending at its `last_index`;
    - `prefill_tile(bucket)`: the tile `_forward_paged` cuts that bucket
      into, None where it runs whole (`ServeLoop` counts the rows computed
      by it);
    - `SERVE_STATS`, the `ServeLoop.stats()` names under which
      `serve_counters` reports what `_forward_paged` counted, and
      `SERVE_GAUGES`, those of them that are set, not added up.

    A family's hooks: `_embed`, `_counted`, and the attributes below. Its
    blocks are called `block(x, rope, *the layer's caches, rows=Rows)` ->
    (y, the new caches, what the layer counted: a tuple as long in every
    layer, None where this layer has no such count)."""

    SERVE_STATS = ()
    SERVE_GAUGES = ()
    LAYER_CACHES = 1
    # the rows of a tile, and the tiles up to which a bucket runs whole: a
    # bucket of two is the smallest that holds its prompt, so both are
    # live and a loop only fetches every weight twice (PERF.md section 6)
    PREFILL_TILE = PREFILL_TILE
    WHOLE_TILES = 2
    # True: `_counted` reads the slots, `Rows.owned` [b] is made and
    # `valid` spread from it. One program once XLA has run, two StableHLO
    # texts: kept apart so that each family's stays what it was (D3)
    SLOT_COUNTS = False

    def __init__(self, cfg, block):
        super().__init__(cfg)
        self.config = cfg
        self.embed = self.matrix(cfg.vocab_size, cfg.hidden_size)
        self.blocks = nn.LayerList(
            [block(i) for i in range(cfg.num_layers)])
        self.norm = self.ones(cfg.hidden_size)
        self.head = self.matrix(cfg.hidden_size, cfg.vocab_size)

    def paged_cache_spec(self):
        raise NotImplementedError

    def serve_counters(self, kind, counted, n_tokens):
        """{`ServeLoop.stats()` name: increment, or for a name in
        `SERVE_GAUGES` the value} for one settled serve program (`kind`
        "decode" or "prefill") that ran `n_tokens` live tokens; `counted`
        is what `_forward_paged` returned past its caches."""
        return {}

    def _embed(self, ids, pos):
        """Under the `embed` scope -> (x [b, s, H], the residual stream
        in the dtype the family keeps it in; every block's `rope`)."""
        raise NotImplementedError

    def _counted(self, caches, rows):
        """What a pass counts beside its layers, from the caches as they
        came in: a tuple of arrays, returned after the layers' as int32."""
        return ()

    def prefill_tile(self, bucket):
        tile = self.PREFILL_TILE
        return _tile_of(bucket, tile) \
            if bucket > self.WHOLE_TILES * tile else None

    def _blocks(self, ids, pos, caches, rows):
        """The stack. `caches`: in spec order, None for a pass without a
        cache. -> (x, new caches in spec order, what the layers counted:
        entry j is every layer's j-th count stacked over the layers that
        have one, [0, 0] i32 where none has)."""
        with jax.named_scope("embed"):
            x, rope = self._embed(ids, pos)
        k = self.LAYER_CACHES
        caches = caches or [None] * (k * len(self.blocks))
        new_caches, counted = [], []
        for i, blk in enumerate(self.blocks):
            with jax.named_scope(f"layer{i}"):
                x, new, n = blk(x, rope, *caches[k * i:k * (i + 1)],
                                rows=rows)
            new_caches += new
            counted.append(n)

        def stacked(counts):
            counts = [n for n in counts if n is not None]
            return jnp.stack(counts) if counts \
                else jnp.zeros((0, 0), jnp.int32)
        return x, new_caches, tuple(stacked(c) for c in zip(*counted))

    def _logits(self, h):
        with jax.named_scope("head"):
            h = _rms(h, self.norm._value, self.config.rms_norm_eps)
            return jnp.dot(h.astype(self.head._value.dtype),
                           self.head._value, preferred_element_type=F32)

    def forward(self, input_ids):
        """Logits [b, s, vocab] (float32) of a whole sequence, no cache."""
        from ...core import tape
        from ...core.tensor import Tensor
        ids = _ids(input_ids)
        with tape.no_grad():
            pos = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
            x, *_ = self._blocks(ids.astype(jnp.int32), pos, None,
                                 Rows(tile=self.PREFILL_TILE))
            return Tensor(self._logits(x), _internal=True)

    def _forward_paged(self, input_ids, caches, last_index=None):
        """One paged prefill/decode pass (the class docstring). Rows that
        no request owns (a slot whose table starts at the trash block, a
        prompt's padding past `last_index`) are not `valid`: what they
        cache goes to the trash block or to their own slot's row, which
        nobody reads, they are routed to no expert and leave every state
        as it was. A bucket that `prefill_tile` cuts computes the tiles up
        to the last prompt's end and leaves the others' rows zero."""
        from ...nn.kv_pool import TRASH_BLOCK
        ids = _ids(input_ids)
        b, s = ids.shape
        lens = jnp.asarray(caches[0].lengths, jnp.int32)
        step = jnp.arange(s, dtype=jnp.int32)[None]
        table = caches[0].block_tables
        owned = table[:, 0] != TRASH_BLOCK if self.SLOT_COUNTS else None
        valid = jnp.broadcast_to(
            table[:, :1] != TRASH_BLOCK if owned is None
            else owned[:, None], (b, s))
        last = live = None
        if last_index is not None:
            last = jnp.asarray(last_index, jnp.int32).reshape(-1)
            valid = valid & (step <= last[:, None])
            tile = self.prefill_tile(s)
            if tile:
                live = jnp.max(last) // tile + 1
        rows = Rows(lens, owned, valid, last, live, self.PREFILL_TILE)
        x, new_caches, counted = self._blocks(
            ids.astype(jnp.int32), lens[:, None] + step, caches, rows)
        h = x[:, -1] if last is None else jnp.take_along_axis(
            x, last[:, None, None], axis=1)[:, 0]
        more = self._counted(caches, rows)
        return (self._logits(h), new_caches, *counted,
                *(c.astype(jnp.int32) for c in more))
