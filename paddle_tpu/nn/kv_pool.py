"""Paged KV-cache pool — the serving tier's shared decode cache.

`StaticKVCache` (nn/layer/transformer.py) preallocates a private
[b, h, max_seq_len, d] slab per batch row. That is the right shape for
ONE generate() call, and exactly the wrong shape for a serve loop:
requests arrive with different lengths, finish at different times, and a
fixed-batch slab burns max_seq_len slots of HBM per row whether the row
holds a 2000-token context or an idle slot. This module is the vLLM-style
fix, TPU-native:

- **arena**: one physical [n_blocks + 1, h, d, block_size] buffer per
  layer per k/v (`PagedKVCache`). Physical block 0 is RESERVED as the
  trash block — writes from masked/inactive rows and table entries past a
  request's allocation all land there, so the kernel's index maps never
  need a branch;
- **block table**: each request maps logical block j -> physical row
  `block_tables[i, j]`; unallocated entries are 0 (trash) by contract;
- **free list**: `KVBlockPool` hands physical blocks out and takes them
  back the moment a request retires — the pool is the serving tier's
  admission currency (inference/serving.py blocks admissions on it).

Attention over the paged layout dispatches to the block-table Pallas
kernel (ops/pallas/decode_attention.paged_decode_attention — lengths AND
block tables ride the scalar-prefetch path, so per-step KV bytes scale
with live blocks, not max_seq_len) behind the same counted gate as every
other kernel; `paged_attention_ref` is the jnp path the gate rejects onto
and the parity oracle.

Why tokens are the arena's MINOR dimension. Three components meet on one
buffer, and each has a physical layout it insists on: the pool's array
gets XLA's default TPU layout, which puts whichever of the two minor
dimensions fills the 128 lanes there; the Mosaic kernel takes its operands
row-major; an XLA scatter wants the scattered dimensions major. With
[.., block_size, d] and d = 64 those were three different layouts, and
every serve program copied every arena three times (PR 26: 76 % of a
decode beat). [.., d, block_size] with block_size a multiple of 128 is
row-major by default, is what the kernel asks for, and `write_kv` rewrites
whole blocks of it in place — never a scatter — so a compiled serve
program holds no copy and no temp of arena size
(tests/test_chip_smoke.py compiles for v5e and asserts it). The shape is
private to this module and the kernels: everything else goes through
`KVBlockPool.arenas`, `PagedKVCache`, `write_kv` and `paged_attention`.
"""
from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["PagedKVCache", "PagedLatentCache", "SlotStateCache",
           "WindowKVCache", "CacheSpec", "KVBlockPool", "paged_caches",
           "cache_arenas", "fresh_slot_rows", "put_slot_rows",
           "paged_attention", "paged_attention_ref", "paged_write_attend",
           "latent_paged_attention", "write_kv", "window_ring_shape",
           "window_fill", "window_write", "window_attention",
           "pick_block_size"]

TRASH_BLOCK = 0  # physical row 0 of every arena; never allocated


class PagedKVCache(typing.NamedTuple):
    """One layer's paged decode cache. `k`/`v` are the physical arenas
    [n_blocks + 1, h, d, block_size] (row 0 = trash; `v` may be its own
    depth, as its `CacheSpec` entry says); `block_tables`
    [b, max_blocks] i32 maps each request-slot's logical blocks to
    physical rows (unallocated entries 0); `lengths` [b] i32 counts the
    tokens already written per slot. A pytree — jit/scan-able, and the
    block_tables/lengths leaves are shared by reference across layers."""

    k: object             # [n_blocks + 1, h, d, block_size]
    v: object             # [n_blocks + 1, h, d_v, block_size]
    block_tables: object  # [b, max_blocks] i32
    lengths: object       # [b] i32

    @property
    def block_size(self):
        return int(self.k.shape[3])


class PagedLatentCache(typing.NamedTuple):
    """One layer's paged cache of ONE vector a token: a latent-attention
    (MLA) layer caches the compressed key-value and the rotated key side
    by side, `dim` = 512 + 64 wide, shared by every query head. `kv` is
    the arena [n_blocks + 1, 1, dim, block_size] — the pool's layout
    with one head, written by `write_kv` like any other arena."""

    kv: object            # [n_blocks + 1, 1, dim, block_size]
    block_tables: object  # [b, max_blocks] i32
    lengths: object       # [b] i32

    @property
    def block_size(self):
        return int(self.kv.shape[3])


class SlotStateCache(typing.NamedTuple):
    """One layer's state that is NOT paged by token: a recurrent
    (linear-attention) layer keeps one matrix a decode slot and the last
    inputs of its short convolution, whatever the length of the stream.
    `state` [slots, dk, heads * dv] float32 (what the recurrence
    accumulates in; the layout is ops/pallas/gated_delta.py's), `conv`
    [slots, taps - 1, channels] in the activations' dtype. Row i belongs to
    decode slot i from admission to retirement: no block table, no
    growth, no free list. `block_tables` and `lengths` ride along as in
    the paged caches (a layer reads which rows are live from them)."""

    state: object         # [slots, dk, heads * dv] float32
    conv: object          # [slots, taps - 1, channels]
    block_tables: object  # [b, max_blocks] i32
    lengths: object       # [b] i32


class WindowKVCache(typing.NamedTuple):
    """One sliding-window layer's cache: the keys and values of the last
    `window` tokens of each decode slot and nothing else, whatever the
    stream's length. A RING a slot, not pages of the pool: `k`/`v` are
    [slots, ring_blocks, h, d, block] (`v` its own depth d_v where a net's
    values are narrower than its keys) — `ring_blocks * block` = window
    tokens in the lanes, a slot's row laid out as `ring_blocks` rows of
    an arena, so that [slots * ring_blocks, h, d, block] (a reshape of
    the leading dimensions, no copy) IS an arena and slot i's ring its
    blocks i * ring_blocks .. under a table of its own
    (`_ring_tables`). The token at position p sits at ring column
    p mod window; keys are rotated before they are cached, so the order
    of a ring's columns does not matter to the softmax. Three rules:
    - it does not grow: the bytes are `slots * window` tokens' from the
      first admission to the last, and `KVBlockPool` hands out no block
      for it (`blocks_for`, admission and the used share count what is
      paged by token);
    - row i belongs to decode slot i from admission to retirement: a
      prefill starts from one zeroed row (`fresh_slot_rows`) and leaves
      the prompt's last `window` tokens in it (`window_fill`);
    - a decode row that no request owns writes into its OWN slot's ring,
      which nobody reads and the next admission replaces: the trash
      block's rule without a trash block.
    `block_tables` and `lengths` ride along as in the paged caches
    (`lengths` is the stream's, not the ring's)."""

    k: object             # [slots, ring_blocks, h, d, block]
    v: object             # [slots, ring_blocks, h, d_v, block]
    block_tables: object  # [b, max_blocks] i32 (the paged layers')
    lengths: object       # [b] i32


class CacheSpec(typing.NamedTuple):
    """What one layer of a served net caches, as the net's
    `paged_cache_spec()` says it: `cache`, the type its `_forward_paged`
    reads (the arenas in order, then the per-slot arrays, then
    `block_tables`, `lengths`); `arenas`, the (heads, dim) of each arena
    paged by token — ((h, d), (h, d)) for per-head keys and values,
    ((1, dim),) for a latent, () for a layer that caches no token; and
    `slots`, the (shape of one slot's row, dtype or None for the pool's)
    of each array indexed by decode slot — a `SlotStateCache`'s state and
    convolution inputs, a `WindowKVCache`'s two rings
    (`window_ring_shape`)."""

    cache: type
    arenas: tuple
    slots: tuple = ()


def paged_caches(spec, arenas, block_tables, lengths):
    """[(arena, ...) per layer] -> one cache per layer, of the type the
    layer's `CacheSpec` names, over shared slot state. The inverse is
    `cache_arenas`."""
    return [layer.cache(*a, block_tables, lengths)
            for layer, a in zip(spec, arenas)]


def cache_arenas(caches):
    """The arenas of each layer's cache, as `KVBlockPool.arenas_for`
    lays them out (and as a serve program donates them)."""
    return [tuple(c[:-2]) for c in caches]


def fresh_slot_rows(spec, arenas):
    """What a prefill of ONE slot is given: the arenas as they are, each
    per-slot array as one zeroed row [1, ...] — an admitted request
    starts from zero state, whatever the slot's last owner left."""
    return [a[:len(layer.arenas)]
            + tuple(jnp.zeros((1,) + x.shape[1:], x.dtype)
                    for x in a[len(layer.arenas):])
            for layer, a in zip(spec, arenas)]


def put_slot_rows(spec, arenas, written, slot):
    """The inverse, after the prefill: `written`'s arenas, and its rows
    [1, ...] put into `arenas`' per-slot arrays at row `slot`."""
    return [w[:len(layer.arenas)]
            + tuple(jax.lax.dynamic_update_slice_in_dim(x, row, slot, 0)
                    for x, row in zip(a[len(layer.arenas):],
                                      w[len(layer.arenas):]))
            for layer, a, w in zip(spec, arenas, written)]


def pick_block_size(max_seq_len, heads, head_dim, dtype="float32",
                    batch=1):
    """Pool block size = the paged kernel's KV block: FLAGS_serve_block_size
    override, else the decode-attention autotune table (measured on TPU,
    disk-cached — same (kernel, shape-bucket, dtype) key family as the
    contiguous kernel), else the 128-column heuristic clamped to the
    sequence budget. Always a multiple of 8; the table offers only lane
    multiples (128, 256), which is where the arena is copy-free."""
    from ..core import flags as _flags
    from ..ops.pallas import autotune
    from ..ops.pallas.flash_attention import _ceil_to, _pick_block
    L = _ceil_to(max(int(max_seq_len), 8), 8)
    cfg = int(_flags.flag("FLAGS_serve_block_size") or 0)
    if cfg:
        if cfg % 8 != 0:
            raise ValueError(
                f"FLAGS_serve_block_size={cfg} must be a multiple of 8")
        return cfg
    default = _pick_block(L, 128) or 8

    def measure(params):
        (bs_,) = params
        nb = max(L // bs_, 1)
        h, d = int(heads), int(head_dim)
        ka = jnp.zeros(KVBlockPool(nb, bs_).arena_shape(h, d), dtype)
        q = jnp.zeros((batch, h, 8, d), dtype)
        bt = jnp.tile(jnp.arange(1, nb + 1, dtype=jnp.int32), (batch, 1))
        lens = jnp.full((batch,), nb * bs_ - 8, jnp.int32)
        from ..ops.pallas.decode_attention import paged_decode_attention
        fn = jax.jit(paged_decode_attention)
        return autotune.time_thunk(lambda: fn(q, ka, ka, bt, lens))

    cands = [(x,) for x in (256, 128) if L % x == 0]
    if len(cands) <= 1:
        return default
    return autotune.lookup(
        "paged_decode_attention", (autotune.bucket(L), int(head_dim)),
        str(jnp.dtype(dtype)), cands, measure, (default,))[0]


class KVBlockPool:
    """Host-side free-list over the physical arena rows. NOT thread-safe:
    the serve loop owns it from one scheduler thread. Block ids are 1-based
    (0 is the trash block)."""

    def __init__(self, n_blocks, block_size):
        if n_blocks < 1:
            raise ValueError("KVBlockPool needs at least one block")
        if block_size < 8 or block_size % 8 != 0:
            raise ValueError(
                f"block_size {block_size} must be a multiple of the 8-row "
                "sublane tile")
        self.n_blocks = int(n_blocks)
        self.block_size = int(block_size)
        # LIFO free list: a just-freed block is hot in whatever cache
        # hierarchy the arena write path touches next
        self._free = list(range(self.n_blocks, 0, -1))

    @property
    def free_blocks(self):
        return len(self._free)

    @property
    def used_blocks(self):
        return self.n_blocks - len(self._free)

    def blocks_for(self, n_tokens):
        """Blocks needed to hold n_tokens."""
        return max(0, -(-int(n_tokens) // self.block_size))

    def can_alloc(self, n):
        return len(self._free) >= int(n)

    def alloc(self, n):
        """Pop n physical block ids; returns None (and takes nothing)
        when the pool can't satisfy the whole request — allocation is
        all-or-nothing so a failed admission never leaks blocks."""
        n = int(n)
        if n < 0 or len(self._free) < n:
            return None
        out = [self._free.pop() for _ in range(n)]
        return out

    def free(self, blocks):
        for b in blocks:
            b = int(b)
            if b < 1 or b > self.n_blocks:
                raise ValueError(f"free of invalid block id {b}")
            if b in self._free:  # double-free is a scheduler bug
                raise ValueError(f"double free of block {b}")
            self._free.append(b)

    def arena_shape(self, heads, head_dim):
        """[n_blocks + 1, h, d, block_size]: tokens in the lanes (see
        the module docstring)."""
        return (self.n_blocks + 1, int(heads), int(head_dim),
                self.block_size)

    def arenas_for(self, spec, dtype=jnp.float32, slots=0):
        """Fresh zeroed device state for a net's `paged_cache_spec()`
        (one `CacheSpec` a layer): [(arena, ..., per-slot array, ...),
        ...], each arena `arena_shape` (row 0 = trash), each per-slot
        array [`slots`, *row shape] (the decode slots of the loop that
        asks). Zeros, not empty: a fresh pool must attend to nothing."""
        return [tuple(jnp.zeros(self.arena_shape(h, d), dtype)
                      for h, d in layer.arenas)
                + tuple(jnp.zeros((int(slots),) + tuple(shape),
                                  own or dtype)
                        for shape, own in layer.slots) for layer in spec]

    def arenas(self, layers, heads, head_dim, dtype=jnp.float32):
        """`arenas_for` k/v pairs of one shape: [(k, v), ...]."""
        return self.arenas_for(
            [CacheSpec(PagedKVCache, ((heads, head_dim),) * 2)]
            * int(layers), dtype)


# --------------------------------------------------------------------------
# functional pieces used inside jitted serve steps
# --------------------------------------------------------------------------

def write_kv(arena, block_tables, lengths, new_kv):
    """Write a chunk's k (or v) into the paged arena, in place. `new_kv`
    is [b, s, h, d] — the s new tokens per slot land at logical positions
    lengths[i]..lengths[i]+s-1. Positions past a slot's table (or rows
    the scheduler parked with an all-zero table) redirect to the trash
    block, so masked/padded rows can never corrupt another request.
    `paged_attention` (and `latent_paged_attention`, `window_attention`)
    attend over what this leaves: write first, then attend. The one
    caller that does not come here for its decode step is
    `paged_write_attend`, where the multi-head kernel writes the token
    itself.

    The arena is only ever updated a whole [1, h, d, block_size] block
    at a time, in place (an XLA scatter, or an update one token wide,
    asks for a layout of its own and copies the arena to get it). s == 1,
    the decode step, goes to the Pallas writer when its gate admits
    (ops/pallas/decode_attention.paged_write_token). Everything else is
    a loop over (slot, block the chunk reaches — at most `touched` per
    slot): read the block, take the chunk's tokens where they fall in
    it, keep its own contents elsewhere, `dynamic_update_slice` it back.
    A loop, not unrolled: a serve program holds two writes per layer."""
    bt = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    if new_kv.shape[1] == 1 and \
            _write_kernel_eligible(arena, new_kv.shape[0]):
        return _write_token(arena, bt, lens, new_kv)
    return _write_blocks(arena, bt, lens, new_kv)


def _phys_row(bt, i, blk):
    """Physical row of slot i's logical block `blk`: the trash block
    past the table."""
    nb = bt.shape[1]
    return jnp.where(blk < nb, bt[i, jnp.minimum(blk, nb - 1)],
                     jnp.int32(TRASH_BLOCK))


def _tokens_to_lanes(arena, new_kv):
    """[b, s, h, d] -> [b, h, d, s] in the arena's dtype."""
    return jnp.transpose(new_kv.astype(arena.dtype), (0, 2, 3, 1))


def _token_lanes(arena, new_kv):
    """A decode step's tokens [b, 1, h, d] as the kernels take them:
    dense, [h, d, b] with the slots in the lanes, in the arena's dtype.
    One small transpose (a [b, h, d, 1] operand, one element a 128-lane
    row, cost a relayout of 128 x the tokens' bytes before every call;
    PR 34)."""
    return jnp.transpose(new_kv[:, 0].astype(arena.dtype), (1, 2, 0))


def _write_token(arena, bt, lens, new_kv):
    """The decode step's write through the Pallas writer, its tokens
    dense (`_token_lanes`)."""
    from ..core import monitor
    from ..ops.pallas import run_guarded
    from ..ops.pallas.decode_attention import (paged_write_cut,
                                               paged_write_token)
    b, bs = new_kv.shape[0], arena.shape[3]
    slots = jnp.arange(b, dtype=jnp.int32)
    tokens = _token_lanes(arena, new_kv)
    # what a call moves, on the writer's span and as gauges per slot
    # count for a dump to read (b32 is a decode step of 32 slots)
    cut = paged_write_cut(tuple(arena.shape), b, arena.dtype.itemsize)
    monitor.stat_set_many({f"pallas.paged_write_token.{name}.b{b}": value
                           for name, value in cut.items()})
    return run_guarded(
        "paged_write_token",
        lambda: paged_write_token(arena, _phys_row(bt, slots, lens // bs),
                                  lens % bs, tokens),
        **cut)


# jitted, like the kernels' calls in ops/pallas/decode_attention.py, so
# that the layers of a serve program trace and lower one loop, not 96
@jax.jit
def _write_blocks(arena, bt, lens, new_kv):
    b, s, h, d = new_kv.shape
    bs = arena.shape[3]
    zero = jnp.int32(0)
    # a block of margin on either side, so that any block's window
    # [blk*bs - lengths[i], +bs) of the chunk is a plain slice
    chunk = jnp.pad(_tokens_to_lanes(arena, new_kv),
                    ((0, 0), (0, 0), (0, 0), (bs, bs)))
    touched = (s + bs - 2) // bs + 1   # blocks a run of s tokens can reach
    lane = jnp.arange(bs, dtype=jnp.int32)

    def write_block(n, a):
        n = jnp.asarray(n, jnp.int32)  # the loop counts in int64 under x64
        i, j = n // touched, n % touched
        start = lens[i]
        blk = start // bs + j
        row = _phys_row(bt, i, blk)
        first = blk * bs - start       # chunk index of the block's lane 0
        new = jax.lax.dynamic_slice(
            chunk, (i, zero, zero, jnp.clip(first, -bs, s) + bs),
            (1, h, d, bs))
        old = jax.lax.dynamic_slice(a, (row, zero, zero, zero),
                                    (1, h, d, bs))
        tok = first + lane
        return jax.lax.dynamic_update_slice(
            a, jnp.where((tok >= 0) & (tok < s), new, old),
            (row, zero, zero, zero))

    return jax.lax.fori_loop(0, b * touched, write_block, arena)


def paged_attention_ref(q, k_arena, v_arena, block_tables, lengths,
                        scale, sinks=None):
    """jnp fallback / parity oracle: gather each slot's blocks into a
    contiguous [b, h_kv, max_blocks*bs, d] view and run the same masked
    softmax as _static_cache_attention, with per-row live lengths. Row r
    of slot i attends logical cols <= lengths[i] + r. q [b, h, s, d] with
    h = G x h_kv: query head j reads key-value head j // G (the G query
    heads of a key-value head are further rows of its product, each at
    its own position). The values may be narrower than the keys (the V
    arena's own depth d_v: -> [b, h, s, d_v]). `sinks` [h] float32: query
    head j's sink logit is one more term of its softmax's denominator, in
    float32, and adds no value."""
    b, h, s, d = q.shape
    hk, bs = k_arena.shape[1], k_arena.shape[3]
    group = h // hk
    bt = jnp.asarray(block_tables, jnp.int32)
    nb = bt.shape[1]
    L = nb * bs

    def gather(arena):
        g = jnp.take(arena, bt, axis=0)          # [b, nb, hk, d, bs]
        return jnp.transpose(g, (0, 2, 1, 4, 3)).reshape(
            b, hk, L, arena.shape[2])

    kc, vc = gather(k_arena), gather(v_arena)
    lens = jnp.asarray(lengths, jnp.int32)
    step = jnp.tile(jnp.arange(s, dtype=jnp.int32), group)        # [G s]
    row = lens[:, None] + step[None]                              # [b, G s]
    col = jnp.arange(L, dtype=jnp.int32)                          # [L]
    live = col[None, None, :] <= row[:, :, None]                  # [b, G s, L]
    scores = jnp.einsum("bhsd,bhld->bhsl",
                        q.reshape(b, hk, group * s, d).astype(kc.dtype), kc,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(live[:, None], scores, -1e9)
    if sinks is None:
        p = jax.nn.softmax(scores, axis=-1)
    else:       # the sink: a last column of the softmax, then dropped
        sink = jnp.repeat(jnp.asarray(sinks, jnp.float32).reshape(hk, group),
                          s, axis=1)                              # [hk, G s]
        p = jax.nn.softmax(jnp.concatenate(
            [scores, jnp.broadcast_to(sink[None, :, :, None],
                                      scores.shape[:3] + (1,))],
            axis=-1), axis=-1)[..., :-1]
    return jnp.einsum("bhsl,bhld->bhsd", p.astype(vc.dtype), vc) \
        .astype(q.dtype).reshape(b, h, s, vc.shape[-1])


def latent_paged_attention(q, arena, block_tables, lengths, scale,
                           value_dim):
    """Attention of q [b, h, s, dim] — every head over the ONE cached
    vector a token of a latent arena [n, 1, dim, bs]: keys are the whole
    `dim`-wide rows, values their first `value_dim` entries (MLA's
    absorbed form: the key and value projections live in q and in what
    is done with the result). Row r of slot i attends logical cols
    <= lengths[i] + r. Returns [b, h, s, value_dim] in q's dtype.

    Gated like `paged_attention`: one query row a slot over a lane-tiled
    arena, the decode step, goes to the Pallas latent kernel
    (ops/pallas/decode_attention.latent_paged_decode_attention: a slot's
    live blocks read once, its heads the rows of one product a block);
    everything else, and every gate rejection, to `_latent_attn_paged`,
    the plain-XLA form and the kernel's parity oracle."""
    if _latent_kernel_eligible(q, arena, value_dim):
        from ..core import monitor
        from ..ops.pallas import run_guarded
        from ..ops.pallas.decode_attention import (
            latent_paged_cut, latent_paged_decode_attention)
        # the cut this program compiles with, on the kernel's span and as
        # gauges per slot count (b64 is a decode step of 64 slots)
        cut = latent_paged_cut(tuple(q.shape), tuple(arena.shape),
                               block_tables.shape[1], arena.dtype.itemsize,
                               value_dim)
        monitor.stat_set_many({
            f"pallas.latent_paged_attention.{name}.b{q.shape[0]}": value
            for name, value in cut.items()})
        return run_guarded(
            "latent_paged_attention",
            lambda: latent_paged_decode_attention(
                q, arena, block_tables, lengths, scale, value_dim),
            **cut)
    return _latent_attn_paged(
        q, arena, jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(lengths, jnp.int32), scale=float(scale),
        value_dim=int(value_dim))


# jitted under a name of its own, so that a device trace can tell its
# fusions from the rest of a serve program
@functools.partial(jax.jit, static_argnames=("scale", "value_dim"))
def _latent_attn_paged(q, arena, bt, lens, *, scale, value_dim):
    """Plain XLA: gather the slot's blocks, two einsums with the tokens
    left in the lanes, softmax in float32 over the whole table width."""
    b, h, s, d = q.shape
    bs, nb = arena.shape[3], bt.shape[1]
    g = jnp.take(arena[:, 0], bt, axis=0)                 # [b, nb, d, bs]
    scores = jnp.einsum("bhsd,bndt->bhsnt", q.astype(g.dtype), g,
                        preferred_element_type=jnp.float32) * scale
    col = jnp.arange(nb * bs, dtype=jnp.int32).reshape(nb, bs)
    row = lens[:, None] + jnp.arange(s, dtype=jnp.int32)[None]    # [b, s]
    live = col[None, None] <= row[:, :, None, None]        # [b, s, nb, bs]
    scores = jnp.where(live[:, None], scores, -1e9)
    p = jax.nn.softmax(scores.reshape(b, h, s, nb * bs), axis=-1)
    p = p.reshape(scores.shape).astype(g.dtype)
    ctx = jnp.einsum("bhsnt,bndt->bhsd", p, g[:, :, :value_dim],
                     preferred_element_type=jnp.float32)
    return ctx.astype(q.dtype)


def _paged_gate(kernel, training, supported):
    """Gate shared by the pool's Pallas kernels; every rejection
    bumps pallas.gate_reject.{kernel}.{reason} so bench/serve output can
    say why the pool path ran on jnp. `supported` is a thunk."""
    from ..core import flags as _flags
    from ..ops.pallas import gate_reject
    if not _flags.flag("FLAGS_use_paged_attention"):
        return gate_reject(kernel, "flag_off")
    from . import functional as F
    if not F._pallas_backend_ok():
        return gate_reject(kernel, "backend")
    if training:
        # eval-only, like the contiguous decode kernel (no dropout/vjp)
        return gate_reject(kernel, "training")
    if not supported():
        return gate_reject(kernel, "shape")
    return True


def _value_arena_matches(k_arena, v_arena):
    """Is the V arena the K arena's blocks and heads in the K arena's
    dtype (its depth may differ)?"""
    return v_arena.dtype == k_arena.dtype and v_arena.shape[:2] \
        + v_arena.shape[3:] == k_arena.shape[:2] + k_arena.shape[3:]


def _paged_kernel_eligible(q, k_arena, v_arena, training, sinks=None):
    """The paged kernel's gate over BOTH arenas. Beside `_paged_gate`'s
    reasons: `value_arena`, a V arena that is not the K arena's blocks
    and heads in the K arena's dtype (its depth may differ), and
    `sinks`, sinks where the kernel takes none (one query head a
    key-value head, or a chunk: only the grouped form starts a slot's
    softmax from a sink)."""
    from ..ops.pallas import gate_reject
    from ..ops.pallas.decode_attention import paged_group, paged_supported
    kernel = "paged_decode_attention"
    if not _paged_gate(
            kernel, training,
            lambda: paged_supported(tuple(q.shape), tuple(k_arena.shape),
                                    k_arena.dtype.itemsize,
                                    d_v=v_arena.shape[2])):
        return False
    if not _value_arena_matches(k_arena, v_arena):
        return gate_reject(kernel, "value_arena")
    if sinks is not None and (
            paged_group(q.shape[1], k_arena.shape[1]) < 2
            or tuple(sinks.shape) != (q.shape[1],)):
        return gate_reject(kernel, "sinks")
    return True


def _latent_kernel_eligible(q, arena, value_dim):
    from ..ops.pallas.decode_attention import latent_paged_supported
    return _paged_gate(
        "latent_paged_attention", False,
        lambda: latent_paged_supported(tuple(q.shape), tuple(arena.shape),
                                       arena.dtype.itemsize, value_dim))


def _write_kernel_eligible(arena, slots):
    from ..ops.pallas.decode_attention import paged_write_supported
    return _paged_gate(
        "paged_write_token", False,
        lambda: paged_write_supported(tuple(arena.shape),
                                      arena.dtype.itemsize, slots))


def paged_attention(q, k_arena, v_arena, block_tables, lengths, scale,
                    training=False, sinks=None):
    """Gated paged attention over arenas the caller has WRITTEN
    (`write_kv`; `paged_write_attend` is the entry that does both): the
    Pallas block-table kernel when
    eligible, `paged_attention_ref` when the gate rejects. The K arena is
    [n, h_kv, d, bs], the V arena [n, h_kv, d_v, bs] (-> [b, h, s, d_v]);
    `sinks` [h] float32 or None: a sink logit a query head
    (`paged_attention_ref`)."""
    if _paged_kernel_eligible(q, k_arena, v_arena, training, sinks):
        from ..ops.pallas import run_guarded
        from ..core import monitor
        from ..ops.pallas.decode_attention import (paged_cut,
                                                   paged_decode_attention)
        # which cut this program compiles with, on the kernel's span and
        # as a pair of gauges per (slots, query rows) for a dump to read:
        # b32s1 is a decode step of 32 slots, b1s256 a prefill, b128s1g6
        # a grouped-query step of 6 query heads a key-value head
        max_steps = _max_list_steps(k_arena, q.shape[0])
        d_v = int(v_arena.shape[2])
        cut = paged_cut(tuple(q.shape), tuple(k_arena.shape),
                        block_tables.shape[1], k_arena.dtype.itemsize,
                        max_steps, d_v=d_v)
        # a call whose values are narrower than its keys, or that starts
        # its softmax from sinks, says so beside its cut
        if d_v != q.shape[3] or sinks is not None:
            cut.update(value_dim=d_v, sinks=int(sinks is not None))
        group = q.shape[1] // k_arena.shape[1]
        key = f"b{q.shape[0]}s{q.shape[2]}" + (f"g{group}" if group > 1
                                               else "")
        monitor.stat_set_many({
            f"pallas.paged_decode_attention.{name}.{key}": value
            for name, value in cut.items()})
        if group == 1:     # the grid that ends at the list's live count
            monitor.stat_add("pallas.hit.paged_work_list")
        return run_guarded(
            "paged_decode_attention",
            lambda: paged_decode_attention(q, k_arena, v_arena,
                                           block_tables, lengths, scale,
                                           max_steps, sinks),
            **cut)
    return paged_attention_ref(q, k_arena, v_arena, block_tables, lengths,
                               scale, sinks)


def _max_list_steps(arena, slots):
    """What bounds a call's work list: a pool's block belongs to ONE
    slot's table (the trash block apart), so the live (slot, block) pairs
    are at most the arena's blocks and a step a slot."""
    return arena.shape[0] - 1 + slots


def _write_attend_cut(q, k_arena, v_arena, table_blocks, training):
    """How ONE call of the multi-head kernel that writes this step's
    tokens and attends would be cut (`paged_write_attend_cut`), or None
    where the step goes to the pair. From the shapes alone. A chunk
    (s > 1) and a group of query heads a key-value head are not this
    gate's to judge and go uncounted; a decode step of one query head a
    key-value head is admitted, or counted under
    `pallas.gate_reject.paged_write_attend.*`: `_paged_gate`'s reasons
    (the pair's own gates count theirs where the call goes next), and
    `shape` where the kernel or the writer would refuse the call, the V
    arena is not the K arena's blocks, heads and dtype, a block is not
    whole 128-lane tiles, or the write would shrink the kernel's head
    tile."""
    from ..ops.pallas.decode_attention import paged_write_attend_cut
    if q.shape[2] != 1 or q.shape[1] != k_arena.shape[1]:
        return None
    cut = paged_write_attend_cut(
        tuple(q.shape), tuple(k_arena.shape), tuple(v_arena.shape),
        table_blocks, k_arena.dtype.itemsize,
        _max_list_steps(k_arena, q.shape[0])) \
        if _value_arena_matches(k_arena, v_arena) else None
    return cut if _paged_gate("paged_write_attend", training,
                              lambda: cut is not None) else None


def paged_write_attend(q, k_arena, v_arena, block_tables, lengths, new_k,
                       new_v, scale, training=False):
    """A layer's step over its paged cache, whole: the chunk's keys and
    values `new_k` [b, s, h, d] and `new_v` [b, s, h, d_v] written at
    `lengths`.., then q [b, h_q, s, d] attended over the arenas as
    written -> (out [b, h_q, s, d_v], k_arena, v_arena). The ONE place
    that chooses how:
    - one token a slot (s = 1), one query head a key-value head, and a
      shape `_write_attend_cut` admits: ONE Pallas call, the paged kernel
      writing each slot's token into the block it holds anyway
      (ops/pallas/decode_attention.paged_write_attend): no block is read
      for the write, each written block is stored once;
    - everything else (a prefill chunk, grouped-query heads, the flag
      off, the CPU, training, a shape the gate refuses): `write_kv` twice,
      then `paged_attention`, each behind its own gate as ever.
    The two give the same output and the same arenas, bit for bit, but
    for the trash block. Two slots may share a physical row only if it is
    the trash block (the pool's invariant). The choice reads the shapes
    and `FLAGS_use_paged_attention`, nothing else."""
    lens = jnp.asarray(lengths, jnp.int32)
    cut = _write_attend_cut(q, k_arena, v_arena, block_tables.shape[1],
                            training)
    if cut is not None:
        from ..core import monitor
        from ..ops.pallas import run_guarded
        from ..ops.pallas.decode_attention import \
            paged_write_attend as write_attend
        # the cut this program compiles with and the bytes a call stores,
        # on the kernel's span and as gauges per slot count (b32s1 is a
        # decode step of 32 slots)
        monitor.stat_set_many({
            f"pallas.paged_write_attend.{name}.b{q.shape[0]}s1": value
            for name, value in cut.items()})
        monitor.stat_add("pallas.hit.paged_work_list")
        return run_guarded(
            "paged_write_attend",
            lambda: write_attend(
                q, k_arena, v_arena, block_tables, lens,
                _token_lanes(k_arena, new_k), _token_lanes(v_arena, new_v),
                scale, _max_list_steps(k_arena, q.shape[0])),
            **cut)
    k_arena = write_kv(k_arena, block_tables, lens, new_k)
    v_arena = write_kv(v_arena, block_tables, lens, new_v)
    return (paged_attention(q, k_arena, v_arena, block_tables, lens, scale,
                            training=training), k_arena, v_arena)


# --------------------------------------------------------------------------
# the window cache: a ring a decode slot (`WindowKVCache`)
# --------------------------------------------------------------------------

def window_ring_shape(window, block, heads, head_dim):
    """One slot's row of a ring of `window` tokens in blocks of `block`:
    (ring_blocks, heads, head_dim, block), an arena's layout; a layer's K
    ring and V ring each have their own (`head_dim` may differ). The
    window is whole blocks (ROADMAP: other windows wait), down to ONE: a
    window of a block's tokens is a ring of one block a slot, and the
    kernel's work list then holds one step a slot."""
    if window < block or window % block:
        raise ValueError(f"a window of {window} tokens is no multiple of "
                         f"its ring's block of {block}")
    return (int(window) // int(block), int(heads), int(head_dim), int(block))


def _ring_arena(ring):
    """[slots, ring_blocks, h, d, block] as the arena [slots *
    ring_blocks, h, d, block] (the leading dimensions merged: no copy)."""
    return ring.reshape((ring.shape[0] * ring.shape[1],) + ring.shape[2:])


def _ring_tables(ring):
    """Slot i's table over `_ring_arena`: its own blocks, in order."""
    slots, blocks = ring.shape[:2]
    return (jnp.arange(slots, dtype=jnp.int32)[:, None] * blocks
            + jnp.arange(blocks, dtype=jnp.int32)[None])


def window_fill(ring, new_kv, count):
    """What a prefill leaves in ONE slot's ring [1, ring_blocks, h, d,
    block]: of the chunk `new_kv` [1, s, h, d], whose first `count`
    tokens exist (positions 0..count-1: a prefill starts an empty slot),
    the last min(count, window), each at column position mod window; the
    columns no token has are zero. One slice of the chunk and one
    rotation: no scatter."""
    _, blocks, h, d, bs = ring.shape
    window = blocks * bs
    count = jnp.asarray(count, jnp.int32).reshape(())
    # positions count - window .. count - 1 (zeros before position 0)
    padded = jnp.pad(new_kv[0].astype(ring.dtype),
                     ((window, 0), (0, 0), (0, 0)))
    last = jax.lax.dynamic_slice_in_dim(padded, count, window, axis=0)
    # row i of `last` is position count - window + i: column (count + i)
    # mod window
    cols = jnp.roll(last, count % window, axis=0)          # [window, h, d]
    return jnp.transpose(cols.reshape(blocks, bs, h, d), (0, 2, 3, 1))[None]


def window_write(ring, lengths, new_kv):
    """A decode step's write: slot i's token `new_kv[i]` ([b, 1, h, d],
    b = the ring's slots) at column lengths[i] mod window of its own
    ring, through `write_kv` (the Pallas token writer where its gate
    admits) over the ring as an arena."""
    slots, blocks, _, _, bs = ring.shape
    lens = jnp.asarray(lengths, jnp.int32) % jnp.int32(blocks * bs)
    return write_kv(_ring_arena(ring), _ring_tables(ring), lens,
                    new_kv).reshape(ring.shape)


def window_attention(q, k_ring, v_ring, lengths, scale, sinks=None):
    """One token a slot over its ring, AFTER `window_write`: q [b, h, 1,
    d] attends the min(lengths[i] + 1, window) columns that hold a token
    (columns 0..lengths[i] until the ring wraps, all of them after).
    `paged_attention` over the ring as an arena under the slot's own
    table: the paged kernel's second call site, and the same gate, `sinks`
    and a V ring of its own depth included."""
    window = k_ring.shape[1] * k_ring.shape[4]
    lens = jnp.minimum(jnp.asarray(lengths, jnp.int32),
                       jnp.int32(window - 1))
    return paged_attention(q, _ring_arena(k_ring), _ring_arena(v_ring),
                           _ring_tables(k_ring), lens, scale, sinks=sinks)
