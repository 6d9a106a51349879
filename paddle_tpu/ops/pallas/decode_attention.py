"""Single-query flash attention over a StaticKVCache — the decode kernel.

The generate() hot loop attends one new token (or a small chunk) against a
preallocated [b, h, max_seq_len, d] cache that is mostly empty: after
prefilling a 32-token prompt into a 1024-slot cache, the jnp path
(nn/layer/transformer._static_cache_attention) still streams all 1024
padded K/V columns through the MXU every step and masks 90%+ of them to
-1e9 after the fact. This kernel moves both the masking and the skipping
inside the Pallas grid:

- the cache length rides in as a *scalar-prefetch* operand (SMEM), so the
  K/V BlockSpec index maps can clamp the block index to the last live
  block — Pallas skips the HBM->VMEM DMA for a revisited block, so a step
  at cache length `len` reads ~ceil(len/bk) blocks instead of
  max_seq_len/bk;
- fully-dead blocks skip their compute via pl.when on the same predicate;
- the live/dead boundary column is masked in-kernel against
  `index + row` (identical semantics to _static_cache_attention: position
  p = index + row attends to cache cols <= p).

Lengths may be a scalar (the StaticKVCache.index fast path) or a [b]
vector — ragged per-batch lengths attend each batch row to its own
prefix, which the jnp path can't express without materializing a mask.

Decode runs under no_grad inside the generation scan, so this kernel is
deliberately vjp-free: differentiating it raises, and the eligibility
gate (nn/layer/transformer._decode_kernel_eligible) keeps training-time
cache use on the jnp path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .flash_attention import (NEG_INF, _Z, _ceil_to, _cparams, _interpret,
                              _pick_block, _vmem)

__all__ = ["decode_attention", "supported",
           "paged_decode_attention", "paged_supported", "paged_group",
           "paged_write_token", "paged_write_supported",
           "paged_write_attend", "paged_write_attend_cut",
           "latent_paged_decode_attention", "latent_paged_supported"]


def _decode_attn_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                        m_scr, l_scr, acc_scr, *, scale, bk, nk, s):
    """Grid (b, h, nk); nk is the sequential accumulator dim. len_ref is
    the scalar-prefetch [b] live-length vector (index + s per batch)."""
    ib, ik = pl.program_id(0), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # np.int32 scalars throughout: arithmetic mixing an SMEM-read scalar
    # with weak python ints emits scalar converts Mosaic can't lower
    length = len_ref[ib]                       # live cols for the LAST row
    index = length - np.int32(s)               # cache fill before the chunk
    last = jnp.maximum(length - np.int32(1),
                       np.int32(0)) // np.int32(bk)  # last live block

    @pl.when(ik <= last)
    def _compute():
        q = q_ref[0, 0]                        # [s, d]
        k = k_ref[0, 0]                        # [bk, d]
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        row = jax.lax.broadcasted_iota(jnp.int32, (s, bk), 0)
        col = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (s, bk), 1)
        # np.float32: weak-f64 scalar converts recurse Mosaic lowering on
        # some jax builds (see flash_attention._causal_mask)
        sc = jnp.where(col <= index + row, sc, np.float32(NEG_INF))
        m_prev = m_scr[:]                      # [s, 1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)                # [s, bk] f32
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0, 0],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = m_new

    @pl.when(ik == nk - 1)
    def _flush():
        denom = jnp.maximum(l_scr[:], 1e-30)   # padded rows stay finite
        o_ref[0, 0] = (acc_scr[:] / denom).astype(o_ref.dtype)


def supported(q_shape, cache_shape) -> bool:
    """Static predicate: can the decode kernel serve this (q, cache) pair?
    q [b, h, s, d] against cache [b, h, L, d]. The query chunk is padded
    to the 8-row sublane tile in the wrapper, so any s up to 256 works;
    beyond that a chunked prefill belongs on the flash kernel instead."""
    if len(q_shape) != 4 or len(cache_shape) != 4:
        return False
    b, h, s, d = q_shape
    bl, hl, L, dl = cache_shape
    if (bl, hl, dl) != (b, h, d):
        return False
    if d > 256 or s < 1 or s > 256 or L < 8:
        return False
    return _pick_block(_ceil_to(L, 8), 128) is not None


def _call(q, kc, vc, lengths, scale, bk):
    """The pallas_call for already-tile-padded operands."""
    from jax.experimental.pallas import tpu as pltpu
    b, h, s_p, d = q.shape
    nk = kc.shape[2] // bk

    def q_map(ib, ih, ik, len_ref):
        return (ib, ih, _Z, _Z)

    def kv_map(ib, ih, ik, len_ref):
        # clamp to the last live block: a revisited block index skips the
        # HBM->VMEM DMA, so dead cache tail blocks are never fetched
        # (np.int32 scalars: see _decode_attn_kernel)
        last = jnp.maximum(len_ref[ib] - np.int32(1),
                           np.int32(0)) // np.int32(bk)
        return (ib, ih, jnp.minimum(ik, last), _Z)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, nk),
        in_specs=[
            pl.BlockSpec((1, 1, s_p, d), q_map),
            pl.BlockSpec((1, 1, bk, d), kv_map),
            pl.BlockSpec((1, 1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, s_p, d), q_map),
        scratch_shapes=[
            _vmem((s_p, 1), jnp.float32),
            _vmem((s_p, 1), jnp.float32),
            _vmem((s_p, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_attn_kernel, scale=float(scale),
                               bk=bk, nk=nk, s=s_p)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s_p, d), q.dtype),
        compiler_params=_cparams("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
    )(lengths, q, kc, vc)


def _pick_bk(shape, dtype, scale, measure_builder):
    """KV block size: FLAGS_decode_block_k override, else the autotune
    table, else 128 columns (one MXU lane tile; small enough that a
    33-token prompt reads one block, big enough to amortize the grid)."""
    from ...core import flags as _flags
    from . import autotune
    b, h, s_p, d, L_p = shape
    cfg = int(_flags.flag("FLAGS_decode_block_k") or 0)
    default = _pick_block(L_p, cfg or 128)
    if cfg:
        return default
    cands = [(x,) for x in (256, 128, 64) if L_p % x == 0]
    if len(cands) <= 1:
        return default
    return autotune.lookup(
        "decode_attention",
        (autotune.bucket(L_p), autotune.bucket(s_p), d),
        dtype, cands, measure_builder(), (default,))[0]


# --------------------------------------------------------------------------
# block-table (paged) variant: the serving tier's kernel
# --------------------------------------------------------------------------
#
# The contiguous kernel above assumes each batch row owns a private
# [L, d] cache slab. The continuous-batching serve loop
# (inference/serving.py) instead shares ONE physical arena
# [n_blocks, h, d, block_size] across every in-flight request
# (nn/kv_pool.py): request i's logical block j lives at physical row
# block_tables[i, j]. The indirection lives in the K/V BlockSpec index
# maps — the block table rides the scalar-prefetch path next to the
# ragged lengths, so the index map gathers the LIVE physical block for
# (batch, logical-block) and clamps past the last live one exactly like
# the contiguous kernel. Per-step HBM traffic therefore scales with
# ceil(live_len/bs) blocks per request, never with max_seq_len, and
# never with the arena size.
#
# Layout contract: a K/V block is [d, block_size], TOKENS IN THE LANES.
# A Mosaic custom call takes its operands row-major; XLA's default TPU
# layout of a 4-d bf16 array is row-major only when the minor dimension
# fills the 128 lanes. With d (64) minor, XLA put block_size in the
# lanes instead, the kernel's operand and the pool's buffer disagreed,
# and every call copied every arena (PR 26: 76 % of a decode beat). With
# block_size minor and a multiple of 128 the pool's buffer IS the
# kernel's operand; nn/kv_pool.write_kv updates it in place.

def _token_into_block(tok_ref, blk_ref, out_ref, buf, i, at, interpret):
    """out_ref[buf] = blk_ref[0] ([h, d, bs] both) with column i of the
    tokens tok_ref [h, d, slots padded to 128s] in lane `at` (no lane
    where `at` is negative): what the token writer would do to the
    block, done by the paged kernel to the block it holds.

    The column gets there as in `_paged_write_kernel`, by a lane rotation
    of the 128 slots around slot i, by at - i: data is moved, never
    computed with, so whatever a token's bits say (-0.0, inf, a NaN) they
    arrive as they are, and no other slot's can leak into the block. But
    on 32-bit WORDS: a block and tokens narrower than 32 bits are read
    and stored as the words their rows pack into (d x itemsize / 4 rows
    of them; a view of the buffers, no element converted: which rows
    share a word is the buffers' layout, the same for the tokens and the
    block), so a bfloat16 block of 100 vector registers is rotated,
    selected and stored as 100, with no float32 form of it in VMEM (a
    step of GPT-2 XL's 25 heads plans 7.8 MiB, 10.6 the writer's way;
    measured alone on a v5e the two ways cost the same ~7 us a call of
    32 slots, PR 47). The interpreter stores through no such view and
    casts the values instead."""
    from jax.experimental.pallas import tpu as pltpu
    lanes, bs = np.int32(128), blk_ref.shape[3]
    tile = pl.ds(pl.multiple_of(i // lanes * lanes, 128), 128)
    if interpret:
        tok = pltpu.bitcast(tok_ref[:, :, tile], jnp.int32)
        blk = pltpu.bitcast(blk_ref[0], jnp.int32)
    else:
        tok = tok_ref.bitcast(jnp.int32)[:, :, tile]
        blk = blk_ref.bitcast(jnp.int32)[0]
    tok = pltpu.roll(tok, (at % lanes - i % lanes + lanes) % lanes, 2)
    # the block's lanes beside the 128 rotated ones: a prefix of them, or
    # whole copies (slot i now sits in lane at % 128 of every copy)
    tok = tok[:, :, :bs] if bs <= 128 else jnp.tile(tok, (1, 1, bs // 128))
    lane = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 2)
    blk = jnp.where(lane == at, tok, blk)
    if interpret:
        out_ref[buf] = pltpu.bitcast(blk, out_ref.dtype)
    else:
        out_ref.bitcast(jnp.int32)[buf] = blk


def _paged_block_update(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, scale,
                        live):
    """One K/V block into the online softmax of a tile of ht heads: q
    [ht, s, d] against kT [ht, d, bs] and vT [ht, d_v, bs] (d_v = d but
    where a net's values are narrower than its keys), the columns `live()`
    ([s, bs] bool) leaves, the float32 state m, l [ht, s, 1] and acc
    [ht, s, d_v] rescaled and added to. Shared by the multi-head and the
    grouped-query kernel: they differ in which columns a row may see and
    in how the grid walks the blocks."""
    sc = jax.lax.dot_general(q_ref[0], k_ref[0],
                             (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32) * scale
    sc = jnp.where(live()[None], sc, np.float32(NEG_INF))
    m_prev = m_scr[:]                          # [ht, s, 1]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(sc - m_new)                    # [ht, s, bs] f32
    l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    # p [ht, s, bs] against vT [ht, d, bs]: both contract their lanes
    pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                             (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    acc_scr[:] = acc_scr[:] * alpha + pv
    m_scr[:] = m_new


def _paged_decode_attn_kernel(len_ref, slot_ref, blk_ref, phys_ref, q_ref,
                              k_ref, v_ref, *rest, scale, bs, nb, s, rows,
                              slots, write=False, interpret=False):
    """Grid (h // ht, n_live): step (ih, w) holds item w of the work list
    (`_paged_live_list`) for a tile of ht heads: logical block blk[w] of
    slot slot[w], q/out [1, ht, s, d], K/V [1, ht, d, bs] fetched from
    physical row phys[w] (the index maps' business). The grid's second
    bound is the list's LIVE count, read on the device: no step is dead. A
    slot's items are consecutive and every slot has one: its softmax
    state is initialised at its block 0 and flushed at its last. The
    heads are batched products (Mosaic unrolls them into straight-line
    code; a fori_loop over the same 2-D body was 5x slower at 25 heads,
    PR 31). len_ref is the [b] vector of fills INCLUDING the chunk's
    `rows` tokens (of the s query rows the first `rows` are positions,
    the rest padding to the sublane tile, sliced off by the caller: they
    bring no block of their own into the list).

    With `write` (one token a slot, `rows` = 1: PR 47) the step's key and
    value tokens come too, [ht, d, slots] and [ht, d_v, slots] before the
    output (`_paged_write_kernel`'s dense operand, a head tile of it),
    and the arenas again as two more outputs, aliased to the inputs and
    left in HBM: the kernel stores what it writes itself. A slot's last
    item holds the token's own block, fill // bs (the table's last block
    where the token falls past the table): it puts the block it fetched,
    with the token at lane fill % bs, into one of two VMEM buffers an
    arena (`k_buf`, `v_buf` [2, ht, d, bs]), starts their copies to the
    token's physical row, and attends over THEM: exactly the blocks the
    writer would have left for a kernel that ran after it. A copy is
    waited for when its buffer comes round again, two (head tile, slot)s
    on, and at the last grid step: it is in flight under the next slot's
    fetches and products. (As pipelined output blocks written back at a
    slot's end, the same 26 MB of GPT-2 XL's step cost 74 us a call, as
    much as the writer they replaced.) The grid of a writing call runs
    in order for that. A token past its slot's table is not in the
    table: the item of the table's last block stores that block
    unamended (to the trash block's row) and attends over it as it is."""
    if write:
        tk_ref, tv_ref, o_ref, ko_hbm, vo_hbm = rest[:5]
        m_scr, l_scr, acc_scr, k_buf, v_buf, sems = rest[5:]
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    ih, w = pl.program_id(0), pl.program_id(1)
    ib, ik = slot_ref[w], blk_ref[w]
    length = len_ref[ib]                       # live cols for the LAST row
    index = length - np.int32(rows)            # cache fill before the chunk
    last = jnp.minimum(
        jnp.maximum(length - np.int32(1), np.int32(0)) // np.int32(bs),
        np.int32(nb - 1))                      # the slot's last listed block

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def live():
        row = jax.lax.broadcasted_iota(jnp.int32, (s, bs), 0)
        col = ik * bs + jax.lax.broadcasted_iota(jnp.int32, (s, bs), 1)
        return col <= index + row

    if not write:
        _paged_block_update(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                            scale, live)
    else:
        from jax.experimental.pallas import tpu as pltpu
        held = ik == last                      # the token's own block
        # the lane the token takes; none where it falls past the table
        past = index // np.int32(bs) >= np.int32(nb)
        at = jnp.where(past, np.int32(-1), index % np.int32(bs))
        # the token's physical row: the trash block past the table
        # (nn/kv_pool._phys_row)
        row = jnp.where(past, np.int32(0), phys_ref[w])
        n = ih * np.int32(slots) + ib          # this (head tile, slot)
        buf = n % np.int32(2)
        ht = k_ref.shape[1]

        def stores(buf):
            heads = pl.ds(ih * np.int32(ht), ht)
            return [pltpu.make_async_copy(src.at[buf], dst.at[row, heads],
                                          sems.at[buf, np.int32(j)])
                    for j, (src, dst) in enumerate(((k_buf, ko_hbm),
                                                    (v_buf, vo_hbm)))]

        @pl.when(held)
        def _write_and_compute():
            @pl.when(n >= 2)                   # the buffer's last store
            def _():
                for store in stores(buf):
                    store.wait()
            _token_into_block(tk_ref, k_ref, k_buf, buf, ib, at, interpret)
            _token_into_block(tv_ref, v_ref, v_buf, buf, ib, at, interpret)
            for store in stores(buf):
                store.start()
            _paged_block_update(q_ref, k_buf.at[pl.ds(buf, 1)],
                                v_buf.at[pl.ds(buf, 1)], m_scr, l_scr,
                                acc_scr, scale, live)

        # the list's last item is the last slot's own block
        @pl.when((ih == pl.num_programs(0) - 1)
                 & (w == pl.num_programs(1) - 1))
        def _drain():                          # what is still in flight
            for store in stores(buf):
                store.wait()

            @pl.when(n >= 1)
            def _():
                for store in stores(np.int32(1) - buf):
                    store.wait()

        @pl.when(jnp.logical_not(held))
        def _compute():
            _paged_block_update(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                                scale, live)

    @pl.when(ik == last)
    def _flush():
        denom = jnp.maximum(l_scr[:], 1e-30)   # padded rows stay finite
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)


# What one grid step of the paged kernel may plan to hold in VMEM: the
# pipeline's two buffers of its K, V, q and out blocks, the softmax state
# and the scores of its heads. Mosaic's scoped VMEM on a v5e is 16 MiB by
# default; the rest is room for what the compiler spills.
_ATTN_VMEM_BYTES = 12 << 20


def _paged_step_bytes(ht, s_p, d, bs, itemsize, d_v=None, write_slots=0):
    """VMEM bytes of one grid step over `ht` heads, as Mosaic lays the
    blocks out: the minor dimension padded to the 128 lanes. Keys (and
    queries) `d` deep, values (and outputs) `d_v`, `d` where not given; a
    group's sinks, where a call has them, are one more column of state
    (4 KB a head) and are not counted. A step that also writes the
    tokens of `write_slots` slots holds, beside that, two buffers of each
    block it stores and the head tile's tokens, double-buffered, and
    the words of the 128 slots it rotates and selects
    (`_token_into_block`)."""
    d_v = d if d_v is None else d_v
    d_l, dv_l, bs_l = _ceil_to(d, 128), _ceil_to(d_v, 128), _ceil_to(bs, 128)
    kv = 2 * ht * (d + d_v) * bs_l * itemsize      # K, V, double-buffered
    qo = 2 * ht * s_p * (d_l + dv_l) * itemsize    # q, out, double-buffered
    state = ht * s_p * (128 + 128 + dv_l) * 4      # m, l, acc in float32
    scores = 3 * ht * s_p * bs_l * 4               # sc, p and a temporary
    if not write_slots:
        return kv + qo + state + scores
    tokens = 2 * ht * (d + d_v) * _ceil_to(write_slots, 128) * itemsize
    words = 3 * ht * (d + d_v) * 128 * itemsize     # read, rotated, selected
    return 2 * kv + qo + state + scores + tokens + words


def paged_heads_per_step(h, s_p, d, bs, itemsize,
                         budget=_ATTN_VMEM_BYTES, d_v=None,
                         write_slots=0) -> int:
    """The paged kernel's head tile: the largest divisor of `h` whose
    grid step fits `budget` bytes of VMEM, 0 where not even one head
    does. From the shape alone: GPT-2 XL's decode step (h 25, 8 padded
    query rows, d 64, block 128, bf16) takes all 25 heads of a block in
    one step, its 128- and 256-row prefills 5; the same decode step
    WRITING its 32 slots' tokens (`write_slots`) still all 25, 7.8 of
    the 12 MiB."""
    for ht in range(int(h), 0, -1):
        if h % ht == 0 and _paged_step_bytes(
                ht, s_p, d, bs, itemsize, d_v, write_slots) <= budget:
            return ht
    return 0


def paged_group(q_heads, kv_heads) -> int:
    """Query heads a key-value head: 1 is multi-head attention, G > 1
    grouped-query attention (query head h reads key-value head h // G),
    0 where the query heads are no multiple of the arena's."""
    return q_heads // kv_heads if kv_heads and q_heads % kv_heads == 0 \
        else 0


def _paged_rows(group, s):
    """The rows a key-value head's product has, padded to the sublane
    tile: a chunk's s positions, or the G query heads of one token."""
    return _ceil_to(group if group > 1 else s, 8)


def paged_supported(q_shape, arena_shape, itemsize=4, d_v=None) -> bool:
    """Static predicate: can the paged kernel serve q [b, h, s, d] over
    a K arena [n_blocks, h_kv, d, block_size] of `itemsize`-byte elements
    (and a V arena of the same blocks and heads, `d_v` deep: `d` where
    not given)?
    h = h_kv (multi-head: the rows of a head's product are the chunk's
    positions), or h = G x h_kv with one token a slot (grouped-query: the
    rows are the G query heads of a key-value head, all under the same
    length). block_size is fixed by the pool layout, so it must already
    be a sublane-tile multiple; one key-value head of one block, with its
    query rows and softmax state, has to fit the kernel's VMEM budget."""
    if len(q_shape) != 4 or len(arena_shape) != 4:
        return False
    b, h, s, d = q_shape
    nb_phys, hl, dl, bs = arena_shape
    group = paged_group(h, hl)
    d_v = d if d_v is None else int(d_v)
    if dl != d or not group or (group > 1 and s != 1):
        return False
    if d > 256 or s < 1 or s > 256 or group > 256:
        return False
    if d_v < 1 or d_v > 256 or d_v % 8:        # the values' sublanes
        return False
    if bs < 8 or bs % 8 != 0 or nb_phys < 1:
        return False
    return paged_heads_per_step(hl, _paged_rows(group, s), d, bs,
                                itemsize, d_v=d_v) > 0


def paged_cut(q_shape, arena_shape, table_blocks, itemsize,
              max_steps=None, d_v=None) -> dict:
    """How a supported call is cut into grid steps: `heads_per_step`
    (key-value heads; each brings its G query heads as rows), and
    `grid_steps` = head tiles x the work list's length
    (`paged_list_steps`). The multi-head form's grid ENDS at the list's
    live count, read on the device: its `grid_steps` is the upper bound,
    and `list_steps` says the list's static length beside it."""
    b, h, s, d = q_shape
    hl = arena_shape[1]
    group = paged_group(h, hl)
    ht = paged_heads_per_step(hl, _paged_rows(group, s), d, arena_shape[3],
                              itemsize, d_v=d_v)
    steps = paged_list_steps(b, table_blocks, max_steps)
    return {"heads_per_step": ht, "grid_steps": (hl // ht) * steps,
            **({"list_steps": steps} if group == 1 else {})}


def paged_write_attend_cut(q_shape, k_shape, v_shape, table_blocks,
                           itemsize, max_steps=None):
    """The writing form's ONE static predicate, and its cut: can one call
    of the multi-head kernel write a decode step's tokens and attend
    (q [b, h, 1, d] over arenas k_shape [n, h, d, bs] and v_shape [n, h,
    d_v, bs])? None where not; else `paged_cut`'s `heads_per_step`,
    `grid_steps` and `list_steps` (the head tile with the write's VMEM
    counted; `max_steps` bounds the list as there) and `write_bytes`,
    the slots' K and V blocks as laid out, each written
    back once (nothing of them is read for the write: the kernel holds
    the block already).

    It takes one token a slot, one query head a key-value head, a call
    and two arenas that the kernel and the token writer each take
    (`paged_supported`, `paged_write_supported`), rows that fill whole
    32-bit words as tiled (`_token_into_block`: d and d_v multiples of 8
    words' rows), a block of whole 128-lane tiles (what the kernel's own
    store of it moves), and a head tile no smaller than the call gets WITHOUT
    the write: a grid step that holds the output blocks and the tokens
    too must not cost the kernel grid steps (GPT-2 XL's 25 heads of 64
    fit; 30 heads of 128 would halve to 15, and stay on the writer and
    the kernel apart)."""
    if len(q_shape) != 4 or len(k_shape) != 4 or len(v_shape) != 4:
        return None
    b, h, s, d = q_shape
    bs, d_v = k_shape[3], v_shape[2]
    if s != 1 or paged_group(h, k_shape[1]) != 1:
        return None
    if not paged_supported(q_shape, k_shape, itemsize, d_v=d_v) or not all(
            paged_write_supported(a, itemsize, b) for a in (k_shape, v_shape)):
        return None
    word_rows = 8 * 4 // int(itemsize)
    if d % word_rows or d_v % word_rows or bs % 128:
        return None
    tile = functools.partial(paged_heads_per_step, h, _paged_rows(1, s), d,
                             bs, itemsize, d_v=d_v)
    ht = tile(write_slots=b)
    if ht != tile():
        return None
    steps = paged_list_steps(b, table_blocks, max_steps)
    return {"heads_per_step": ht, "grid_steps": (h // ht) * steps,
            "list_steps": steps,
            "write_bytes": b * h * (d + d_v) * _ceil_to(bs, 128) * itemsize}


# --------------------------------------------------------------------------
# the WORK LIST: one grid step a live block
# --------------------------------------------------------------------------
#
# A grid over every entry of every slot's table has mostly dead steps: a
# table is as wide as the longest stream the loop admits (8 blocks for
# GPT-2 XL's 1024 tokens, 72 at Laguna's 9216) and a slot holds 2 or 19 of
# them, and a dead step still costs grid overhead (~0.15-0.3 us measured:
# Laguna's full layer 3.13 ms over 9216 steps, 2.32 over the 3200 of the
# list). Both paged forms therefore walk a list of the LIVE (slot, logical
# block) pairs, made outside the kernel from tables and lengths and read
# through scalar prefetch: item w holds slot `slot[w]`'s block `blk[w]` at
# physical row `phys[w]`; a slot's items are consecutive, so its softmax
# state is initialised at its first block and flushed at its last, and the
# output's block index changes when the slot does. The list's arrays are
# as long as the caller says the live pairs can be (`max_steps`; the
# pool's invariant, a physical block belongs to one slot, bounds them by
# the arena's rows plus a step a slot). The multi-head form's grid ENDS at
# the live count, a bound read on the device (PR 48: GPT-2 XL's pool of
# 224 blocks bounds its 32 x 8 = 256 entries by 256, and ~70 are live);
# the grouped-query form's grid is the static length, and its items past
# the live ones repeat the last live item (nothing is fetched) and skip
# their body.

def _paged_work_list(block_tables, lengths, bs, steps):
    """(slot, blk, phys) [steps] i32 and the live count [1] i32 of the
    work list: slot i contributes its logical blocks 0..last_i, last_i =
    min((lengths[i] - 1) // bs, nb - 1) with `lengths` the fill INCLUDING
    this step's tokens, an empty slot its block 0 (every slot's output is
    written)."""
    b, nb = block_tables.shape
    counts = jnp.minimum(jnp.maximum(lengths - 1, 0) // jnp.int32(bs),
                         jnp.int32(nb - 1)) + 1                   # [b]
    ends = jnp.cumsum(counts)
    w = jnp.arange(steps, dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(ends, w, side="right"),
                       b - 1).astype(jnp.int32)
    blk = w - (ends[slot] - counts[slot])
    live = w < ends[-1]
    slot = jnp.where(live, slot, b - 1)
    blk = jnp.where(live, blk, counts[b - 1] - 1).astype(jnp.int32)
    return (slot, blk, block_tables[slot, blk],
            jnp.minimum(ends[-1:], steps).astype(jnp.int32))


def _paged_live_list(block_tables, lengths, bs, steps):
    """`_paged_work_list`'s list, item for item, made of comparisons and
    sums alone. The search and the lookups of small arrays there lower,
    on a TPU, to hundreds of slices and selects a call; these are a few
    fusions over [steps, slots] and [steps, table entries], and the
    layers of a program share them (the multi-head form's; the grouped
    form keeps the list its compiled programs were counted with until
    its grid gets the dynamic end too)."""
    b, nb = block_tables.shape
    i32 = jnp.int32
    counts = jnp.minimum(jnp.maximum(lengths - 1, 0) // i32(bs),
                         i32(nb - 1)) + 1                         # [b]
    i = jnp.arange(b, dtype=i32)
    ends = jnp.sum(jnp.where(i[None] <= i[:, None], counts[None], 0),
                   axis=1, dtype=i32)             # the inclusive cumsum
    w = jnp.arange(steps, dtype=i32)
    before = ends[None] <= w[:, None]             # [steps, b]: slots done
    slot = jnp.minimum(jnp.sum(before, axis=1, dtype=i32), b - 1)
    blk = w - jnp.sum(jnp.where(before, counts[None], 0), axis=1,
                      dtype=i32)
    blk = jnp.where(w < ends[-1], blk, counts[-1] - 1)
    entry = jnp.arange(b * nb, dtype=i32)
    phys = jnp.sum(jnp.where((slot * nb + blk)[:, None] == entry[None],
                             block_tables.reshape(-1)[None], 0),
                   axis=1, dtype=i32)
    return slot, blk, phys, jnp.minimum(ends[-1:], steps)


def paged_list_steps(b, table_blocks, max_steps=None) -> int:
    """The work list's length for b slots over tables `table_blocks`
    wide: `max_steps` where the caller bounds the live (slot, block)
    pairs, never more than every entry of every table."""
    full = int(b) * int(table_blocks)
    return full if not max_steps else max(int(b), min(full, int(max_steps)))


# The two calls below are jitted so that a program with many identical
# layers traces and lowers each of them once (XLA inlines the calls):
# un-jitted, each of GPT-2 XL's serve programs spent seconds re-tracing 48
# identical kernels, all of it set-up time (PR 26). What they read from
# flags is a static argument, so a cached trace never outlives a flag.
# The layers of a program give the work list the same tables and lengths:
# inlined, XLA keeps ONE of their identical computations
# (tests/test_chip_smoke.py counts the compiled program's).

@functools.partial(jax.jit,
                   static_argnames=("scale", "rows", "steps", "interpret"))
def _paged_call_once(q, k_arena, v_arena, block_tables, lengths,
                     new_k=None, new_v=None, *, scale, rows, steps,
                     interpret):
    """The multi-head pallas_call for tile-padded q [b, h, s_p, d] (its
    first `rows` query rows are positions) over arenas [n_blocks, h, d,
    block_size]; `lengths` [b] the fill INCLUDING the chunk's `rows`
    tokens, `steps` the work list's static length. -> out [b, h, s_p,
    d_v]; with the step's tokens `new_k` [h, d, slots padded to 128s]
    and `new_v` [h, d_v, the same] (one token a slot), -> (out, k_arena,
    v_arena): the arenas aliased in and out, each slot's token written
    into its block by the grid step that holds it and the block stored
    by the kernel's own copy (`_paged_decode_attn_kernel`). With
    block_size a multiple of 128 the compiled program hands the pool's
    buffers to the kernel as they are (tests/test_chip_smoke.py holds
    that: no copy, no temp of arena size); other multiples of 8 are
    correct, and XLA relays them out. A device trace names either form
    after this function."""
    from jax.experimental.pallas import tpu as pltpu
    b, h, s_p, d = q.shape
    d_v, bs = v_arena.shape[2], k_arena.shape[3]
    nb = block_tables.shape[1]
    write = new_k is not None
    # the cut into grid steps: a step pays ~0.3 us whatever it holds, so
    # it holds as many of a block's heads as fit (one head a step made
    # GPT-2 XL's decode 6400 steps a layer of 16 KB each: 1.6 ms, all of
    # it step overhead, against 0.17 ms for 256 steps of 400 KB; PR 31),
    # and there is a step for a LIVE block only
    ht = paged_heads_per_step(h, s_p, d, bs, k_arena.dtype.itemsize,
                              d_v=d_v, write_slots=b if write else 0)
    slot, blk, phys, n_live = _paged_live_list(block_tables, lengths, bs,
                                               steps)

    def q_map(ih, w, len_ref, slot_ref, blk_ref, phys_ref):
        return (slot_ref[w], ih, _Z, _Z)

    def kv_map(ih, w, len_ref, slot_ref, blk_ref, phys_ref):
        return (phys_ref[w], ih, _Z, _Z)

    def tok_map(ih, w, len_ref, slot_ref, blk_ref, phys_ref):
        return (ih, _Z, _Z)        # resident across a head tile's steps

    out_spec = pl.BlockSpec((1, ht, s_p, d_v), q_map)
    out_shape = jax.ShapeDtypeStruct((b, h, s_p, d_v), q.dtype)
    tokens = (new_k, new_v) if write else ()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(h // ht, n_live[0]),
        in_specs=[
            pl.BlockSpec((1, ht, s_p, d), q_map),
            pl.BlockSpec((1, ht, d, bs), kv_map),
            pl.BlockSpec((1, ht, d_v, bs), kv_map),
        ] + [pl.BlockSpec((ht,) + t.shape[1:], tok_map) for t in tokens],
        # the arenas as outputs stay in HBM: the kernel stores a slot's
        # amended blocks itself, from two buffers each, a store in flight
        # while the next slot's blocks are fetched and attended
        out_specs=[out_spec] + [pl.BlockSpec(memory_space=pl.ANY)] * 2
        if write else out_spec,
        scratch_shapes=[
            _vmem((ht, s_p, 1), jnp.float32),
            _vmem((ht, s_p, 1), jnp.float32),
            _vmem((ht, s_p, d_v), jnp.float32),
        ] + ([_vmem((2, ht, d, bs), k_arena.dtype),
              _vmem((2, ht, d_v, bs), v_arena.dtype),
              pltpu.SemaphoreType.DMA((2, 2))] if write else []),
    )
    kernel = functools.partial(_paged_decode_attn_kernel, scale=scale,
                               bs=bs, nb=nb, s=s_p, rows=rows, slots=b,
                               write=write, interpret=bool(interpret))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[out_shape] + [jax.ShapeDtypeStruct(a.shape, a.dtype)
                                 for a in (k_arena, v_arena)]
        if write else out_shape,
        # operands: lengths, slot, blk, phys, q, k_arena, v_arena, tokens
        input_output_aliases={5: 1, 6: 2} if write else {},
        # a writing call's stores are waited for two (head tile, slot)s
        # on: its grid runs in order
        compiler_params=_cparams(*(("arbitrary",) * 2 if write else
                                   ("parallel", "arbitrary"))),
        interpret=interpret,
    )(lengths, slot, blk, phys, q, k_arena, v_arena, *tokens)
    return tuple(out) if write else out


# --------------------------------------------------------------------------
# grouped-query form: the list at its static length
# --------------------------------------------------------------------------

def _paged_grouped_kernel(len_ref, slot_ref, blk_ref, phys_ref, n_ref,
                          q_ref, k_ref, v_ref, *rest, scale, bs, nb, s,
                          sinks=False):
    """Grid (h_kv // ht, steps): step (ih, w) holds work item w — logical
    block blk[w] of slot slot[w] for a tile of ht key-value heads, q
    [1, ht, s, d] and out [1, ht, s, d_v] with the G query heads of a
    key-value head as rows (one token a slot: every row under the same
    length), K [1, ht, d, bs], V [1, ht, d_v, bs]. The arithmetic of a
    block, and the blocks' order within a slot, are
    `_paged_decode_attn_kernel`'s. len_ref [b]: the fill with this step's
    token; phys_ref is consumed by the index maps.

    With `sinks`, one more operand [ht, s, 1] float32 before the output:
    a learned logit a query head that joins the softmax's denominator and
    has no value (an attention sink). It is where a slot's state STARTS:
    m = sink, l = exp(sink - m) = 1, acc = 0, and the blocks are added to
    that as to any earlier block; a padded row's sink is NEG_INF, the
    state the others start from but for l, which its first block's alpha
    of 0 wipes out."""
    sink_ref = rest[0] if sinks else None
    o_ref, m_scr, l_scr, acc_scr = rest[-4:]
    w = pl.program_id(1)
    ib, ik = slot_ref[w], blk_ref[w]
    live = w < n_ref[0]
    length = len_ref[ib]
    index = length - np.int32(1)               # this step's token's column
    last = jnp.minimum(
        jnp.maximum(length - np.int32(1), np.int32(0)) // np.int32(bs),
        np.int32(nb - 1))                      # the slot's last live block

    @pl.when(live & (ik == 0))
    def _init():
        if sinks:
            m_scr[:] = sink_ref[:]
            l_scr[:] = jnp.ones_like(l_scr)
        else:
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(live)
    def _compute():
        def seen():
            col = ik * bs + jax.lax.broadcasted_iota(jnp.int32, (s, bs), 1)
            return col <= index
        _paged_block_update(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                            scale, seen)

    @pl.when(live & (ik == last))
    def _flush():
        denom = jnp.maximum(l_scr[:], 1e-30)   # padded rows stay finite
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "steps"))
def _paged_grouped_call_once(q, k_arena, v_arena, block_tables, lengths,
                             sinks=None, *, scale, interpret, steps):
    """q [b, h_kv, s_p, d] (the groups' query heads as rows, padded),
    lengths [b] the fill INCLUDING this step's token, sinks None or
    [h_kv, s_p, 1] float32 (a padded row's NEG_INF)."""
    from jax.experimental.pallas import tpu as pltpu
    b, h, s_p, d = q.shape
    d_v = v_arena.shape[2]
    bs, nb = k_arena.shape[3], block_tables.shape[1]
    ht = paged_heads_per_step(h, s_p, d, bs, k_arena.dtype.itemsize,
                              d_v=d_v)
    slot, blk, phys, n_live = _paged_work_list(block_tables, lengths, bs,
                                               steps)

    def q_map(ih, w, len_ref, slot_ref, blk_ref, phys_ref, n_ref):
        return (slot_ref[w], ih, _Z, _Z)

    def kv_map(ih, w, len_ref, slot_ref, blk_ref, phys_ref, n_ref):
        return (phys_ref[w], ih, _Z, _Z)

    def sink_map(ih, w, len_ref, slot_ref, blk_ref, phys_ref, n_ref):
        return (ih, _Z, _Z)        # resident across a head tile's steps

    has_sinks = sinks is not None
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(h // ht, steps),
        in_specs=[
            pl.BlockSpec((1, ht, s_p, d), q_map),
            pl.BlockSpec((1, ht, d, bs), kv_map),
            pl.BlockSpec((1, ht, d_v, bs), kv_map),
        ] + [pl.BlockSpec((ht, s_p, 1), sink_map)] * has_sinks,
        out_specs=pl.BlockSpec((1, ht, s_p, d_v), q_map),
        scratch_shapes=[
            _vmem((ht, s_p, 1), jnp.float32),
            _vmem((ht, s_p, 1), jnp.float32),
            _vmem((ht, s_p, d_v), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_grouped_kernel, scale=scale, bs=bs,
                               nb=nb, s=s_p, sinks=has_sinks)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s_p, d_v), q.dtype),
        compiler_params=_cparams("parallel", "arbitrary"),
        interpret=interpret,
    )(lengths, slot, blk, phys, n_live, q, k_arena, v_arena,
      *([sinks] * has_sinks))


def paged_decode_attention(q, k_arena, v_arena, block_tables, lengths,
                           scale=None, max_steps=None, sinks=None):
    """Attention of q [b, h, s, d] over a PAGED cache: per-request block
    tables [b, max_blocks] of physical block ids into shared arenas
    k_arena [n_blocks, h_kv, d, block_size] and v_arena [n_blocks, h_kv,
    d_v, block_size] (d_v = d in most nets). `lengths` [b] is each
    request's cache fill count BEFORE this chunk (the chunk's k/v must
    already be written into the arena — nn/kv_pool.write_kv; the form
    that writes a decode step's tokens itself is `paged_write_attend`,
    below). Row r of
    batch i attends to logical cache cols <= lengths[i] + r. Block-table
    entries past the allocation MUST be 0 (the pool's reserved trash
    block): an empty slot's one work item, and a chunk that runs past its
    table, must land on a valid physical row. Eval-only (no vjp); returns
    [b, h, s, d_v] in q's dtype.

    h = G x h_kv, G > 1, is grouped-query attention and takes one token a
    slot (s = 1): query head j reads key-value head j // G, the G query
    heads of a key-value head are the rows of ONE product over that
    head's block (padded to the sublane tile), each K/V block is fetched
    once for its whole group, and every row attends cols <= lengths[i].

    Both forms walk a work list of the live (slot, block) pairs
    (`_paged_work_list`; the multi-head form's grid ends at their count);
    `max_steps` bounds them where the caller can
    (nn/kv_pool.paged_attention: a pool's block belongs to one slot),
    else the list is as long as the tables.

    `sinks` [h] float32, the grouped form only: query head j's learned
    sink logit joins its softmax's denominator and adds no value
    (`_paged_grouped_kernel`): p_t = exp(s_t) / (exp(sink_j) + sum_t'
    exp(s_t'))."""
    b, h, s, d = q.shape
    hl, d_v = k_arena.shape[1], v_arena.shape[2]
    group = paged_group(h, hl)
    if v_arena.shape[:2] + v_arena.shape[3:] \
            != k_arena.shape[:2] + k_arena.shape[3:] \
            or k_arena.shape[2] != d or not group or (group > 1 and s != 1) \
            or (sinks is not None and (group < 2 or sinks.shape != (h,))):
        raise ValueError(
            f"paged_decode_attention: arena shapes k{tuple(k_arena.shape)} "
            f"v{tuple(v_arena.shape)} don't match q{tuple(q.shape)}"
            + ("" if sinks is None else f" with sinks{tuple(sinks.shape)}"))
    bs = k_arena.shape[3]
    if bs % 8 != 0 or bs < 8:
        raise ValueError(
            f"paged_decode_attention: block_size {bs} must be a multiple "
            "of the 8-row sublane tile")
    if scale is None:
        scale = d ** -0.5
    out_dtype = q.dtype
    if q.dtype != k_arena.dtype:
        q = q.astype(k_arena.dtype)
    if group > 1:          # [b, G h_kv, 1, d] -> [b, h_kv, G, d]
        q = q.reshape(b, hl, group, d)
    rows = q.shape[2]

    s_p = _ceil_to(rows, 8)  # sublane tile: pad query rows, slice back below
    if s_p != rows:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, s_p - rows), (0, 0)))
    # lengths WITH the chunk's tokens (the kernels recover the fill):
    # the s positions of a chunk, or the one of a group's rows. Padded
    # rows attend a few cols past the live end: garbage rows, sliced off
    # below, that bring no block into the work list
    lens = jnp.asarray(lengths, jnp.int32)
    lens = jnp.broadcast_to(lens.reshape(-1), (b,)) \
        + jnp.int32(1 if group > 1 else s)
    bt = jnp.asarray(block_tables, jnp.int32)
    if sinks is not None:      # [h] -> [h_kv, G padded, 1]
        sinks = jnp.pad(jnp.asarray(sinks, jnp.float32).reshape(hl, group),
                        ((0, 0), (0, s_p - rows)),
                        constant_values=NEG_INF)[..., None]
    steps = paged_list_steps(b, bt.shape[1], max_steps)
    if group > 1:
        out = _paged_grouped_call_once(
            q, k_arena, v_arena, bt, lens, sinks, scale=float(scale),
            interpret=_interpret(), steps=steps)
    else:
        out = _paged_call_once(q, k_arena, v_arena, bt, lens,
                               scale=float(scale), rows=s, steps=steps,
                               interpret=_interpret())
    out = out.astype(out_dtype)
    out = out[:, :, :rows] if s_p != rows else out
    return out.reshape(b, h, s, d_v) if group > 1 else out


def paged_write_attend(q, k_arena, v_arena, block_tables, lengths, new_k,
                       new_v, scale=None, max_steps=None):
    """`paged_decode_attention` for one token a slot on the multi-head
    form (q [b, h, 1, d], h the arenas' heads) that ALSO writes the
    step's tokens: -> (out [b, h, 1, d_v], k_arena, v_arena), the arenas
    updated in place (PR 47). `new_k` [h, d, b] and `new_v` [h, d_v, b]
    are the tokens, dense with the slots in the lanes, in the arenas'
    dtype; slot i's go to lane lengths[i] % block_size of its logical
    block lengths[i] // block_size, the trash block where that is past
    its table, put there by the grid step that holds that block
    (`_paged_decode_attn_kernel` with `write`). What is attended is what
    `nn/kv_pool.write_kv` would have left: output and arenas are those
    of the writer twice and then `paged_decode_attention`, bit for bit
    (but for the trash block, whose content no one may rely on). Two
    slots may share a physical row only if it is the trash block (the
    pool's invariant, the token writer's condition too): a slot's block
    is written back while the next slot's are fetched. The caller asks
    `paged_write_attend_cut` first; table entries past the allocation
    are 0, and `max_steps` bounds the work list, as there."""
    b, h, s, d = q.shape
    d_v, bs = v_arena.shape[2], k_arena.shape[3]
    if s != 1 or k_arena.shape[1:3] != (h, d) \
            or v_arena.shape[:2] + v_arena.shape[3:] \
            != k_arena.shape[:2] + k_arena.shape[3:] \
            or new_k.shape != (h, d, b) or new_v.shape != (h, d_v, b):
        raise ValueError(
            f"paged_write_attend: tokens k{tuple(new_k.shape)} "
            f"v{tuple(new_v.shape)} and arenas k{tuple(k_arena.shape)} "
            f"v{tuple(v_arena.shape)} are not one token a slot, one query "
            f"head a key-value head, for q{tuple(q.shape)}")
    s_p = _paged_rows(1, s)     # the sublane tile of padded query rows
    q_p = jnp.pad(q.astype(k_arena.dtype),
                  ((0, 0), (0, 0), (0, s_p - s), (0, 0)))
    # the kernel takes the fill with the step's token, and rotates whole
    # 128-lane tiles of slots
    lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1),
                            (b,)) + jnp.int32(s)
    bt = jnp.asarray(block_tables, jnp.int32)
    pad = ((0, 0), (0, 0), (0, -b % 128))
    out, k_arena, v_arena = _paged_call_once(
        q_p, k_arena, v_arena, bt, lens,
        jnp.pad(new_k, pad), jnp.pad(new_v, pad),
        scale=float(d ** -0.5 if scale is None else scale), rows=s,
        steps=paged_list_steps(b, bt.shape[1], max_steps),
        interpret=_interpret())
    return out.astype(q.dtype)[:, :, :s], k_arena, v_arena


# --------------------------------------------------------------------------
# latent variant: every head over ONE cached vector a token
# --------------------------------------------------------------------------
#
# A latent-attention (MLA) layer in its absorbed form caches one `dim`-wide
# vector a token, shared by every query head: the arena is [n, 1, dim, bs],
# a block [dim, bs] with the tokens in the lanes. The keys are the whole
# block, the values its first `value_dim` rows. So the needs are the
# reverse of the kernel above: there a head is a batch dimension with its
# own keys and `s` query rows; here the HEADS are the rows of one product,
# q [h, dim] @ block [dim, bs], with no transpose, and the values are
# already in VMEM when the scores are done. A path of its own, which shares
# the gate, the scalar prefetch and the trick of the index map with the
# pair above, and no kernel body.
#
# The cut. A bf16 block of the Kimi share (576 x 128) is 147 KB, 0.18 us
# of HBM time, and ~8 of a slot's 24 table blocks are live. A step
# takes `blocks_per_step` logical blocks of its slot: the arena is passed
# that many times, operand g under an index map of its own (logical block
# ik * G + g). What the index maps read is a plan made outside the kernel
# from tables and lengths (`_latent_block_plan`): a live block's physical
# id, and for a dead one the id that operand fetched LAST, in grid order,
# across slots too. A repeated block index is no DMA, so a call reads each
# live block once and nothing else, however wide the table is; the dead
# blocks' products are skipped by `pl.when`.

def _latent_paged_attn_kernel(len_ref, plan_ref, q_ref, *rest, scale, bs,
                              nb, nk, group, value_dim):
    """Grid (b, nk): step (ib, ik) holds slot ib's q as rows [h, dim] and
    its logical blocks ik * group + g, g < group, each [dim, bs]. len_ref
    [b]: columns <= len_ref[ib] are live (the step's own token is
    written). plan_ref is consumed by the index maps.

    A step is two passes over its live blocks, so that no block waits
    for the softmax of the one before: the scores of each (kept in
    sc_scr) and their running maximum element by element; then ONE new
    row maximum and rescaling of the state; then each block's
    probabilities, their sum element by element, and its product with
    the values. The reductions along the lanes are two a step."""
    blocks, o_ref = rest[:group], rest[group]
    m_scr, l_scr, acc_scr, sc_scr, red_scr = rest[group + 1:]
    ib, ik = pl.program_id(0), pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # np.int32 / np.float32 scalars: see _decode_attn_kernel
    length = len_ref[ib]
    last = jnp.minimum(jnp.maximum(length, np.int32(0)) // np.int32(bs),
                       np.int32(nb - 1))       # last live logical block
    first = ik * np.int32(group)               # the step's first block
    red_scr[:] = jnp.full_like(red_scr, NEG_INF)

    for g, blk_ref in enumerate(blocks):
        j = first + np.int32(g)

        @pl.when(j <= last)
        def _scores(g=g, j=j, blk_ref=blk_ref):
            sc = jax.lax.dot_general(
                q_ref[0], blk_ref[0, 0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * np.float32(scale)
            col = j * np.int32(bs) + jax.lax.broadcasted_iota(
                jnp.int32, sc.shape, 1)
            sc = jnp.where(col <= length, sc, np.float32(NEG_INF))
            sc_scr[g] = sc                     # [h, bs] f32
            red_scr[:] = jnp.maximum(red_scr[:], sc)

    @pl.when(first <= last)
    def _rescale():
        m_prev = m_scr[:]                      # [h, 1]
        m_new = jnp.maximum(
            m_prev, jnp.max(red_scr[:], axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha
        acc_scr[:] = acc_scr[:] * alpha
        red_scr[:] = jnp.zeros_like(red_scr)   # now the sum of p

    for g, blk_ref in enumerate(blocks):
        j = first + np.int32(g)

        @pl.when(j <= last)
        def _values(g=g, blk_ref=blk_ref):
            p = jnp.exp(sc_scr[g] - m_scr[:])  # [h, bs] f32
            red_scr[:] = red_scr[:] + p
            # p [h, bs] against the values [value_dim, bs]: both contract
            # their lanes, the tokens
            acc_scr[:] = acc_scr[:] + jax.lax.dot_general(
                p.astype(blk_ref.dtype), blk_ref[0, 0, :value_dim],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(first <= last)
    def _sum():
        l_scr[:] = l_scr[:] + jnp.sum(red_scr[:], axis=-1, keepdims=True)

    @pl.when(ik == nk - 1)
    def _flush():
        denom = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)


# The most logical blocks a grid step of the latent kernel takes. Measured
# on a v5e at the Kimi share's decode step (64 slots, 24-block tables, ~8
# live blocks a slot, bf16; PERF.md section 6, PR 38): alone, 1 / 4 / 8 / 12
# blocks a step all read 0.36-0.38 ms a call, because a step whose blocks
# are dead fetches nothing and skips its body, and 24 (the whole table: no
# state carried between steps, a slot's fetches in flight together)
# 0.30-0.31; in the cell 24 is worth 2 % of its tokens a second over 8.
_LATENT_BLOCKS_PER_STEP = 24


def _latent_step_bytes(group, h, dim, value_dim, bs, itemsize):
    """VMEM bytes of one grid step over `group` blocks, lanes padded to
    128: the blocks and q and out double-buffered, the softmax state, the
    blocks' scores and three more of a block's in float32."""
    bs_l = _ceil_to(bs, 128)
    blocks = 2 * group * dim * bs_l * itemsize
    qo = 2 * h * (_ceil_to(dim, 128) + _ceil_to(value_dim, 128)) * itemsize
    state = h * (128 + 128 + _ceil_to(value_dim, 128)) * 4
    scores = (group + 3) * h * bs_l * 4
    return blocks + qo + state + scores


def latent_paged_blocks_per_step(h, dim, value_dim, bs, table_blocks,
                                 itemsize) -> int:
    """The latent kernel's cut: how many logical blocks of a slot one
    grid step takes. As many as fit `_ATTN_VMEM_BYTES` of VMEM, at most
    `_LATENT_BLOCKS_PER_STEP`, then evened out over the steps a table
    of `table_blocks` needs (24 blocks: one step; 40: 2 steps of 20); 0
    where not even one block fits."""
    fit = max((group for group in range(1, _LATENT_BLOCKS_PER_STEP + 1)
               if _latent_step_bytes(group, h, dim, value_dim, bs,
                                     itemsize) <= _ATTN_VMEM_BYTES),
              default=0)
    if not fit:
        return 0
    nb = max(int(table_blocks), 1)
    return -(-nb // -(-nb // fit))


def latent_paged_supported(q_shape, arena_shape, itemsize,
                           value_dim) -> bool:
    """Static predicate: can the latent kernel serve q [b, h, s, dim]
    over an arena [n_blocks, 1, dim, block_size] of `itemsize`-byte
    elements? One query row a slot (the decode step), one arena head,
    the block whole lane tiles, the values a prefix of the keys that ends
    on a sublane tile, and a step of one block within the VMEM budget."""
    if len(q_shape) != 4 or len(arena_shape) != 4:
        return False
    b, h, s, dim = q_shape
    nb_phys, heads, dim_a, bs = arena_shape
    if s != 1 or heads != 1 or dim_a != dim or nb_phys < 1 or b < 1:
        return False
    if bs < 128 or bs % 128 != 0:
        return False
    sublane = 32 // int(itemsize)              # 8 rows of 32 bits a tile
    if value_dim < 1 or value_dim > dim or value_dim % sublane != 0:
        return False
    return latent_paged_blocks_per_step(h, dim, value_dim, bs, 1,
                                        itemsize) > 0


def latent_paged_cut(q_shape, arena_shape, table_blocks, itemsize,
                     value_dim) -> dict:
    """How a supported call is cut: `blocks_per_step` logical blocks of a
    slot a grid step, `grid_steps` = slots x the steps a table needs, and
    `live_bytes`, what the call reads from the arena for each live block
    of a slot (a block as laid out; dead blocks cost no bytes). The Kimi
    share's decode step (64 slots, 64 heads, 576 wide, 24-block tables
    of 128, bf16): the table's 24 blocks a step, 64 steps, 147 456 B a
    live block."""
    b, h, _, dim = q_shape
    bs = arena_shape[3]
    group = latent_paged_blocks_per_step(h, dim, value_dim, bs,
                                         table_blocks, itemsize)
    return {"blocks_per_step": group,
            "grid_steps": b * -(-int(table_blocks) // group),
            "live_bytes": dim * _ceil_to(bs, 128) * itemsize}


def _latent_block_plan(block_tables, lengths, bs, group):
    """What the latent kernel's index maps read: [group * b * nk] i32,
    operand-major, the physical block operand g holds at grid step
    (slot, ik). Where logical block ik * group + g of the slot is live,
    its id from the table; where it is dead, the id the operand held at
    the step before (in grid order, the slot before's too): the index
    repeats and Pallas fetches nothing. Before an operand's first live
    block: the trash block, one fetch a call."""
    b, nb = block_tables.shape
    nk = -(-nb // group)
    last = jnp.clip(lengths // jnp.int32(bs), 0, nb - 1)          # [b]
    j = jnp.arange(nk * group, dtype=jnp.int32)                   # logical
    live = (j[None] <= last[:, None]).reshape(b * nk, group)
    phys = jnp.take(block_tables, jnp.minimum(j, nb - 1),
                    axis=1).reshape(b * nk, group)
    step = jnp.arange(b * nk, dtype=jnp.int32)[:, None]
    held = jax.lax.cummax(jnp.where(live, step, jnp.int32(-1)), axis=0)
    plan = jnp.where(held >= 0,
                     jnp.take_along_axis(phys, jnp.maximum(held, 0), axis=0),
                     jnp.int32(0))
    return plan.T.reshape(-1)


# jitted under its own name, like the pair's calls above: a program's
# layers trace it once, and a device trace lists the kernel under it
@functools.partial(jax.jit, static_argnames=("scale", "value_dim",
                                             "interpret"))
def _latent_paged_call_once(q, arena, block_tables, lengths, *, scale,
                            value_dim, interpret):
    from jax.experimental.pallas import tpu as pltpu
    b, h, dim = q.shape
    bs, nb = arena.shape[3], block_tables.shape[1]
    group = latent_paged_blocks_per_step(h, dim, value_dim, bs, nb,
                                         arena.dtype.itemsize)
    nk = -(-nb // group)
    plan = _latent_block_plan(block_tables, lengths, bs, group)

    def q_map(ib, ik, len_ref, plan_ref):
        return (ib, _Z, _Z)

    def block_map(g):
        first = np.int32(g * b * nk)

        def index(ib, ik, len_ref, plan_ref):
            return (plan_ref[first + ib * np.int32(nk) + ik], _Z, _Z, _Z)
        return index

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nk),
        in_specs=[pl.BlockSpec((1, h, dim), q_map)]
        + [pl.BlockSpec((1, 1, dim, bs), block_map(g))
           for g in range(group)],
        out_specs=pl.BlockSpec((1, h, value_dim), q_map),
        scratch_shapes=[
            _vmem((h, 1), jnp.float32),
            _vmem((h, 1), jnp.float32),
            _vmem((h, value_dim), jnp.float32),
            _vmem((group, h, bs), jnp.float32),
            _vmem((h, bs), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _latent_paged_attn_kernel, scale=scale, bs=bs, nb=nb, nk=nk,
        group=group, value_dim=value_dim)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, value_dim), q.dtype),
        compiler_params=_cparams("parallel", "arbitrary"),
        interpret=interpret,
    )(lengths, plan, q, *([arena] * group))


def latent_paged_decode_attention(q, arena, block_tables, lengths, scale,
                                  value_dim):
    """Attention of q [b, h, 1, dim], every head over the ONE cached
    vector a token of a paged latent arena [n_blocks, 1, dim,
    block_size]: keys are a block's whole `dim` rows, values its first
    `value_dim`. `lengths` [b] is each slot's fill BEFORE this step's
    token, which must already be written (nn/kv_pool.write_kv): slot i
    attends logical columns <= lengths[i]. Table entries past the
    allocation MUST be 0 (the trash block). Operands in the arena's
    dtype, products accumulated in float32, softmax in float32, the
    probabilities rounded to the arena's dtype before the second
    product: `nn/kv_pool._latent_attn_paged`'s arithmetic, its softmax
    online across the grid steps of a table too wide for one. Eval-only (no vjp); returns [b, h, 1, value_dim] in
    q's dtype."""
    b, h, s, dim = q.shape
    if s != 1 or arena.shape[1] != 1 or arena.shape[2] != dim:
        raise ValueError(
            f"latent_paged_decode_attention: q{tuple(q.shape)} needs one "
            f"query row and a one-head arena {dim} wide, got "
            f"{tuple(arena.shape)}")
    out = _latent_paged_call_once(
        q[:, :, 0].astype(arena.dtype), arena,
        jnp.asarray(block_tables, jnp.int32),
        jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1), (b,)),
        scale=float(scale), value_dim=int(value_dim),
        interpret=_interpret())
    return out[:, :, None].astype(q.dtype)


# The decode step's write: one token per slot into the same arena, as a
# kernel because XLA's form of it (read the slot's block, select, write it
# back, in a loop over slots) runs the slots one after another — 0.26 ms a
# layer at 32 slots on a v5e against 0.08 ms here for a layer's two calls,
# where the grid's pipeline fetches slot i+1's block while slot i's is
# written back (PR 26). That figure left out what the operand cost: the
# kernel took a slot's token as a [1, h, d, 1] block, and a 4-d array whose
# minor dimension is 1 is one element a 128-lane row, so XLA laid the 102 KB
# of GPT-2 XL's 32 tokens out as 13 MB before every call (`reshape`, 2 x
# 36.5 us a layer beside the writer's 45.5 + 41.2: 160 us a layer, 7.7 ms of
# a 15.7 ms decode step). Since PR 34 the tokens come dense, [h, d, slots]
# with the slots in the lanes, and the kernel brings its slot's lane to the
# lane it writes: a call measured alone 55 -> 46 us (45 is the blocks' round
# trip), and GPT-2 XL's decode step 15.7 -> 13.4 ms.

def _paged_write_kernel(row_ref, off_ref, tok_ref, blk_ref, out_ref):
    """Grid (b,): step i holds slot i's block [1, h, d, bs], aliased in
    and out, beside the step's tokens [h, d, slots padded to 128s]
    (resident: their block index never changes, so they are fetched once
    a call), and puts column i of the tokens in lane off[i] of the block.

    The column gets there by a lane rotation of the 128 slots around
    slot i, by off[i] - i: data is moved, never computed with, so
    whatever a token's bits say (-0.0, inf, a NaN) they arrive as they
    are, and no other slot's can leak into the block. Mosaic rotates
    32-bit lanes only, hence through float32 (exact for the arena's
    narrower floats)."""
    from jax.experimental.pallas import tpu as pltpu
    i = pl.program_id(0)
    off = off_ref[i]
    lanes, bs = np.int32(128), blk_ref.shape[3]
    tok = tok_ref[:, :, pl.ds(pl.multiple_of(i // lanes * lanes, 128), 128)]
    tok = pltpu.roll(tok.astype(jnp.float32),
                     (off % lanes - i % lanes + lanes) % lanes, 2)
    tok = tok.astype(tok_ref.dtype)
    # the block's lanes beside the 128 rotated ones: a prefix of them, or
    # whole copies (slot i now sits in lane off % 128 of every copy)
    tok = tok[:, :, :bs] if bs <= 128 else jnp.tile(tok, (1, 1, bs // 128))
    lane = jax.lax.broadcasted_iota(jnp.int32, blk_ref.shape[1:], 2)
    out_ref[0] = jnp.where(lane == off, tok, blk_ref[0])


def _write_step_bytes(h, d, bs, slots, itemsize):
    """VMEM bytes of one grid step of the writer, lanes padded to 128:
    the slot's block twice in and twice out (double buffering), the
    resident tokens twice, and the float32 forms of the 128 slots the
    kernel rotates."""
    block = h * d * _ceil_to(bs, 128) * itemsize
    tokens = h * d * _ceil_to(slots, 128) * itemsize
    return 4 * block + 2 * tokens + 3 * h * d * 128 * 4


def paged_write_cut(arena_shape, slots, itemsize) -> dict:
    """What a supported call moves, as laid out on the device:
    `token_bytes` of the token operand [h, d, slots] (fetched once) and
    `block_bytes` of the slots' blocks, in and out."""
    _, h, d, bs = arena_shape
    return {"token_bytes": h * d * _ceil_to(slots, 128) * itemsize,
            "block_bytes": 2 * slots * h * d * _ceil_to(bs, 128) * itemsize}


def paged_write_supported(arena_shape, itemsize, slots) -> bool:
    """Static predicate: do a slot's whole block and the tokens of
    `slots` slots fit the writer's VMEM budget? [n_blocks, h, d,
    block_size] arenas of at most 32-bit floats only, the block a
    sublane-tile multiple inside one 128-lane tile or whole tiles."""
    if len(arena_shape) != 4 or int(itemsize) > 4 or slots < 1:
        return False
    _, h, d, bs = arena_shape
    if bs % 8 != 0 or (bs > 128 and bs % 128 != 0):
        return False
    return _write_step_bytes(h, d, bs, slots,
                             int(itemsize)) <= _ATTN_VMEM_BYTES


def paged_write_token(arena, rows, offsets, tokens):
    """arena [n_blocks, h, d, bs] with tokens[:, :, i] ([h, d, b], the
    arena's dtype) written to lane offsets[i] of physical row rows[i]
    (both [b] i32), in place. Two slots may share a row only if it is the
    trash block: a step reads its block before the step before it has
    written its own back."""
    # the kernel rotates whole 128-lane tiles of slots
    tokens = jnp.pad(tokens, ((0, 0), (0, 0), (0, -tokens.shape[2] % 128)))
    return _paged_write_once(arena, rows, offsets, tokens,
                             interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_write_once(arena, rows, offsets, tokens, *, interpret):
    from jax.experimental.pallas import tpu as pltpu
    _, h, d, bs = arena.shape
    b = rows.shape[0]

    def tok_map(i, row_ref, off_ref):
        return (_Z, _Z, _Z)

    def blk_map(i, row_ref, off_ref):
        return (row_ref[i], _Z, _Z, _Z)

    blk = pl.BlockSpec((1, h, d, bs), blk_map)
    return pl.pallas_call(
        _paged_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec(tokens.shape, tok_map), blk],
            out_specs=blk),
        out_shape=jax.ShapeDtypeStruct(arena.shape, arena.dtype),
        input_output_aliases={3: 0},  # operands: rows, offsets, tokens, arena
        compiler_params=_cparams("arbitrary"),
        interpret=interpret,
    )(rows, offsets, tokens, arena)


def decode_attention(q, kc, vc, index, scale=None, block_k=None):
    """Attention of q [b, h, s, d] over a partially-filled cache
    kc/vc [b, h, L, d]. `index` is the cache fill count before this chunk
    — an i32 scalar (StaticKVCache.index) or a [b] vector for ragged
    per-batch fills. Row r of the chunk attends to cache cols
    <= index + r. Returns [b, h, s, d] in q's dtype. Eval-only (no vjp).
    """
    b, h, s, d = q.shape
    L = kc.shape[2]
    if vc.shape != kc.shape or kc.shape[3] != d:
        raise ValueError(f"decode_attention: cache shapes k{tuple(kc.shape)}"
                         f" v{tuple(vc.shape)} don't match q{tuple(q.shape)}")
    if scale is None:
        scale = d ** -0.5
    out_dtype = q.dtype
    if q.dtype != kc.dtype:
        q = q.astype(kc.dtype)  # keep both matmuls on one MXU dtype

    s_p = _ceil_to(s, 8)   # sublane tile: pad query rows, slice back below
    if s_p != s:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, s_p - s), (0, 0)))
    # lengths are in PADDED-row terms (the kernel recovers the fill count
    # as length - s_p); padded rows attend a few cols past the live end —
    # they are garbage rows sliced off below
    lengths = jnp.asarray(index, jnp.int32)
    lengths = jnp.broadcast_to(lengths.reshape(-1), (b,)) + jnp.int32(s_p)
    L_p = _ceil_to(L, 8)
    if L_p != L:
        # ragged caches only appear in tests; padded cols are dead because
        # lengths <= L never reaches them
        kc = jnp.pad(kc, ((0, 0), (0, 0), (0, L_p - L), (0, 0)))
        vc = jnp.pad(vc, ((0, 0), (0, 0), (0, L_p - L), (0, 0)))

    def measure_builder():
        def measure(params):
            from . import autotune
            (bk_,) = params
            # measure at full cache length — the worst case every long
            # generation converges to; synthetic zeros (tracer-safe)
            qz = jnp.zeros(q.shape, q.dtype)
            kz = jnp.zeros(kc.shape, kc.dtype)
            lz = jnp.full((b,), L_p, jnp.int32)
            fn = jax.jit(lambda a, k_, v_, ln: _call(a, k_, v_, ln,
                                                     float(scale), bk_))
            return autotune.time_thunk(lambda: fn(qz, kz, kz, lz))
        return measure

    if block_k:
        bk = int(block_k)
        if L_p % bk != 0:
            # a non-divisor would floor-truncate the grid and silently
            # drop tail cache blocks from attention
            raise ValueError(f"decode_attention: block_k={bk} does not "
                             f"divide the padded cache length {L_p}")
    else:
        bk = _pick_bk((b, h, s_p, d, L_p), str(q.dtype), scale,
                      measure_builder)
    out = _call(q, kc, vc, lengths, scale, bk)
    out = out.astype(out_dtype)
    return out[:, :, :s] if s_p != s else out
