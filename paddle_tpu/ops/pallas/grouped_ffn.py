"""The held experts' SwiGLU products as one pipelined Pallas kernel.

`nn/layer/experts.py` sorts a batch's (token, expert) pairs by expert and
cuts them into row blocks of one expert each. Its plain form runs a
`fori_loop` over the blocks in use: every pass slices three matrices of
its expert out of `[n, H, I]` and multiplies, and XLA pipelines nothing
across the passes of a `while`, so each pass opens three weight streams
from nothing and closes them (0.146 ms a pass against a 0.092 ms floor at
the LongCat share's shapes on a v5e, PERF.md section 5).

Here the blocks are the first axis of ONE grid and the expert width `I`,
cut into tiles of `tile` columns, the second. Step (j, t) holds tile t of
block j's expert: `gate[e][:, tile]`, `up[e][:, tile]` and
`down[e][tile, :]`, picked by index maps that read the block -> expert
map through scalar prefetch. Pallas fetches step (j, t + 1)'s tiles, or
step (j + 1, 0)'s, the next expert's, while step (j, t) multiplies: the
weight stream never stops between blocks or experts. The grid is static,
`ceil(P / M) + n` blocks (the rule of the layer's docstring); a block not
in use repeats the index of the last live step, so nothing is fetched
for it, and `pl.when` skips its body.

What a block does, in the arithmetic of `experts._swiglu` and `one_block`:
its rows' tokens are gathered from `x`, which stays in VMEM whole, by a
0/1 matrix on the MXU (exact: one term a row); a tile gives
`silu(xs @ gate) * (xs @ up)` in float32, rounded to x's dtype, times the
`down` tile, accumulated in float32 over the tiles; the last tile's step
multiplies each row by its pair's weight in float32 and adds the rows to
their tokens' rows of `y [T, H]` float32, which stays in VMEM until the
grid ends, by the transposed 0/1 matrix against the rows' three bf16
pieces (each row exact, so only the order in which a token's pairs are
summed differs from the plain form). So nothing padded crosses HBM: no
gathered copy of x, no scattered rows of y. The 0/1 matrices are made in
the kernel from each (token, choice) pair's row in the blocks' order,
which array code outside makes by counting, with the counts and the
block -> expert map.

Two things bound the kernel. `x` and `y` whole in VMEM: `grouped_ffn_tile`
gives the widest tile (a multiple of 128 that divides `I`) under
`_FFN_VMEM_BYTES`, or 0 where none fits
(`pallas.gate_reject.grouped_expert_ffn.vmem` in the layer's gate). And
a block's work on `y`, which grows with the tokens while its weight bytes
do not: `GROUPED_FFN_MAX_TOKENS` (`...tokens`).

On a v5e (PERF.md section 6, PR 40) a layer of the LongCat share's decode
step reads 1.76 ms against the loop's 2.34, and the same time with every
product taken out: the kernel is bound by its fetches, at ~690 GB/s.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .flash_attention import _Z, _ceil_to, _interpret, _vmem

__all__ = ["grouped_ffn", "grouped_ffn_tile", "grouped_ffn_cut",
           "grouped_ffn_blocks", "GROUPED_FFN_MAX_TOKENS"]

# What a grid step may hold of a v5e core's 128 MiB of VMEM: the three
# weight tiles double-buffered, x and y whole, a block's rows and its
# float32 sum, and the step's float32 temporaries. The compiler's own
# default (16 MiB) is raised to this for the call.
_FFN_VMEM_BYTES = 96 << 20
_FFN_TILE_MAX = 256
# rows of y a pass of the scatter takes: bounds its float32 temporaries
_SCATTER_ROWS = 128
# The most tokens a call may bring. A block adds its rows to y by a
# product over ALL of y's rows, so a block's work on y grows with T while
# its weight bytes do not: on a v5e (PERF.md section 6, PR 40) a layer at
# the Kimi share's widths reads 1.58 / 1.69 ms at 256 / 512 tokens against
# the loop's 2.04 / 2.29, and 3.13 ms at 1024 against 2.11.
GROUPED_FFN_MAX_TOKENS = 512


def grouped_ffn_blocks(tokens, top_k, count, rows):
    """The static bound on the row blocks: `P = tokens * top_k` pairs at
    most are held, an expert's rows are padded to a whole block."""
    return -(-(int(tokens) * int(top_k)) // int(rows)) + int(count)


def _ffn_step_bytes(T, H, tile, rows, itemsize):
    """VMEM bytes of one grid step: the three weight tiles double-
    buffered, x and y whole and single-buffered, the block's rows and
    their float32 sum, and what the step's values take: a tile's
    products, the gathered rows and the down product in float32, the
    weighted rows with their three bf16 pieces, and a pass of the
    scatter."""
    T = _ceil_to(T, 16)
    tiles = 2 * 3 * H * tile * itemsize
    xy = T * H * (itemsize + 4)
    block = rows * H * (itemsize + 4)
    temps = rows * tile * (8 + itemsize) + rows * H * (3 * 4 + 3 * 2) \
        + 4 * min(T, _SCATTER_ROWS) * H * 4
    onehot = rows * _ceil_to(T, 128) * (4 + itemsize)
    return tiles + xy + block + temps + onehot


def grouped_ffn_tile(T, H, I, rows, itemsize) -> int:
    """The kernel's cut of the expert width: the widest tile, a multiple
    of 128 that divides `I` and is at most `_FFN_TILE_MAX`, whose step
    fits `_FFN_VMEM_BYTES`; 0 where none does."""
    for tile in range(min(_FFN_TILE_MAX, int(I)) // 128 * 128, 0, -128):
        if I % tile == 0 and _ffn_step_bytes(
                T, H, tile, rows, itemsize) <= _FFN_VMEM_BYTES:
            return tile
    return 0


def grouped_ffn_cut(T, top_k, count, H, I, rows, itemsize) -> dict:
    """How a supported call is cut: `rows_per_block`, `tile_bytes` (the
    three weight tiles a step fetches) and `grid_steps` (blocks x tiles,
    live or not)."""
    tile = grouped_ffn_tile(T, H, I, rows, itemsize)
    return {"rows_per_block": int(rows),
            "tile_bytes": 3 * H * tile * itemsize,
            "grid_steps": grouped_ffn_blocks(T, top_k, count, rows)
            * (I // tile)}


def _grouped_ffn_kernel(be_ref, nl_ref, x_ref, rowk_ref, rowt_ref, w_ref,
                        gate_ref, up_ref, down_ref, y_ref, xs_scr, acc_scr,
                        *, ni):
    """Grid (blocks, ni): step (j, t) holds tile t of block j's expert.
    be_ref / nl_ref (block -> expert, live blocks) are consumed by the
    index maps. rowk_ref [K, T] / rowt_ref [T, K]: the padded row of each
    (token, choice) pair in the blocks' order, -1 where the pair is not
    held, the tokens along the lanes and down the sublanes; w_ref [K, T]
    the pairs' weights. Block j's rows are j * M .. j * M + M - 1."""
    j, t = pl.program_id(0), pl.program_id(1)
    live = j < nl_ref[0]
    rows, tokens = xs_scr.shape[0], x_ref.shape[0]
    top_k = rowk_ref.shape[0]
    first_row = j * np.int32(rows)
    one, zero = np.float32(1), np.float32(0)

    def rows_by_tokens(values):
        """Σ over a token's choices of values[k] where the pair sits in a
        row of this block: [rows, tokens] float32 (a token's choices are
        distinct experts, so at most one term an element)."""
        row = first_row + jax.lax.broadcasted_iota(
            jnp.int32, (rows, tokens), 0)
        return sum(jnp.where(rowk_ref[k:k + 1, :] == row, values(k), zero)
                   for k in range(top_k))

    @pl.when((j == 0) & (t == 0))
    def _zero():
        y_ref[:] = jnp.zeros_like(y_ref)

    @pl.when(live & (t == 0))
    def _gather():
        # a 0/1 matrix times x: one term a row, exact (float32 operands
        # need `highest` for that)
        pick = rows_by_tokens(lambda k: one).astype(x_ref.dtype)
        xs_scr[:] = jnp.dot(
            pick, x_ref[:], preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST
            if x_ref.dtype == jnp.float32 else None).astype(xs_scr.dtype)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(live)
    def _tile():
        xs = xs_scr[:]
        g = jnp.dot(xs, gate_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(xs, up_ref[0], preferred_element_type=jnp.float32)
        a = (jax.nn.silu(g) * u).astype(xs.dtype)
        acc_scr[:] += jnp.dot(a, down_ref[0],
                              preferred_element_type=jnp.float32)

    @pl.when(live & (t == ni - 1))
    def _scatter():
        weight = jnp.sum(rows_by_tokens(lambda k: w_ref[k:k + 1, :]),
                         axis=1, keepdims=True)                # [rows, 1]
        # v = hi + mid + lo exactly (3 x 8 bits of mantissa), and a 0/1
        # matrix times a bf16 piece is exact on the MXU: three bf16
        # passes where a float32 product at `highest` takes six
        v = acc_scr[:] * weight
        hi = v.astype(jnp.bfloat16)
        rest = v - hi.astype(jnp.float32)
        mid = rest.astype(jnp.bfloat16)
        lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
        for c in range(0, tokens, _SCATTER_ROWS):
            size = min(_SCATTER_ROWS, tokens - c)
            row = first_row + jax.lax.broadcasted_iota(
                jnp.int32, (size, rows), 1)
            put = sum(jnp.where(rowt_ref[c:c + size, k:k + 1] == row, one,
                                zero) for k in range(top_k)
                      ).astype(jnp.bfloat16)
            y_ref[c:c + size, :] += (
                jnp.dot(put, hi, preferred_element_type=jnp.float32)
                + jnp.dot(put, mid, preferred_element_type=jnp.float32)
                + jnp.dot(put, lo, preferred_element_type=jnp.float32))


# jitted under a name of its own: a program's layers trace it once, and a
# device trace lists the kernel under it
@functools.partial(jax.jit, static_argnames=("rows", "tile", "interpret"))
def _grouped_ffn_call(x, pair_row, w, blk_expert, n_live, gate, up, down, *,
                      rows, tile, interpret):
    from jax.experimental.pallas import tpu as pltpu
    T, H = x.shape
    K = pair_row.shape[1]
    nb = blk_expert.shape[0]
    ni = gate.shape[2] // tile
    last = np.int32(ni - 1)

    def tile_of(j, t, nl_ref):                 # a dead block fetches nothing
        return jnp.where(j < nl_ref[0], t, last)

    def gate_map(j, t, be_ref, nl_ref):
        return (be_ref[j], _Z, tile_of(j, t, nl_ref))

    def down_map(j, t, be_ref, nl_ref):
        return (be_ref[j], tile_of(j, t, nl_ref), _Z)

    def whole(j, t, be_ref, nl_ref):
        return (_Z, _Z)

    def resident(shape):                       # fetched once, one buffer
        return pl.BlockSpec(shape, whole, pipeline_mode=pl.Buffered(1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, ni),
        in_specs=[resident((T, H)), resident((K, T)), resident((T, K)),
                  resident((K, T)),
                  pl.BlockSpec((1, H, tile), gate_map),
                  pl.BlockSpec((1, H, tile), gate_map),
                  pl.BlockSpec((1, tile, H), down_map)],
        out_specs=resident((T, H)),
        scratch_shapes=[_vmem((rows, H), x.dtype),
                        _vmem((rows, H), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_grouped_ffn_kernel, ni=ni),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, H), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_FFN_VMEM_BYTES + (8 << 20)),
        interpret=interpret,
    )(blk_expert, n_live.reshape(1), x, pair_row.T, pair_row, w.T, gate, up,
      down)


def grouped_ffn(x, pair_row, w, blk_expert, n_live, gate, up, down, *, rows):
    """Σ over the row blocks in use of the rows' weighted SwiGLU, added
    to their tokens. x [T, H]; pair_row [T, K] i32: the row of each
    (token, choice) pair in the blocks' padded order (block j is rows
    j * rows .. j * rows + rows - 1), -1 where the pair is not held; w
    [T, K] f32 the pairs' weights; blk_expert [blocks] i32, a block's
    expert (a block past `n_live` names the last live block's); gate / up
    [n, H, I], down [n, I, H] in x's dtype. -> y [T, H] f32. Tokens are
    padded to a 16-row tile here; `H` and `I` must be multiples of 128
    and `grouped_ffn_tile` non-zero (the layer's gate sees to both)."""
    T, H = x.shape
    tile = grouped_ffn_tile(T, H, gate.shape[2], rows, x.dtype.itemsize)
    if not tile or H % 128:
        raise ValueError(
            f"grouped_ffn: x{tuple(x.shape)} over experts "
            f"{tuple(gate.shape)} has no cut under {_FFN_VMEM_BYTES} B")
    pad = _ceil_to(T, 16) - T
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        pair_row = jnp.pad(pair_row, ((0, pad), (0, 0)), constant_values=-1)
        w = jnp.pad(w, ((0, pad), (0, 0)))
    y = _grouped_ffn_call(
        x, pair_row.astype(jnp.int32), w.astype(jnp.float32),
        blk_expert.astype(jnp.int32), jnp.asarray(n_live, jnp.int32),
        gate, up, down, rows=int(rows), tile=tile, interpret=_interpret())
    return y[:T] if pad else y
