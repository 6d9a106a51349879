"""Continuous-batching serve tier: paged KV pool + block-table kernel +
admission scheduler (inference/serving.py, nn/kv_pool.py,
ops/pallas/decode_attention.paged_decode_attention).

THE proof: greedy continuous-batched decode — ragged prompts admitted
mid-flight, retiring early on EOS, evicted and replayed under pool
pressure — is TOKEN-IDENTICAL to per-request sequential GPT.generate.
Plus: block-table kernel parity vs the jnp gather fallback at several
fill levels, pool-exhaustion backpressure then admission-on-retire, and
an injected kernel crash failing the in-flight requests (no demotion)
while the serve loop itself survives.
"""
import importlib
import os
import sys
import threading

import jax.numpy as jnp
import numpy as np
import pytest
from span_util import self_times

import paddle_tpu as paddle
from paddle_tpu.core import monitor, trace
from paddle_tpu.inference import ServeConfig, ServeLoop
from paddle_tpu.nn.kv_pool import (KVBlockPool, PagedKVCache,
                                   paged_attention_ref, write_kv)
from paddle_tpu.text.models.gpt import GPT, GPTConfig

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import obs_report  # noqa: E402


@pytest.fixture(scope="module")
def net():
    paddle.seed(0)
    m = GPT(GPTConfig.tiny())
    m.eval()
    return m


@pytest.fixture
def interpret():
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    yield
    paddle.set_flags({"FLAGS_pallas_interpret": False})


def _ref_generate(net, prompt, n, eos=None):
    """Sequential single-request oracle: greedy generate, truncated at
    the first eos like the serve loop retires."""
    out = np.asarray(net.generate(
        paddle.to_tensor(np.asarray(prompt, np.int64)[None]),
        max_new_tokens=n, temperature=0, use_cache=True)
        .numpy())[0, len(prompt):]
    if eos is None:
        return out
    hits = np.where(out == eos)[0]
    return out[: hits[0] + 1] if hits.size else out


# --------------------------------------------------------------------------
# pool
# --------------------------------------------------------------------------

def test_pool_alloc_free_invariants():
    pool = KVBlockPool(4, 16)
    assert pool.free_blocks == 4 and pool.used_blocks == 0
    a = pool.alloc(3)
    assert len(a) == 3 and pool.used_blocks == 3
    assert 0 not in a, "trash block must never be allocated"
    assert pool.alloc(2) is None, "all-or-nothing alloc"
    assert pool.used_blocks == 3, "failed alloc must not leak"
    b = pool.alloc(1)
    assert pool.free_blocks == 0
    assert not pool.can_alloc(1)
    pool.free(a)
    assert pool.free_blocks == 3
    with pytest.raises(ValueError, match="double free"):
        pool.free([a[0]])
    with pytest.raises(ValueError, match="invalid block"):
        pool.free([0])
    pool.free(b)
    assert pool.blocks_for(0) == 0 and pool.blocks_for(1) == 1 \
        and pool.blocks_for(16) == 1 and pool.blocks_for(17) == 2


def test_pool_rejects_bad_block_size():
    with pytest.raises(ValueError, match="sublane"):
        KVBlockPool(4, 12)


# --------------------------------------------------------------------------
# block-table kernel parity vs the jnp fallback
# --------------------------------------------------------------------------

@pytest.mark.parametrize("h,d,s,bs", [
    # two heads at block sizes under, at and over a lane tile's sublane
    # count and at the lane width itself
    (2, 16, 1, 8), (2, 16, 1, 16), (2, 16, 1, 128),
    (2, 16, 8, 8), (2, 16, 8, 16), (2, 16, 8, 128),
    # head counts that are no power of two, all in one grid step
    (5, 64, 1, 128), (25, 64, 1, 128), (25, 64, 8, 16),
    # 256 query rows of 25 heads do not fit the VMEM budget: 5 head tiles
    (25, 64, 256, 128),
])
def test_paged_kernel_parity_fill_levels(interpret, h, d, s, bs):
    """Several fill levels across slots — an empty slot, one block partly
    full, one full, a partly full last block, a full table — kernel vs
    gather fallback. Every table but the full one ends in the trash
    block; the empty slot's is nothing else."""
    from paddle_tpu.ops.pallas.decode_attention import (
        paged_cut, paged_decode_attention, paged_supported)
    rng = np.random.RandomState(0)
    MB, NB = 4, 14
    pool = KVBlockPool(NB, bs)
    (ka, va), = pool.arenas(1, h, d)
    # 1 part (if it holds the chunk), 1 full, 3 part, 4 full blocks, none
    fills = [max(bs // 2 + 1, s), max(bs, s), max(2 * bs + 5, s), 4 * bs, 0]
    b = len(fills)
    bt = np.zeros((b, MB), np.int32)
    for i, ln in enumerate(fills):
        blocks = pool.alloc(pool.blocks_for(ln))
        bt[i, :len(blocks)] = blocks
    bt = jnp.asarray(bt)
    for i, ln in enumerate(fills):
        if not ln:
            continue
        ka = write_kv(ka, bt[i:i + 1], jnp.zeros((1,), jnp.int32),
                      jnp.asarray(rng.randn(1, ln, h, d), jnp.float32))
        va = write_kv(va, bt[i:i + 1], jnp.zeros((1,), jnp.int32),
                      jnp.asarray(rng.randn(1, ln, h, d), jnp.float32))
    assert paged_supported((b, h, s, d), tuple(ka.shape), ka.dtype.itemsize)
    cut = paged_cut((b, h, s, d), tuple(ka.shape), MB, ka.dtype.itemsize)
    assert h // cut["heads_per_step"] == (5 if s == 256 else 1)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    lens = jnp.asarray([max(ln - s, 0) for ln in fills], jnp.int32)
    out = paged_decode_attention(q, ka, va, bt, lens)
    ref = paged_attention_ref(q, ka, va, bt, lens, d ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


@pytest.mark.parametrize("h", [25, 12, 16, 7])
def test_paged_head_tile_comes_from_the_shape(h):
    """The paged kernel's head tile: a divisor of h, never smaller under a
    larger budget, every head of a block for a decode step at GPT-2 XL's
    widths; where not even one head fits, the gate says no."""
    from paddle_tpu.ops.pallas.decode_attention import (
        _ATTN_VMEM_BYTES, _paged_step_bytes, paged_cut,
        paged_heads_per_step, paged_supported)
    shape = (256, 64, 128, 2)                   # s_p, d, block, bf16
    budgets = [kib << 10 for kib in (64, 256, 1024, 4096, 12288, 65536)]
    picked = [paged_heads_per_step(h, *shape, budget=x) for x in budgets]
    assert picked == sorted(picked) and picked[0] == 0 and picked[-1] == h
    for ht, budget in zip(picked, budgets):
        fits = [n for n in range(1, h + 1) if h % n == 0
                and _paged_step_bytes(n, *shape) <= budget]
        assert ht == max(fits, default=0)
    assert paged_heads_per_step(h, 8, 64, 128, 2) == h
    assert paged_heads_per_step(25, 256, 64, 128, 2) == 5
    # the grid's bound: head tiles x the work list's length (the grid
    # itself ends at the list's live count)
    assert paged_cut((32, 25, 1, 64), (225, 25, 64, 128), 8, 2) == \
        {"heads_per_step": 25, "grid_steps": 256, "list_steps": 256}
    assert paged_cut((1, 25, 256, 64), (225, 25, 64, 128), 8, 2) == \
        {"heads_per_step": 5, "grid_steps": 40, "list_steps": 8}
    # one head of a 2048-token float32 block at d 256 is over the budget
    assert _paged_step_bytes(1, 256, 256, 2048, 4) > _ATTN_VMEM_BYTES
    assert not paged_supported((1, h, 256, 256), (4, h, 256, 2048), 4)
    assert paged_supported((1, h, 256, 256), (4, h, 256, 1024), 4)


def _write_kv_numpy(arena, bt, lens, new):
    """Token-at-a-time reference for write_kv over the pool's arena
    [n, h, d, block_size]: position p of slot i lives in lane p % bs of
    row bt[i, p // bs]; past the table it is the trash block's."""
    out = np.array(arena)
    bs, nb = out.shape[3], bt.shape[1]
    for i in range(new.shape[0]):
        for t in range(new.shape[1]):
            p = int(lens[i]) + t
            row = bt[i, p // bs] if p // bs < nb else 0
            out[row, :, :, p % bs] = new[i, t]
    return out


# (block_size, table width, lengths, chunk, block tables or None = one
# run of blocks per slot); bs 128 is the serving size, 16 keeps the
# multi-slot and past-the-table cases small
_WRITE_CASES = {
    "s1_offset0": (128, 2, [0, 128], 1, None),
    "s1_offset127": (128, 2, [127, 255], 1, None),
    "s1_many_slots": (16, 3, [0, 15, 16, 47, 5], 1, None),
    "s8_from0": (128, 2, [0], 8, None),
    "s64_from0": (128, 2, [0], 64, None),
    "s256_from0": (128, 2, [0], 256, None),
    "s64_four_slots": (128, 1, [0, 0, 0, 0], 64, None),
    "mid_block": (16, 4, [5, 23], 20, None),
    "mid_block_to_boundary": (16, 4, [9], 7, None),
    "s1_past_table": (16, 2, [32, 31], 1, None),
    "chunk_runs_past_table": (16, 2, [20], 30, None),
    "zero_table_s1": (16, 2, [3, 0], 1, np.zeros((2, 2), np.int32)),
    "zero_table_chunk": (16, 2, [3], 24, np.zeros((1, 2), np.int32)),
}


def _check_write_case(case):
    bs, nb, lens, s, bt = _WRITE_CASES[case]
    b, h, d = len(lens), 2, 8
    pool = KVBlockPool(b * nb + 2, bs)
    trash_only = bt is not None
    if bt is None:
        bt = np.asarray([pool.alloc(nb) for _ in range(b)], np.int32)
    rng = np.random.RandomState(1)
    (arena, _), = pool.arenas(1, h, d)
    arena = np.asarray(arena) + rng.randn(*arena.shape).astype(np.float32)
    new = rng.randn(b, s, h, d).astype(np.float32)
    lens = np.asarray(lens, np.int32)
    got = np.asarray(write_kv(jnp.asarray(arena), jnp.asarray(bt),
                              jnp.asarray(lens), jnp.asarray(new)))
    want = _write_kv_numpy(arena, bt, lens, new)
    np.testing.assert_array_equal(got[1:], want[1:])
    if trash_only:
        np.testing.assert_array_equal(got[1:], arena[1:])
    else:
        assert not np.array_equal(got[1:], arena[1:])


@pytest.mark.parametrize("case", sorted(_WRITE_CASES))
def test_write_kv_matches_numpy_reference(case):
    """write_kv (its XLA form: the writer kernel's gate rejects on the
    CPU) against a token-at-a-time numpy writer: single tokens at a
    block's first and last lane, chunks from length 0 and from inside a
    block, positions past the table and parked (all-zero) tables — which
    may touch the trash block and nothing else."""
    monitor.reset(prefix="pallas.")
    _check_write_case(case)
    assert monitor.stat_get("pallas.hit.paged_write_token") == 0


# the cells' decode steps through the writer, block 128, bf16: GPT-2 XL's
# 32 slots x 25 heads x 64 and the Kimi share's 64 slots over its one-head
# latent arena [n, 1, 576, 128]; (slots, heads, dim, offsets, poisoned)
_CELL_WRITE_CASES = {
    f"{cell}_{offsets}": (*shape, offsets, False)
    for cell, shape in (("gpt2xl_b32", (32, 25, 64)),
                        ("kimi_b64", (64, 1, 576)))
    for offsets in ("offset0", "offset127", "mixed")}
_CELL_WRITE_CASES.update(
    gpt2xl_b32_isolation=(32, 25, 64, "mixed", True),
    kimi_b64_isolation=(64, 1, 576, "mixed", True))


def _check_cell_write_case(case):
    """One decode write at a cell's shape: every slot owns one block but
    two, parked on the trash block (all-zero tables). `poisoned`: one
    slot's token is all NaN / inf, and every OTHER slot's block has to be
    the reference's all the same."""
    b, h, d, offsets, poisoned = _CELL_WRITE_CASES[case]
    bs, parked, bf16 = 128, (3, b - 2), jnp.bfloat16
    pool = KVBlockPool(b + 1, bs)
    bt = np.asarray([[0] if i in parked else pool.alloc(1)
                     for i in range(b)], np.int32)
    rng = np.random.RandomState(b)
    arena = np.asarray(jnp.asarray(
        rng.randn(*pool.arena_shape(h, d)).astype(np.float32), bf16))
    new = rng.randn(b, 1, h, d).astype(np.float32)
    new[1, 0, 0, :2] = [-0.0, 0.0]          # bits, not values, are moved
    if poisoned:
        new[5] = np.resize([np.nan, np.inf, -np.inf], new[5].shape)
    new = np.asarray(jnp.asarray(new, bf16))
    lens = {"offset0": np.zeros(b), "offset127": np.full(b, bs - 1),
            "mixed": rng.randint(0, bs, b)}[offsets].astype(np.int32)
    got = np.asarray(write_kv(jnp.asarray(arena), jnp.asarray(bt),
                              jnp.asarray(lens), jnp.asarray(new)))
    want = _write_kv_numpy(arena, bt, lens, new)
    assert got.dtype == want.dtype == arena.dtype
    if poisoned:
        assert not np.isfinite(got[bt[5, 0], :, :, lens[5]]
                               .astype(np.float32)).any()
        np.testing.assert_array_equal(got[1:].astype(np.float32),
                                      want[1:].astype(np.float32))
        clean = np.delete(np.arange(1, b + 2), bt[5, 0] - 1)
        got, want = got[clean], want[clean]
        assert np.isfinite(got.astype(np.float32)).all()
    else:
        got, want = got[1:], want[1:]
    np.testing.assert_array_equal(got.view(np.uint16), want.view(np.uint16))
    assert not np.array_equal(got, arena[1:][:len(got)])


@pytest.mark.parametrize(
    "case", sorted(c for c in _WRITE_CASES if _WRITE_CASES[c][3] == 1)
    + sorted(_CELL_WRITE_CASES))
def test_write_kv_token_kernel_matches_numpy_reference(interpret, case):
    """The decode step's single-token write through the Pallas writer
    (interpreted), same cases, same reference; then the cells' shapes,
    bit for bit, and a slot of NaN / inf that stays in its own block."""
    monitor.reset(prefix="pallas.")
    if case in _WRITE_CASES:
        _check_write_case(case)
    else:
        _check_cell_write_case(case)
    assert monitor.stat_get("pallas.hit.paged_write_token") == 1


def test_paged_write_supported_reads_block_and_slots():
    """The writer's gate is VMEM accounting from the shape: the cells'
    arenas pass at 32 and 64 slots (and at the small programs' 2 and 4),
    a block or a slot count that does not fit is refused."""
    from paddle_tpu.ops.pallas.decode_attention import (
        _ATTN_VMEM_BYTES, _write_step_bytes, paged_write_cut,
        paged_write_supported)
    for arena in ((225, 25, 64, 128), (1025, 1, 576, 128)):
        for slots in (2, 4, 32, 64):
            assert paged_write_supported(arena, 2, slots), (arena, slots)
    assert paged_write_cut((225, 25, 64, 128), 32, 2) == {
        "token_bytes": 25 * 64 * 128 * 2,               # 410 KB, dense
        "block_bytes": 2 * 32 * 25 * 64 * 128 * 2}      # 26.2 MB in + out
    assert paged_write_cut((1025, 1, 576, 128), 64, 2) == {
        "token_bytes": 576 * 128 * 2, "block_bytes": 2 * 64 * 576 * 128 * 2}
    assert not paged_write_supported((9, 25, 64, 1024), 2, 32)   # block
    assert not paged_write_supported((225, 25, 64, 128), 2, 2048)  # slots
    assert _write_step_bytes(25, 64, 128, 2048, 2) > _ATTN_VMEM_BYTES
    assert not paged_write_supported((9, 4, 64, 12), 2, 2)      # sublanes
    assert paged_write_supported((9, 4, 64, 256), 2, 2)         # two tiles
    assert not paged_write_supported((9, 4, 64, 192), 2, 2)     # 1.5 tiles
    assert not paged_write_supported((9, 4, 64, 128), 8, 2)     # float64
    assert not paged_write_supported((9, 64, 128), 2, 2)


def test_write_kv_shape_the_writer_refuses_takes_the_xla_loop(
        interpret, monkeypatch):
    """A shape over the writer's VMEM budget goes to write_kv's XLA loop,
    to the same bytes, counted as a gate rejection."""
    da = importlib.import_module("paddle_tpu.ops.pallas.decode_attention")
    monkeypatch.setattr(da, "_ATTN_VMEM_BYTES", 1 << 10)
    monitor.reset(prefix="pallas.")
    _check_write_case("s1_many_slots")
    assert monitor.stat_get("pallas.hit.paged_write_token") == 0
    assert monitor.stat_get(
        "pallas.gate_reject.paged_write_token.shape") == 1


# the fused call (PR 47): (heads, d, d_v, block, table width, dtype,
# fills with -1 an idle slot under an all-zero table, the head tile the
# call must be cut with where that is fewer than all heads, poisoned:
# slot 1's tokens hold -0.0 / inf / NaN bits)
_WRITE_ATTEND_CASES = {
    "lane0_of_a_fresh_block": (4, 16, 16, 128, 3, "float32", [0, 128, 256],
                               None, False),
    "last_lane": (4, 16, 16, 128, 3, "float32", [127, 255, 383], None,
                  False),
    "past_the_table": (4, 16, 16, 128, 2, "float32", [256, 255, 300], None,
                       False),
    "idle_slot": (4, 16, 16, 128, 3, "float32", [150, -1, 7], None, False),
    "head_tile_of_eight": (16, 128, 128, 128, 2, "float32", [5, 130, 255],
                           8, False),
    "bf16_block128": (5, 64, 64, 128, 2, "bfloat16", [0, 127, 128, 200],
                      None, False),
    "bf16_poisoned": (5, 64, 64, 128, 2, "bfloat16", [3, 127, 128, 255],
                      None, True),
    "f32_poisoned": (4, 16, 16, 128, 3, "float32", [127, 128, 260], None,
                     True),
    "values_narrower": (4, 24, 16, 128, 3, "float32", [0, 127, 300], None,
                        False),
}


@pytest.mark.parametrize("case", sorted(_WRITE_ATTEND_CASES))
def test_paged_write_attend_is_the_pair_bit_for_bit(interpret, case):
    """ONE call of the multi-head kernel that writes the step's token
    into the block it holds, against `write_kv` twice and then the
    kernel, on the same inputs: the output AND both arenas, bit for bit
    (but for the trash block, whose content nobody may rely on: there the
    test asks only that nothing unreal got in)."""
    from paddle_tpu.nn.kv_pool import paged_attention, paged_write_attend
    da = importlib.import_module("paddle_tpu.ops.pallas.decode_attention")
    h, d, d_v, bs, nb, dtype, fills, tile, poisoned = \
        _WRITE_ATTEND_CASES[case]
    if tile:        # a head tile smaller than h: the kernel itself, past
        # the gate (which would not trade the pair's 16 heads a step)
        assert da.paged_heads_per_step(h, 8, d, bs, 4, d_v=d_v,
                                       write_slots=len(fills)) == tile < h
    b, dt = len(fills), jnp.dtype(dtype)
    pool = KVBlockPool(b * nb + 1, bs)
    bt = np.zeros((b, nb), np.int32)
    for i, n in enumerate(fills):
        if n >= 0:
            blocks = pool.alloc(min(n // bs + 1, nb))
            bt[i, :len(blocks)] = blocks
    lens = np.maximum(np.asarray(fills, np.int32), 0)
    rng = np.random.RandomState(len(case))
    ka = jnp.asarray(rng.randn(*pool.arena_shape(h, d)), dt)
    va = jnp.asarray(rng.randn(*pool.arena_shape(h, d_v)), dt)
    q = jnp.asarray(rng.randn(b, h, 1, d), dt)
    nk = rng.randn(b, 1, h, d).astype(np.float32)
    nv = rng.randn(b, 1, h, d_v).astype(np.float32)
    nk[0, 0, 0, :2] = [-0.0, 0.0]              # bits, not values, move
    if poisoned:
        nk[1] = np.resize([np.nan, np.inf, -np.inf, -0.0], nk[1].shape)
        nv[1] = np.resize([np.inf, np.nan, -0.0], nv[1].shape)
    nk, nv = jnp.asarray(nk, dt), jnp.asarray(nv, dt)
    monitor.reset(prefix="pallas.")
    if tile:
        lanes = [jnp.transpose(t[:, 0], (1, 2, 0)) for t in (nk, nv)]
        out, k2, v2 = da.paged_write_attend(q, ka, va, bt, lens, *lanes,
                                            0.25)
    else:
        out, k2, v2 = paged_write_attend(q, ka, va, bt, lens, nk, nv, 0.25)
        assert monitor.stat_get("pallas.hit.paged_write_attend") == 1
    assert monitor.stat_get("pallas.hit.paged_write_token") == 0
    assert monitor.stat_get("pallas.hit.paged_decode_attention") == 0
    k1 = write_kv(ka, bt, lens, nk)
    v1 = write_kv(va, bt, lens, nv)
    want = paged_attention(q, k1, v1, bt, lens, 0.25)
    assert monitor.stat_get("pallas.hit.paged_write_token") == 2
    bits = np.uint16 if dtype == "bfloat16" else np.uint32

    def same(got, ref):
        got, ref = np.asarray(got), np.asarray(ref)
        assert got.dtype == ref.dtype and got.shape == ref.shape
        np.testing.assert_array_equal(got.view(bits), ref.view(bits))

    same(out, want)
    same(k2[1:], k1[1:])
    same(v2[1:], v1[1:])
    assert not np.array_equal(np.asarray(k2)[1:], np.asarray(ka)[1:]) \
        or all(n < 0 or n >= nb * bs for n in fills)
    # a written token is where the numpy writer puts it
    i = 0 if fills[0] >= 0 and fills[0] < nb * bs else 1
    np.testing.assert_array_equal(
        np.asarray(k2)[bt[i, lens[i] // bs], :, :, lens[i] % bs]
        .view(bits), np.asarray(nk)[i, 0].view(bits))
    if not poisoned:    # the trash block holds blocks and tokens, no junk
        assert np.isfinite(np.asarray(k2, np.float32)[0]).all()
        assert np.isfinite(np.asarray(v2, np.float32)[0]).all()
    else:               # and a slot's NaN stays in its own block and row
        clean = [j for j in range(b) if j != 1]
        assert np.isfinite(np.asarray(out, np.float32)[clean]).all()
        assert not np.isfinite(np.asarray(out, np.float32)[1]).all()


def test_paged_write_attend_gate_reads_the_shapes(interpret):
    """The fused form's gate is a predicate of shapes: GPT-2 XL's decode
    step (32 slots, 25 heads of 64, block 128, bf16) writes and attends
    in one call with all 25 heads a grid step; the hybrid's 30 heads of
    128 would fall from 30 heads a step to 15, so its step stays on the
    writer and the kernel apart, counted; a chunk and a group of query
    heads are not the gate's business and count nothing."""
    from paddle_tpu.nn.kv_pool import paged_write_attend
    from paddle_tpu.ops.pallas.decode_attention import (
        _ATTN_VMEM_BYTES, _paged_step_bytes, paged_heads_per_step,
        paged_write_attend_cut)
    chat = ((32, 25, 1, 64), (225, 25, 64, 128), (225, 25, 64, 128))
    assert paged_write_attend_cut(*chat, 8, 2) == {
        "heads_per_step": 25, "grid_steps": 256, "list_steps": 256,
        "write_bytes": 32 * 25 * 128 * 128 * 2}         # 26.2 MB stored
    # the pool bounds the list: 100 blocks and a step a slot
    assert paged_write_attend_cut(*chat, 8, 2, max_steps=100 + 32)[
        "list_steps"] == 132
    assert _paged_step_bytes(25, 8, 64, 128, 2, write_slots=32) \
        <= _ATTN_VMEM_BYTES
    hybrid = ((32, 30, 1, 128), (337, 30, 128, 128), (337, 30, 128, 128))
    assert paged_heads_per_step(30, 8, 128, 128, 2) == 30
    assert paged_heads_per_step(30, 8, 128, 128, 2, write_slots=32) == 15
    assert paged_write_attend_cut(*hybrid, 8, 2) is None
    # the small programs' shapes, and what the form does not take at all:
    # a chunk, grouped queries, a block the writer refuses, a block too
    # large for VMEM, rows that do not fill whole 32-bit words as tiled,
    # a block of less than a 128-lane tile
    assert paged_write_attend_cut((2, 4, 1, 64), (9, 4, 64, 128),
                                  (9, 4, 48, 128), 4, 2)["grid_steps"] == 8
    for q, k, v in (((2, 4, 8, 64), (9, 4, 64, 128), (9, 4, 64, 128)),
                    ((2, 8, 1, 64), (9, 4, 64, 128), (9, 4, 64, 128)),
                    ((2, 4, 1, 64), (9, 4, 64, 192), (9, 4, 64, 192)),
                    ((32, 25, 1, 64), (9, 25, 64, 1024), (9, 25, 64, 1024)),
                    ((2, 4, 1, 24), (9, 4, 24, 128), (9, 4, 24, 128)),
                    ((2, 4, 1, 64), (9, 4, 64, 16), (9, 4, 64, 16))):
        assert paged_write_attend_cut(q, k, v, 4, 2) is None, (q, k, v)

    def run(b, h_q, s, h, d, bs=128, dtype=jnp.float32):
        pool = KVBlockPool(8, bs)
        (ka, va), = pool.arenas(1, h, d, dtype)
        bt = np.asarray([pool.alloc(2) for _ in range(b)], np.int32)
        monitor.reset(prefix="pallas.")
        out, k2, v2 = paged_write_attend(
            jnp.ones((b, h_q, s, d)), ka, va, bt, np.zeros(b, np.int32),
            jnp.ones((b, s, h, d)), jnp.ones((b, s, h, d)), 0.25)
        assert out.shape == (b, h_q, s, d) and k2.shape == ka.shape
        return monitor.stats("pallas.")

    stats = run(2, 4, 1, 4, 16)
    assert stats["pallas.hit.paged_write_attend"] == 1
    for b, h_q, s in ((2, 4, 4), (2, 8, 1)):    # a chunk; grouped queries
        stats = run(b, h_q, s, 4, 16)
        assert "pallas.hit.paged_write_attend" not in stats
        assert not [k for k in stats if "paged_write_attend" in k], stats
        assert stats["pallas.hit.paged_decode_attention"] == 1
    stats = run(2, 30, 1, 30, 128, bs=128, dtype=jnp.bfloat16)  # hybrid's
    assert stats["pallas.gate_reject.paged_write_attend.shape"] == 1
    assert stats["pallas.hit.paged_write_token"] == 2
    assert stats["pallas.hit.paged_decode_attention"] == 1
    paddle.set_flags({"FLAGS_use_paged_attention": False})
    try:        # the flag off still means the jnp pair
        stats = run(2, 4, 1, 4, 16)
    finally:
        paddle.set_flags({"FLAGS_use_paged_attention": True})
    assert not [k for k in stats if k.startswith("pallas.hit.")], stats
    assert stats["pallas.gate_reject.paged_write_attend.flag_off"] == 1
    assert stats["pallas.gate_reject.paged_decode_attention.flag_off"] == 1


def test_mha_paged_matches_static_cache_bitwise():
    """The MHA PagedKVCache branch (jnp path) must be BITWISE equal to
    the StaticKVCache path across a prefill + decode sequence — the
    foundation of serve-vs-generate token identity."""
    from paddle_tpu import nn
    paddle.seed(3)
    mha = nn.MultiHeadAttention(32, 2, dropout=0.0)
    mha.eval()
    b, bs, NB, MB = 2, 16, 10, 4
    static = mha.gen_static_cache(b, 64)
    pool = KVBlockPool(NB, bs)
    bt = np.zeros((b, MB), np.int32)
    for i in range(b):
        bt[i, :] = pool.alloc(MB)
    (ka, va), = pool.arenas(1, 2, 16)
    paged = PagedKVCache(ka, va, jnp.asarray(bt),
                         jnp.zeros((b,), jnp.int32))
    rng = np.random.RandomState(5)
    for chunk in (7, 1, 1, 1):
        x = paddle.to_tensor(rng.randn(b, chunk, 32).astype(np.float32))
        os_, static = mha(x, cache=static)
        op_, paged = mha(x, cache=paged)
        np.testing.assert_array_equal(np.asarray(os_._value),
                                      np.asarray(op_._value))
    assert np.asarray(paged.lengths).tolist() == [10, 10]


# --------------------------------------------------------------------------
# THE proof: continuous batching == sequential generate
# --------------------------------------------------------------------------

def test_serve_greedy_token_identical_ragged_admission(net):
    """More ragged-prompt requests than slots: admission happens
    mid-flight while earlier streams are still decoding, and every
    stream's tokens must equal its sequential generate run."""
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 1024, (n,)).astype(np.int64)
               for n in (5, 9, 3, 17, 7, 12)]
    loop = ServeLoop(net, ServeConfig(max_active=3, kv_blocks=32,
                                      block_size=16, max_seq_len=64))
    results = loop.serve(prompts, max_new_tokens=8)
    for p, got in zip(prompts, results):
        np.testing.assert_array_equal(got, _ref_generate(net, p, 8))
    st = loop.stats()
    assert st["kv_pool_used_blocks"] == 0 and st["active_slots"] == 0


def test_serve_eos_retires_early_and_frees_blocks(net):
    rng = np.random.RandomState(1)
    p = rng.randint(1, 1024, (6,)).astype(np.int64)
    eos = int(_ref_generate(net, p, 10)[0])
    loop = ServeLoop(net, ServeConfig(max_active=2, kv_blocks=16,
                                      block_size=16, max_seq_len=64))
    monitor.reset(prefix="serve.")
    out = loop.serve([p], max_new_tokens=10, eos_token_id=eos)[0]
    np.testing.assert_array_equal(out, _ref_generate(net, p, 10, eos))
    assert len(out) < 10, "eos must retire the stream early"
    assert loop.stats()["kv_pool_used_blocks"] == 0
    assert monitor.stat_get("serve.requests_completed") == 1


def test_pool_exhaustion_backpressure_then_admission_on_retire(net):
    """Pool fits ONE stream's worst case: the queue must drain strictly
    serially (peak one active) and still produce exact tokens."""
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, 1024, (10,)).astype(np.int64)
               for _ in range(3)]
    loop = ServeLoop(net, ServeConfig(max_active=4, kv_blocks=2,
                                      block_size=16, max_seq_len=32))
    monitor.reset(prefix="serve.")
    peak = [0]
    orig = loop._dispatch_decode

    def spying_dispatch():
        peak[0] = max(peak[0],
                      sum(s is not None for s in loop._slots))
        return orig()

    loop._dispatch_decode = spying_dispatch
    results = loop.serve(prompts, max_new_tokens=12)
    for p, got in zip(prompts, results):
        np.testing.assert_array_equal(got, _ref_generate(net, p, 12))
    assert peak[0] == 1, "pool for one stream must serialize admissions"
    assert monitor.stat_get("serve.requests_completed") == 3
    assert loop.stats()["kv_pool_used_blocks"] == 0


def test_preemption_replays_token_identical(net):
    """Overcommitted pool: growth preempts the youngest stream, which
    re-queues with its generated prefix and must still end
    token-identical (fold-in sampling keys make the replay exact)."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 1024, (6,)).astype(np.int64)
               for _ in range(3)]
    loop = ServeLoop(net, ServeConfig(max_active=4, kv_blocks=3,
                                      block_size=8, max_seq_len=16))
    monitor.reset(prefix="serve.")
    results = loop.serve(prompts, max_new_tokens=8)
    for p, got in zip(prompts, results):
        np.testing.assert_array_equal(got, _ref_generate(net, p, 8))
    assert monitor.stat_get("serve.preempted") > 0, \
        "this config must exercise eviction"
    assert loop.stats()["kv_pool_used_blocks"] == 0


def test_serve_threaded_concurrent_clients(net):
    loop = ServeLoop(net, ServeConfig(max_active=4, kv_blocks=32,
                                      block_size=16,
                                      max_seq_len=64)).start()
    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, 1024, (4 + i % 5,)).astype(np.int64)
               for i in range(10)]
    outs = {}

    def client(i):
        outs[i] = loop.submit(prompts[i],
                              max_new_tokens=6).result(timeout=120)

    ts = [threading.Thread(target=client, args=(i,)) for i in range(10)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    loop.stop()
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(outs[i], _ref_generate(net, p, 6))


def test_submit_rejects_over_cap(net):
    loop = ServeLoop(net, ServeConfig(max_active=2, kv_blocks=4,
                                      block_size=16, max_seq_len=32))
    with pytest.raises(ValueError, match="serving cap"):
        loop.submit(np.arange(1, 30), max_new_tokens=10)


# --------------------------------------------------------------------------
# kernel crash + observability
# --------------------------------------------------------------------------

def test_injected_kernel_crash_fails_requests(net, interpret, monkeypatch):
    """With the paged kernel eligible (interpret backend) but crashing,
    nothing demotes to the jnp path: every in-flight request carries the
    error, and the loop itself survives to report them."""
    # the pallas package __init__ shadows the module name with the
    # function; importlib reaches the module itself
    da = importlib.import_module("paddle_tpu.ops.pallas.decode_attention")

    def boom(*a, **k):
        raise RuntimeError("injected Mosaic crash")

    monkeypatch.setattr(da, "_paged_call_once", boom)
    monitor.reset(prefix="pallas.")
    monitor.reset(prefix="serve.")
    rng = np.random.RandomState(5)
    loop = ServeLoop(net, ServeConfig(max_active=2, kv_blocks=16,
                                      block_size=16, max_seq_len=64))
    reqs = [loop.submit(rng.randint(1, 1024, (n,)).astype(np.int64),
                        max_new_tokens=6) for n in (5, 8)]
    loop.run_until_idle()
    for r in reqs:
        with pytest.raises(Exception, match="injected Mosaic crash"):
            r.result(timeout=0)
    assert monitor.stat_get("serve.requests_errored") == 2
    assert monitor.stat_get("pallas.hit.paged_decode_attention") == 0


def test_paged_kernel_engages_in_serve(net, interpret):
    """With interpret on and no crash, the block-table kernel actually
    serves the loop (hit counter) and tokens stay exact."""
    for name in list(monitor.stats("pallas.")):
        monitor.reset(name)
    rng = np.random.RandomState(6)
    p = rng.randint(1, 1024, (7,)).astype(np.int64)
    loop = ServeLoop(net, ServeConfig(max_active=2, kv_blocks=16,
                                      block_size=16, max_seq_len=64))
    trace.reset()
    out = loop.serve([p], max_new_tokens=4)[0]
    np.testing.assert_array_equal(out, _ref_generate(net, p, 4))
    assert monitor.stat_get("pallas.hit.paged_decode_attention") > 0
    # the decode step says how it was cut into grid steps: every head of
    # a block in one step, 2 slots x 4 logical blocks; as gauges, on the
    # kernel's span, and in the report of a dump
    # a block in one step, a work list of 2 slots x 4 logical blocks whose
    # live items bound the grid (counted once a traced call); as gauges, on
    # the kernel's span, and in the report of a dump, where the loop's
    # share of live table entries stands beside the bound
    heads = GPTConfig.tiny().num_heads
    cut = {"heads_per_step": heads, "grid_steps": 2 * 4, "list_steps": 2 * 4}
    for name, value in cut.items():
        assert monitor.stat_get(
            f"pallas.paged_decode_attention.{name}.b2s1") == value
    assert monitor.stat_get("pallas.hit.paged_work_list") \
        == monitor.stat_get("pallas.hit.paged_decode_attention")
    spans = [sp.attrs for sp in trace.recent()
             if sp.name == "pallas/paged_decode_attention"]
    assert any(cut.items() <= attrs.items() for attrs in spans), spans
    report = obs_report.pallas_rates({"values": {
        **monitor.stats("pallas."),
        "serve.paged_live_step_share": loop.stats()["paged_live_step_share"]}})
    assert (f"cut:b2s1={heads}heads/stepx<=8steps,"
            "25.0% of table entries live") in report   # idle: 2 of 2 x 4
    # and the writer what a call moves: the two slots' tokens as ONE
    # lane-padded [h, d, 2] operand, not a 128-lane row an element, and
    # the two blocks [1, h, d, 16] in and out (float32 here)
    dim = GPTConfig.tiny().hidden_size // heads
    moved = {"token_bytes": heads * dim * 128 * 4,
             "block_bytes": 2 * 2 * heads * dim * 128 * 4}
    for name, value in moved.items():
        assert monitor.stat_get(
            f"pallas.paged_write_token.{name}.b2") == value
    spans = [sp.attrs for sp in trace.recent()
             if sp.name == "pallas/paged_write_token"]
    assert any(moved.items() <= attrs.items() for attrs in spans), spans
    assert (f"write:b2={moved['token_bytes'] / 1e3:.0f}KB"
            f"/{moved['block_bytes'] / 1e6:.1f}MB") in report
    # a block of 16 lanes is less than the 128-lane tile the kernel's own
    # store moves: the decode step's gate left it on the pair, counted
    assert monitor.stat_get("pallas.hit.paged_write_attend") == 0
    assert monitor.stat_get("pallas.gate_reject.paged_write_attend.shape") > 0
    # the same net over blocks of 128 (the pool's size on the chip): the
    # decode step is ONE kernel that writes its token and attends (PR 47),
    # the prefill's chunk `write_kv`'s loop and the kernel as ever. It
    # says how it was cut and what it stores: every head of a block in one
    # step, 2 slots x 1 logical block, the two slots' K and V blocks
    # [1, h, d, 128] (float32 here)
    monitor.reset(prefix="pallas.")
    trace.reset()
    loop = ServeLoop(net, ServeConfig(max_active=2, kv_blocks=4,
                                      block_size=128, max_seq_len=64))
    np.testing.assert_array_equal(loop.serve([p], max_new_tokens=4)[0], out)
    assert monitor.stat_get("pallas.hit.paged_decode_attention") > 0
    assert monitor.stat_get("pallas.hit.paged_write_attend") > 0
    assert monitor.stat_get("pallas.hit.paged_write_token") == 0
    assert not monitor.stats("pallas.gate_reject.paged_write_attend.")
    cut = {"heads_per_step": heads, "grid_steps": 2 * 1, "list_steps": 2,
           "write_bytes": 2 * 2 * heads * dim * 128 * 4}
    for name, value in cut.items():
        assert monitor.stat_get(
            f"pallas.paged_write_attend.{name}.b2s1") == value
    assert monitor.stat_get("pallas.hit.paged_work_list") \
        == monitor.stat_get("pallas.hit.paged_write_attend") \
        + monitor.stat_get("pallas.hit.paged_decode_attention")
    spans = [sp.attrs for sp in trace.recent()
             if sp.name == "pallas/paged_write_attend"]
    assert any(cut.items() <= attrs.items() for attrs in spans), spans
    report = obs_report.pallas_rates({"values": monitor.stats("pallas.")})
    assert (f"cut:b2s1={heads}heads/stepx<=2steps,"
            f"{cut['write_bytes'] / 1e6:.1f}MB stored") in report


def test_paged_live_step_share_is_the_work_lists_share_of_the_tables(net):
    """`stats()["paged_live_step_share"]`: the (slot, block) pairs a
    decode step's work list holds (the pool's blocks in use and one item
    an idle slot) over slots x the table's width, from the host's books."""
    loop = ServeLoop(net, ServeConfig(max_active=4, kv_blocks=16,
                                      block_size=16, max_seq_len=64))
    assert loop.stats()["paged_live_step_share"] == 4 / (4 * 4)
    req = loop.submit(np.arange(1, 21).astype(np.int64), max_new_tokens=30)
    loop._tick()                    # admitted: 20 tokens are two blocks
    stats = loop.stats()
    assert (stats["active_slots"], stats["kv_pool_used_blocks"]) == (1, 2)
    assert stats["paged_live_step_share"] == (2 + 3) / (4 * 4)
    assert monitor.stat_get("serve.paged_live_step_share") == 5 / 16
    loop.run_until_idle()
    assert len(req.result(timeout=0)) == 30
    assert loop.stats()["paged_live_step_share"] == 4 / 16


def test_serve_spans_and_gauges(net):
    trace.reset()
    monitor.reset(prefix="serve.")
    monitor.reset(prefix="serve/")   # the ttft/token histograms
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 1024, (5,)).astype(np.int64)
               for _ in range(2)]
    loop = ServeLoop(net, ServeConfig(max_active=2, kv_blocks=16,
                                      block_size=16, max_seq_len=64))
    loop.serve(prompts, max_new_tokens=4)
    names = {sp.name for sp in trace.recent()}
    for want in ("serve/admit", "serve/prefill", "serve/decode_step",
                 "serve/retire", "serve/dispatch", "serve/retire_wait"):
        assert want in names, f"missing span {want} (have {names})"
    stats = monitor.stats("serve.")
    for g in ("serve.queue_depth", "serve.active_slots",
              "serve.kv_pool_used_blocks", "serve.kv_pool_free_blocks",
              "serve.tokens_generated", "serve.requests_completed"):
        assert g in stats, f"missing gauge {g}"
    assert monitor.stat_get("serve.requests_completed") == 2
    # latency histograms feed bench's serve snapshot
    assert monitor.histogram_summary("serve/ttft_ms")["count"] == 2


# --------------------------------------------------------------------------
# a beat's phases, a request's stamps, the scheduler's counts
# --------------------------------------------------------------------------

def _toy_loop(net, **kw):
    cfg = dict(max_active=4, kv_blocks=32, block_size=16, max_seq_len=64)
    cfg.update(kw)
    return ServeLoop(net, ServeConfig(**cfg))


def test_every_beat_is_a_tick_whose_phases_add_up(net):
    trace.reset()
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, 1024, (n,)).astype(np.int64)
               for n in (5, 9, 6)]
    loop = _toy_loop(net)
    loop.serve(prompts, max_new_tokens=5)
    spans = trace.recent()
    ticks = [sp for sp in spans if sp.name == "serve/tick"]
    assert len(ticks) >= loop.stats()["steps"] == 5
    assert [t.attrs["beat"] for t in ticks[:5]] == [0, 1, 2, 3, 4]
    assert ticks[0].attrs["queued"] == 3 and ticks[0].attrs["active"] == 0
    assert ticks[1].attrs["active"] == 3 and ticks[1].attrs["queued"] == 0
    selfs = self_times(spans)
    for tick in ticks:
        kids = [sp for sp in spans if sp.parent_id == tick.span_id]
        names = [sp.name for sp in kids]
        if "serve/decode_step" in names:
            # a dispatching beat: grow, upload, dispatch, in that order,
            # after the admissions (first beat) or the settles (later)
            assert names[-3:] == ["serve/grow", "serve/upload",
                                  "serve/decode_step"]
            assert set(names[:-3]) <= {"serve/settle", "serve/admit"}
        if tick.attrs["beat"] >= 1:
            assert "serve/settle" in names
        # phases lie inside the beat and one after the other
        for a, b in zip(kids, kids[1:]):
            assert tick.t0 <= a.t0 and a.t1 <= b.t0 and b.t1 <= tick.t1
        # ... so self times add up to the beat
        inside = [sp for sp in spans
                  if tick.t0 <= sp.t0 and sp.t1 <= tick.t1]
        total = sum(selfs[sp.span_id] for sp in inside)
        assert total == pytest.approx(tick.t1 - tick.t0, rel=0.01)
    # waiting is a child of settling, so the two can be told apart
    by_id = {sp.span_id: sp for sp in spans}
    waits = [sp for sp in spans if sp.name == "serve/settle_wait"]
    assert waits and all(by_id[w.parent_id].name == "serve/settle"
                         and w.attrs["kind"] in ("prefill", "decode")
                         for w in waits)
    retire = next(sp for sp in spans if sp.name == "serve/retire")
    assert by_id[retire.parent_id].name == "serve/settle"


def test_request_stamps_order_and_completion_record(net):
    records = []
    loop = ServeLoop(net, ServeConfig(max_active=1, kv_blocks=16,
                                      block_size=16, max_seq_len=64),
                     on_complete=records.append)
    rng = np.random.RandomState(12)
    reqs = [loop.submit(rng.randint(1, 1024, (6,)).astype(np.int64),
                        max_new_tokens=4) for _ in range(2)]
    loop.run_until_idle()
    for req in reqs:
        assert len(req.t_tokens) == len(req.out) == 4
        assert req.t_submit <= req.t_admit <= req.t_first
        assert req.t_tokens[0] == req.t_first
        assert req.t_tokens == sorted(req.t_tokens)
        assert req.t_tokens[-1] <= req.t_done
    # one slot: the second request waits for the first to retire
    assert reqs[1].t_admit >= reqs[0].t_done
    rec = {r["rid"]: r for r in records}[reqs[1].rid]
    assert rec["t_admit"] == reqs[1].t_admit
    assert rec["t_tokens"] == reqs[1].t_tokens
    assert rec["t_tokens"] is not reqs[1].t_tokens   # a copy, host floats


def test_stats_count_the_schedulers_work(net):
    monitor.reset(prefix="serve.")
    rng = np.random.RandomState(13)
    lens = (5, 9, 6, 7, 8)
    loop = _toy_loop(net, max_active=2)      # five requests, two slots
    before = loop.stats()
    reqs = [loop.submit(rng.randint(1, 1024, (n,)).astype(np.int64),
                        max_new_tokens=6) for n in lens]
    loop.run_until_idle()
    after = loop.stats()
    d = {k: after[k] - before[k] for k in (
        "steps", "decode_tokens", "prefill_dispatches", "prefill_tokens",
        "admitted", "queue_wait_s")}
    generated = sum(len(r.out) for r in reqs)
    assert generated == 30 == monitor.stat_get("serve.tokens_generated")
    assert monitor.stat_get("serve.preempted") == 0
    # a token comes from a prefill (the first) or from a decode beat
    assert d["decode_tokens"] + d["prefill_dispatches"] == generated
    assert d["prefill_dispatches"] == d["admitted"] == len(lens)
    assert d["prefill_tokens"] == sum(lens)
    assert d["decode_tokens"] <= d["steps"] * after["max_active"]
    assert d["queue_wait_s"] == pytest.approx(
        sum(r.t_admit - r.t_submit for r in reqs))
    # the same numbers as gauges, for whoever reads the monitor
    for name in ("decode_tokens", "prefill_dispatches", "prefill_tokens",
                 "admitted", "queue_wait_s"):
        assert monitor.stat_get(f"serve.{name}") == after[name]


def test_preemption_reprefills_but_admits_once(net):
    monitor.reset(prefix="serve.")
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 1024, (6,)).astype(np.int64)
               for _ in range(3)]
    loop = _toy_loop(net, kv_blocks=3, block_size=8, max_seq_len=16)
    reqs = [loop.submit(p, max_new_tokens=8) for p in prompts]
    loop.run_until_idle()
    st = loop.stats()
    preempted = int(monitor.stat_get("serve.preempted"))
    assert preempted > 0
    assert st["admitted"] == 3                   # first admissions only
    assert st["prefill_dispatches"] == 3 + preempted
    assert st["prefill_tokens"] > sum(p.size for p in prompts)
    for req in reqs:
        assert len(req.t_tokens) == len(req.out) == 8
        assert req.t_admit <= req.t_first        # not moved by a replay
    assert st["queue_wait_s"] == pytest.approx(
        sum(r.t_admit - r.t_submit for r in reqs))


def test_idle_scheduler_thread_sits_in_wait_work(net):
    import time
    trace.reset()
    loop = _toy_loop(net).start()
    try:
        time.sleep(0.12)                          # nothing to do yet
        out = loop.submit(np.arange(1, 7), max_new_tokens=3).result(
            timeout=120)
    finally:
        loop.stop(timeout=60)
    assert len(out) == 3
    spans = trace.recent()
    waits = [sp for sp in spans if sp.name == "serve/wait_work"]
    ticks = [sp for sp in spans if sp.name == "serve/tick"]
    assert waits and waits[0].duration_ms >= 100 and ticks
    assert waits[0].thread == ticks[0].thread == "serve-loop"
    # waiting and beating alternate on the one thread, never nested
    assert all(t.parent_id is None for t in ticks)
    assert all(w.t1 <= t.t0 or t.t1 <= w.t0 for w in waits for t in ticks)


# --------------------------------------------------------------------------
# satellite: per-request EOS handling in batched generate
# --------------------------------------------------------------------------

def test_batched_generate_eos_matches_sequential(net):
    """Batched cached generate with per-request EOS: finished rows
    freeze to eos and every row equals its single-request run — the
    contract that lets the serve loop retire rows early."""
    rng = np.random.RandomState(8)
    prompts = np.stack([rng.randint(1, 1024, (5,)) for _ in range(3)])
    refs = [np.asarray(net.generate(
        paddle.to_tensor(prompts[i][None]), max_new_tokens=10,
        temperature=0, use_cache=True).numpy())[0, 5:]
        for i in range(3)]
    eos = int(refs[0][1])  # row 0 finishes after <= 2 tokens
    batched = np.asarray(net.generate(
        paddle.to_tensor(prompts.astype(np.int64)), max_new_tokens=10,
        temperature=0, use_cache=True,
        eos_token_id=eos).numpy())[:, 5:]
    for i in range(3):
        ref = refs[i]
        hits = np.where(ref == eos)[0]
        if hits.size:
            cut = hits[0] + 1
            assert batched[i][:cut].tolist() == ref[:cut].tolist()
            assert (batched[i][cut:] == eos).all(), \
                "finished rows must stay frozen at eos"
        else:
            assert batched[i].tolist() == ref.tolist()
