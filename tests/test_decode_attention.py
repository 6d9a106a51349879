"""Pallas decode-attention kernel: parity with the jnp StaticKVCache path.

Interpret-mode (FLAGS_pallas_interpret) parity tests vs
_static_cache_attention / _sdpa — cache-length masking at several index
values, ragged per-batch lengths, bf16/f32 tolerances, and the vjp-free
eval contract (training-time cache attention stays on the jnp path).
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import monitor
from paddle_tpu.nn.layer.transformer import _static_cache_attention
from paddle_tpu.ops.pallas.decode_attention import decode_attention, supported


@pytest.fixture
def interpret():
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    yield
    paddle.set_flags({"FLAGS_pallas_interpret": False})


def _ref_ragged(q, kc, vc, lengths, scale):
    """Dense numpy oracle with per-batch live lengths (row r of batch i
    attends to cache cols <= lengths[i] - s + r)."""
    b, h, s, d = q.shape
    L = kc.shape[2]
    out = []
    for i in range(b):
        index = int(lengths[i]) - s
        live = np.arange(L)[None, :] <= index + np.arange(s)[:, None]
        sc = np.einsum("hsd,hld->hsl", np.asarray(q[i], np.float32),
                       np.asarray(kc[i], np.float32)) * scale
        sc = np.where(live[None], sc, -1e9)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        out.append(np.einsum("hsl,hld->hsd", p,
                             np.asarray(vc[i], np.float32)))
    return np.stack(out)


@pytest.mark.parametrize("index,s", [(0, 8), (0, 1), (17, 1), (31, 1),
                                     (96, 32), (127, 1)])
def test_matches_static_cache_attention(interpret, index, s):
    """Scalar cache index at several fill levels, incl. empty-cache
    prefill (index=0) and a full cache (index + s == L)."""
    rng = np.random.RandomState(0)
    b, h, d, L = 2, 3, 16, 128
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    kc = jnp.asarray(rng.randn(b, h, L, d), jnp.float32)
    vc = jnp.asarray(rng.randn(b, h, L, d), jnp.float32)
    idx = jnp.int32(index)

    out = decode_attention(q, kc, vc, idx)
    ref = _static_cache_attention(q, kc, vc, idx, d ** -0.5, 0.0, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ragged_per_batch_lengths(interpret):
    """A [b] index vector: each batch row attends its own prefix — the
    jnp path can't express this without a materialized mask."""
    rng = np.random.RandomState(1)
    b, h, s, d, L = 4, 2, 1, 32, 256
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    kc = jnp.asarray(rng.randn(b, h, L, d), jnp.float32)
    vc = jnp.asarray(rng.randn(b, h, L, d), jnp.float32)
    index = jnp.asarray([0, 17, 130, 255], jnp.int32)

    out = decode_attention(q, kc, vc, index)
    ref = _ref_ragged(q, kc, vc, np.asarray(index) + s, d ** -0.5)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5)


def test_bf16_tolerance(interpret):
    rng = np.random.RandomState(2)
    b, h, s, d, L = 2, 2, 1, 32, 128
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
    kc = jnp.asarray(rng.randn(b, h, L, d), jnp.bfloat16)
    vc = jnp.asarray(rng.randn(b, h, L, d), jnp.bfloat16)
    idx = jnp.int32(40)
    out = decode_attention(q, kc, vc, idx)
    assert out.dtype == jnp.bfloat16
    ref = _static_cache_attention(q.astype(jnp.float32),
                                  kc.astype(jnp.float32),
                                  vc.astype(jnp.float32), idx, d ** -0.5,
                                  0.0, False)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=2e-2)


def test_under_jit_traced_index(interpret):
    """The generate() scan passes a traced index; the scalar-prefetch grid
    must handle it (this is the whole point of the design)."""
    rng = np.random.RandomState(3)
    b, h, s, d, L = 2, 2, 1, 16, 64
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    kc = jnp.asarray(rng.randn(b, h, L, d), jnp.float32)
    vc = jnp.asarray(rng.randn(b, h, L, d), jnp.float32)

    fn = jax.jit(lambda q, kc, vc, i: decode_attention(q, kc, vc, i))
    for index in (0, 13, 63):
        out = fn(q, kc, vc, jnp.int32(index))
        ref = _static_cache_attention(q, kc, vc, jnp.int32(index),
                                      d ** -0.5, 0.0, False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)


def test_block_k_override_and_flag(interpret):
    rng = np.random.RandomState(4)
    b, h, s, d, L = 1, 1, 1, 16, 256
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    kc = jnp.asarray(rng.randn(b, h, L, d), jnp.float32)
    vc = jnp.asarray(rng.randn(b, h, L, d), jnp.float32)
    ref = _static_cache_attention(q, kc, vc, jnp.int32(100), d ** -0.5,
                                  0.0, False)
    for bk in (64, 128, 256):
        out = decode_attention(q, kc, vc, jnp.int32(100), block_k=bk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
    paddle.set_flags({"FLAGS_decode_block_k": 64})
    try:
        out = decode_attention(q, kc, vc, jnp.int32(100))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
    finally:
        paddle.set_flags({"FLAGS_decode_block_k": 0})


def test_supported_gate():
    assert supported((2, 4, 1, 64), (2, 4, 1024, 64))
    assert supported((2, 4, 32, 64), (2, 4, 1024, 64))     # chunked prefill
    assert not supported((2, 4, 1, 512), (2, 4, 1024, 512))  # head too wide
    assert not supported((2, 4, 512, 64), (2, 4, 1024, 64))  # prefill, not
    assert not supported((2, 4, 1, 64), (2, 2, 1024, 64))    # heads differ


def test_mha_cache_path_uses_kernel_in_eval(interpret):
    """MultiHeadAttention + StaticKVCache routes through the decode kernel
    in eval mode (hit counter) and matches the jnp path bit-for-bit-ish;
    training with dropout stays on jnp (gate counter)."""
    from paddle_tpu import nn
    paddle.seed(0)
    mha = nn.MultiHeadAttention(32, 2, dropout=0.5)
    mha.eval()
    x = paddle.randn([2, 4, 32])
    cache = mha.gen_static_cache(2, 16, "float32")

    for name in list(monitor.stats("pallas.")):
        monitor.reset(name)
    out_k, _ = mha(x, cache=cache)
    assert monitor.stat_get("pallas.hit.decode_attention") == 1

    paddle.set_flags({"FLAGS_use_decode_attention": False})
    try:
        out_j, _ = mha(x, cache=cache)
    finally:
        paddle.set_flags({"FLAGS_use_decode_attention": True})
    np.testing.assert_allclose(np.asarray(out_k._value),
                               np.asarray(out_j._value), atol=2e-5)
    assert monitor.stat_get(
        "pallas.gate_reject.decode_attention.flag_off") == 1

    # training mode: gate keeps the kernel out (vjp-free contract — even
    # at dropout=0 the kernel must not end up in a differentiated graph)
    mha.train()
    _ = mha(x, cache=mha.gen_static_cache(2, 16, "float32"))
    assert monitor.stat_get(
        "pallas.gate_reject.decode_attention.training") == 1


def test_gpt_generate_cached_kernel_matches_oracle(interpret):
    """End to end: tiny-GPT generate(use_cache=True) with the decode
    kernel equals the no-cache host-loop oracle (greedy)."""
    from paddle_tpu.text.models.gpt import GPT, GPTConfig
    paddle.seed(0)
    net = GPT(GPTConfig.tiny())
    net.eval()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, 1024, (2, 7)).astype("int64"))

    for name in list(monitor.stats("pallas.")):
        monitor.reset(name)
    out_cached = net.generate(ids, max_new_tokens=9, temperature=0,
                              use_cache=True)
    assert monitor.stat_get("pallas.hit.decode_attention") > 0
    out_oracle = net.generate(ids, max_new_tokens=9, temperature=0,
                              use_cache=False)
    np.testing.assert_array_equal(np.asarray(out_cached._value),
                                  np.asarray(out_oracle._value))


# --------------------------------------------------------------------------
# the multi-head paged kernel: a grid that ends at the work list's live items
# --------------------------------------------------------------------------

BS, NB = 128, 4      # a block of one lane tile, tables of four entries


def _paged_case(h, d, fills, s=1, idle=(), seed=0, dtype=jnp.bfloat16):
    """Arenas of noise, a table a slot that holds its fill and the
    chunk (none for an idle slot: the trash block), q and the chunk."""
    from paddle_tpu.nn.kv_pool import KVBlockPool
    rng = np.random.RandomState(seed)
    b = len(fills)
    pool = KVBlockPool(b * NB, BS)
    bt = np.zeros((b, NB), np.int32)
    for i, fill in enumerate(fills):
        if i not in idle:
            blocks = pool.alloc(min((fill + s - 1) // BS + 1, NB))
            bt[i, :len(blocks)] = blocks
    shape = pool.arena_shape(h, d)
    draw = lambda *dims: jnp.asarray(rng.randn(*dims), dtype)  # noqa: E731
    return (draw(*shape), draw(*shape), jnp.asarray(bt),
            jnp.asarray(fills, jnp.int32), draw(b, h, s, d),
            draw(b, s, h, d), draw(b, s, h, d))


def _bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


# (the list's live items, slots' fills, query rows, idle slots, head tiles)
LIST_CASES = {
    "every_slot_idle": (3, [0, 0, 0], 1, (0, 1, 2), 1),
    "every_table_full": (3 * NB, [NB * BS - 1] * 3, 1, (), 1),
    "a_blocks_last_lane_and_first": (1 + 2 + 2 + 3, [127, 128, 255, 256], 1,
                                     (), 1),
    "a_token_past_its_table": (NB + 1, [NB * BS, 5], 1, (), 1),
    "mixed_with_an_idle_slot_in_the_middle": (3 + 1 + 1 + 4,
                                              [300, 0, 17, 400], 1, (1,), 1),
    "two_head_tiles": (2 + 1 + 3, [130, 0, 290], 1, (1,), 2),
    "one_slot_of_64_rows": (1, [0], 64, (), 1),
    "one_slot_of_256_rows": (3, [100], 256, (), 1),
    "one_slot_of_256_rows_to_the_tables_end": (NB, [NB * BS - 256], 256, (),
                                               1),
}


@pytest.mark.parametrize("case,writing", [
    (case, writing) for case, (_, _, s, _, _) in LIST_CASES.items()
    for writing in (False, True) if s == 1 or not writing],     # a chunk
    ids=lambda x: {False: "attend", True: "write_attend"}.get(x, x))
def test_paged_list_form_matches_the_pair(interpret, monkeypatch, case,
                                          writing):
    """The multi-head paged kernel walks the LIVE (slot, block) pairs
    under a dynamic grid bound. Its non-writing form after `write_kv`
    against `paged_attention_ref`; its writing form (one token a slot)
    against that pair bit for bit: live slots' outputs and both arenas
    (the trash block apart). A chunk's rows are `write_kv`'s to write."""
    from paddle_tpu.nn.kv_pool import paged_attention_ref, write_kv
    da = importlib.import_module("paddle_tpu.ops.pallas.decode_attention")
    n_live, fills, s, idle, tiles = LIST_CASES[case]
    h, d = 2 * tiles, 64
    if tiles > 1:       # a head tile of h // tiles, by a smaller budget
        tile = da.paged_heads_per_step
        monkeypatch.setattr(
            da, "paged_heads_per_step",
            lambda heads, *a, **k: min(tile(heads, *a, **k), heads // tiles))
        jax.clear_caches()
        assert da.paged_heads_per_step(h, 8, d, BS, 2, write_slots=3) == 2
    ka, va, bt, lens, q, nk, nv = _paged_case(h, d, fills, s, idle,
                                              seed=len(case))
    # the list this call walks: an item a live pair, every slot at least one
    assert int(da._paged_live_list(bt, lens + s, BS, len(fills) * NB)[3][0]) \
        == n_live
    k1, v1 = write_kv(ka, bt, lens, nk), write_kv(va, bt, lens, nv)
    out = da.paged_decode_attention(q, k1, v1, bt, lens)
    live = [i for i in range(len(fills)) if i not in idle]
    want = paged_attention_ref(q, k1, v1, bt, lens, d ** -0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(want, np.float32)[live], atol=2e-2)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    if writing:
        tokens = (jnp.transpose(nk[:, 0], (1, 2, 0)),
                  jnp.transpose(nv[:, 0], (1, 2, 0)))
        fused, k2, v2 = da.paged_write_attend(q, ka, va, bt, lens, *tokens)
        np.testing.assert_array_equal(_bits(fused)[live], _bits(out)[live])
        np.testing.assert_array_equal(_bits(k2)[1:], _bits(k1)[1:])
        np.testing.assert_array_equal(_bits(v2)[1:], _bits(v1)[1:])
    if tiles > 1:
        jax.clear_caches()


def test_work_list_counts_a_tokens_own_block_and_no_padded_rows():
    """One token a slot brings `fill // bs + 1` blocks into the list: the
    7 query rows that pad it to the sublane tile bring none (a table-wide
    grid computed up to `(fill + 7) // bs`); a chunk of s rows brings
    `(fill + s - 1) // bs + 1`. The multi-head form's list (comparisons
    and sums) is the grouped form's (a search and lookups), item for item
    over the live ones."""
    da = importlib.import_module("paddle_tpu.ops.pallas.decode_attention")
    rng = np.random.RandomState(0)
    bs, nb, b = 128, 8, 32
    fills = np.concatenate([[0, 120, 121, 127, 128, 1023, 1024, 2000],
                            rng.randint(0, 1024, b - 8)]).astype(np.int32)
    bt = jnp.asarray(rng.randint(1, 225, (b, nb)), jnp.int32)
    for s in (1, 64, 256):
        counts = np.minimum((fills + s - 1) // bs, nb - 1) + 1
        got = da._paged_live_list(bt, jnp.asarray(fills + s), bs, b * nb)
        was = da._paged_work_list(bt, jnp.asarray(fills + s), bs, b * nb)
        n = int(got[3][0])
        assert n == counts.sum() == int(was[3][0])
        for mine, theirs in zip(got[:3], was[:3]):
            assert mine.dtype == jnp.int32
            np.testing.assert_array_equal(np.asarray(mine)[:n],
                                          np.asarray(theirs)[:n])
        slot, blk, phys = (np.asarray(x)[:n] for x in got[:3])
        assert (np.bincount(slot, minlength=b) == counts).all()
        assert (phys == np.asarray(bt)[slot, blk]).all()
    padded = np.minimum((fills + 7) // bs, nb - 1) + 1     # as the grid was
    assert (np.minimum(fills // bs, nb - 1) + 1).sum() < padded.sum()
    # the pool's bound: the list's arrays end there, and so does the count
    short = da._paged_live_list(bt, jnp.asarray(fills + 1), bs, 40)
    assert int(short[3][0]) == 40 and short[0].shape == (40,)
