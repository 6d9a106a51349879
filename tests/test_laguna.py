"""Laguna behind ServeLoop (text/models/laguna.py): grouped-query attention
with a gate a head, sliding-window layers that keep a ring a slot beside
full layers that page by token, the grouped-query form of the paged
kernel, against the plain float32 reference
(text/models/reference/laguna.py). Toy size, CPU: window 8 in ring blocks
of 4, 4 | 6 query heads over 2 key-value heads, 16 experts top-3."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core import monitor
from paddle_tpu.inference import ServeConfig, ServeLoop
from paddle_tpu.nn.kv_pool import (CacheSpec, KVBlockPool, PagedKVCache,
                                   WindowKVCache, cache_arenas,
                                   fresh_slot_rows, paged_attention_ref,
                                   paged_caches, window_attention,
                                   window_fill, window_ring_shape,
                                   window_write, write_kv)
from paddle_tpu.text.models import GPT, GPTConfig, Laguna, LagunaConfig
from paddle_tpu.text.models import decoder, laguna
from paddle_tpu.text.models.reference import laguna as ref

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import obs_report  # noqa: E402
from test_olmo_hybrid import forced_logits, rel_err, small_loop  # noqa: E402

HELD = (4, 8)            # routed experts 4..11 of 16
VOCAB = 256


def ref_config(cfg):
    """The reference's dict of published keys for a LagunaConfig."""
    return dict(
        num_hidden_layers=cfg.num_layers, layer_types=cfg.layer_types,
        num_attention_heads_per_layer=cfg.num_attention_heads_per_layer,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        sliding_window=cfg.sliding_window,
        rope_parameters=cfg.rope_parameters,
        mlp_only_layers=list(cfg.mlp_only_layers),
        num_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        moe_routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob, rms_norm_eps=cfg.rms_norm_eps)


def make_net(dtype="float32", **kw):
    paddle.seed(7)
    # std 0.1: at 64 wide the attention and the router then move the
    # logits by as much as the embedding does
    net = Laguna(LagunaConfig.tiny(experts_held=HELD, dtype=dtype,
                                   init_std=0.1, **kw))
    net.eval()
    return net


@pytest.fixture(scope="module")
def net():
    return make_net()


@pytest.fixture
def interpret():
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    yield
    paddle.set_flags({"FLAGS_pallas_interpret": False})


# -- 1. served logits against the reference ---------------------------------

@pytest.mark.parametrize("dtype,limit", [
    ("float32", 1e-4),
    # bf16 weights, matrix inputs, keys, values and probabilities; the
    # stream and the softmax float32. Five layers at 64 wide read 0.01-
    # 0.02 over token draws; a routing decision that bf16 flips near the
    # third score moves one position by more (0.05 seen), hence the room
    ("bfloat16", 0.1)])
def test_served_logits_match_reference(dtype, limit):
    """ServeLoop's own programs, a prompt longer than the window (21 of
    8), then 19 decode steps: the ring wraps five times."""
    net = make_net(dtype)
    params, _ = net.functional_state()
    ids = np.random.RandomState(0).randint(1, VOCAB, 21 + 19)
    got = forced_logits(net, small_loop(net, block_size=8, kv_blocks=32),
                        1, ids, 21)                         # bucket 32
    want = np.asarray(ref.forward(params, ref_config(net.config), ids,
                                  HELD))[20:]
    assert got.shape == want.shape == (20, VOCAB)
    for step in range(20):     # the prefill's logits, then 19 decode steps
        assert rel_err(got[step], want[step]) <= limit, step


@pytest.mark.parametrize("prompt_len", [3, 8, 9])
def test_a_prompt_shorter_than_the_window_wraps_in_decode(net, prompt_len):
    """The ring holds fewer tokens than it has columns, then exactly the
    window, then wraps, one decode step at a time."""
    params, _ = net.functional_state()
    ids = np.random.RandomState(prompt_len).randint(1, VOCAB, prompt_len + 12)
    got = forced_logits(net, small_loop(net, block_size=8, kv_blocks=32),
                        0, ids, prompt_len)
    want = np.asarray(ref.forward(params, ref_config(net.config), ids,
                                  HELD))[prompt_len - 1:]
    assert rel_err(got, want) <= 1e-4


def test_served_logits_match_reference_through_live_tiles(monkeypatch):
    """ServeLoop's own prefill program over 3 tiles of a bucket of 4, a
    sliding layer's tiles meeting the band only (window 8 in tiles of
    16: the tile before and its own)."""
    monkeypatch.setattr(decoder.PagedDecoder, "PREFILL_TILE", 16)
    net = make_net()
    assert [net.prefill_tile(b) for b in (16, 32, 64, 256)] \
        == [None, None, 16, 16]
    params, _ = net.functional_state()
    ids = np.random.RandomState(1).randint(1, VOCAB, 35 + 9)
    got = forced_logits(net, small_loop(net), 1, ids, 35)   # bucket 64
    want = np.asarray(ref.forward(params, ref_config(net.config), ids,
                                  HELD))[34:]
    assert rel_err(got, want) < 1e-4


@pytest.mark.parametrize("prompt_len", [1, 16, 17, 40, 255, 256])
def test_a_prefill_computes_only_the_tiles_that_hold_a_token(
        net, monkeypatch, prompt_len):
    """With tiles of 16 rows a bucket of 256 runs its row-wise work and
    its queries' tiles over ceil(prompt_len / 16) tiles and leaves the
    other rows zero; logits, the full layers' cached keys and the rings
    are those of the exact length computed whole."""
    ids = np.random.RandomState(prompt_len).randint(1, VOCAB, prompt_len)
    spec = net.paged_cache_spec()
    arenas = KVBlockPool(16, 16).arenas_for(spec, slots=1)
    table = jnp.asarray(np.arange(1, 17, dtype=np.int32)[None])
    last = jnp.asarray([prompt_len - 1], jnp.int32)

    def run(ids):
        return net._forward_paged(
            ids, paged_caches(spec, arenas, table,
                              jnp.zeros((1,), jnp.int32)), last_index=last)

    def cached(caches):
        out = []
        for c in caches:
            if isinstance(c, WindowKVCache):
                out.append(np.asarray(c.k))
            else:   # [256 tokens, h, d] by position, the prompt's only
                k = np.asarray(c.k[1:17]).transpose(0, 3, 1, 2)
                out.append(k.reshape(256, *k.shape[2:])[:prompt_len])
        return out

    exact, exact_caches, *_ = run(jnp.asarray(ids[None]))
    monkeypatch.setattr(decoder.PagedDecoder, "PREFILL_TILE", 16)
    padded = np.zeros((1, 256), np.int32)
    padded[0, :prompt_len] = ids
    got, got_caches, *_ = jax.jit(run)(jnp.asarray(padded))
    net.load_functional_state(*net.functional_state())
    assert rel_err(got, exact) <= 2e-5
    for a, b in zip(cached(got_caches), cached(exact_caches)):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_serve_loop_tokens_are_the_references_greedy(net):
    params, _ = net.functional_state()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, VOCAB, n) for n in (5, 17, 30, 9)]
    loop = ServeLoop(net, ServeConfig(max_active=2, kv_blocks=32,
                                      block_size=8, max_seq_len=64))
    outs = loop.serve(prompts, max_new_tokens=14)
    for prompt, out in zip(prompts, outs):
        logits = np.asarray(ref.forward(
            params, ref_config(net.config),
            np.concatenate([prompt, out]), HELD))
        np.testing.assert_array_equal(
            out, logits[len(prompt) - 1:-1].argmax(-1))


def test_uncut_model_matches_uncut_reference():
    paddle.seed(3)
    cfg = LagunaConfig.tiny(init_std=0.1)     # every routed expert held
    net = Laguna(cfg)
    net.eval()
    ids = np.random.RandomState(2).randint(1, VOCAB, (2, 40))
    got = np.asarray(net(ids)._value)
    params, _ = net.functional_state()
    for row in range(2):
        want = ref.forward(params, ref_config(cfg), ids[row])
        assert rel_err(got[row], want) <= 1e-4


# -- 2. the layer's parts ---------------------------------------------------

def test_rotary_settings_by_layer_type():
    """Full layers rotate half the head under YaRN with the published
    factor on cos and sin, sliding layers all of it at their own theta;
    the program's frequencies are the reference's."""
    cfg = LagunaConfig()
    r, freq, factor = laguna._rotary(cfg, laguna.FULL)
    assert (r, freq.shape) == (64, (32,))
    assert factor == cfg.rope_parameters[laguna.FULL]["attention_factor"] \
        == pytest.approx(0.1 * np.log(128.0) + 1.0)
    _, want, want_factor = ref.inv_freq(cfg.rope_parameters[ref.FULL], 128)
    np.testing.assert_allclose(np.asarray(freq), np.asarray(want), rtol=1e-6)
    assert want_factor == factor
    # the fastest pair is extrapolated (as published), the slowest
    # interpolated by the factor of 128
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    assert freq[0] == pytest.approx(plain[0])
    assert freq[-1] == pytest.approx(plain[-1] / 128.0, rel=1e-5)
    r, freq, factor = laguna._rotary(cfg, laguna.SLIDING)
    assert (r, factor) == (128, 1.0)
    np.testing.assert_allclose(np.asarray(freq),
                               10000.0 ** (-np.arange(0, 128, 2) / 128),
                               rtol=1e-6)


def test_the_gate_multiplies_each_heads_output_before_the_projection():
    cfg = LagunaConfig.tiny(init_std=0.1)
    paddle.seed(1)
    attn = laguna.GatedGroupedAttention(cfg, laguna.SLIDING, 6)
    rng = np.random.RandomState(0)
    out = jnp.asarray(rng.randn(1, 3, 6 * 16), jnp.float32)
    gate = jnp.asarray(rng.rand(1, 3, 6), jnp.float32)
    want = (np.asarray(out).reshape(1, 3, 6, 16)
            * np.asarray(gate)[..., None]).reshape(1, 3, 96) \
        @ np.asarray(attn.o._value)
    np.testing.assert_allclose(np.asarray(attn.output(out, gate)), want,
                               atol=1e-5)
    # a closed gate silences its head, an open one passes it whole
    shut = attn.output(out, jnp.zeros_like(gate))
    assert float(jnp.abs(shut).max()) == 0.0


@pytest.mark.parametrize("window,live", [(None, None), (None, 3), (8, None),
                                         (8, 2), (16, 4), (40, None)])
def test_chunk_attention_tiles_equal_the_dense_mask(window, live):
    """Tiles of 16 queries, the group folded into the rows, against the
    key tiles that meet the mask, under an online softmax: the dense
    masked softmax; under `live` the tiles past it come out zero."""
    rng = np.random.RandomState(5)
    b, s, hk, g, d = 2, 64, 2, 3, 8
    q = rng.randn(b, s, hk * g, d).astype(np.float32)
    k, v = rng.randn(2, b, s, hk, d).astype(np.float32)
    i, j = np.arange(s)[:, None], np.arange(s)[None]
    seen = (j <= i) if window is None else (j <= i) & (j > i - window)
    scores = np.einsum("bqkgd,btkd->bkgqt", q.reshape(b, s, hk, g, d), k) * 0.3
    scores = np.where(seen, scores, -np.inf)
    p = np.exp(scores - scores.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    want = np.einsum("bkgqt,btkd->bqkgd", p, v).reshape(b, s, hk * g, d)
    got = np.asarray(laguna._gqa_chunk_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        None if live is None else jnp.int32(live), scale=0.3, window=window,
        q_block=16))
    rows = s if live is None else 16 * live
    np.testing.assert_allclose(got[:, :rows], want[:, :rows], atol=2e-5)
    assert not got[:, rows:].any()


# -- 3. the grouped-query form of the paged kernel --------------------------

def filled_pool(rng, hk, d, bs, fills, dtype=jnp.float32):
    MB, NB = 4, 14
    pool = KVBlockPool(NB, bs)
    (ka, va), = pool.arenas(1, hk, d, dtype)
    bt = np.zeros((len(fills), MB), np.int32)
    for i, ln in enumerate(fills):
        blocks = pool.alloc(pool.blocks_for(ln))
        bt[i, :len(blocks)] = blocks
    bt = jnp.asarray(bt)
    for i, ln in enumerate(fills):
        if ln:
            zero = jnp.zeros((1,), jnp.int32)
            ka = write_kv(ka, bt[i:i + 1], zero,
                          jnp.asarray(rng.randn(1, ln, hk, d), dtype))
            va = write_kv(va, bt[i:i + 1], zero,
                          jnp.asarray(rng.randn(1, ln, hk, d), dtype))
    return ka, va, bt


@pytest.mark.parametrize("group,hk,d,bs", [
    (1, 2, 16, 8), (6, 2, 16, 8), (9, 2, 16, 16), (6, 8, 128, 128),
    (9, 8, 128, 128), (3, 5, 64, 128)])
def test_grouped_paged_kernel_parity_ragged_lengths(interpret, group, hk, d,
                                                    bs):
    """One token a slot, G query heads a key-value head as the rows of one
    product, slots at ragged fills (a block partly full, full, a partly
    full last block, a full table, empty): kernel vs gather fallback."""
    from paddle_tpu.ops.pallas.decode_attention import (
        paged_cut, paged_decode_attention, paged_supported)
    rng = np.random.RandomState(group)
    fills = [bs // 2 + 1, bs, 2 * bs + 5, 4 * bs, 0]
    ka, va, bt = filled_pool(rng, hk, d, bs, fills)
    b = len(fills)
    shape = (b, group * hk, 1, d)
    assert paged_supported(shape, tuple(ka.shape), ka.dtype.itemsize)
    # the work list: as long as the tables, or as the caller bounds it
    cut = paged_cut(shape, tuple(ka.shape), 4, ka.dtype.itemsize)
    assert cut == {"heads_per_step": hk, "grid_steps": b * 4,
                   **({"list_steps": b * 4} if group == 1 else {})}
    assert paged_cut(shape, tuple(ka.shape), 4, ka.dtype.itemsize,
                     max_steps=14 + b)["grid_steps"] == 19
    q = jnp.asarray(rng.randn(*shape), jnp.float32)
    lens = jnp.asarray([max(ln - 1, 0) for ln in fills], jnp.int32)
    out = paged_decode_attention(q, ka, va, bt, lens)
    want = paged_attention_ref(q, ka, va, bt, lens, d ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    # bounded by the pool's blocks and a step a slot (12 live pairs here),
    # and at the bound itself, the list holds every live pair
    for max_steps in (14 + b, 12):
        bounded = paged_decode_attention(q, ka, va, bt, lens,
                                         max_steps=max_steps)
        np.testing.assert_array_equal(np.asarray(bounded), np.asarray(out))
    # the fallback's grouping is the definition: head j reads j // G
    j = group * hk - 1
    alone = paged_attention_ref(q[:, j:j + 1], ka[:, j // group:][:, :1],
                                va[:, j // group:][:, :1], bt, lens,
                                d ** -0.5)
    np.testing.assert_allclose(np.asarray(want[:, j:j + 1]),
                               np.asarray(alone), atol=1e-6)


def test_one_query_head_a_group_is_the_kernel_as_it_was(interpret):
    """G = 1 takes the multi-head kernel; and the grouped kernel given
    one row a head (G = 1 forced through its work list) computes the same
    bits: the arithmetic of a block and the blocks' order within a slot
    are the multi-head kernel's, whose grid ends where the list's live
    items do."""
    from paddle_tpu.ops.pallas.decode_attention import (
        _paged_call_once, _paged_grouped_call_once, paged_decode_attention)
    rng = np.random.RandomState(3)
    h, d, bs = 5, 64, 128
    fills = [bs // 2 + 1, bs, 2 * bs + 5, 4 * bs, 0]
    ka, va, bt = filled_pool(rng, h, d, bs, fills)
    q = jnp.asarray(rng.randn(len(fills), h, 1, d), jnp.float32)
    lens = jnp.asarray([max(ln - 1, 0) for ln in fills], jnp.int32)
    plain = paged_decode_attention(q, ka, va, bt, lens)
    padded = jnp.pad(q, ((0, 0), (0, 0), (0, 7), (0, 0)))
    direct = _paged_call_once(padded, ka, va, bt, lens + 1, scale=d ** -0.5,
                              rows=1, steps=20, interpret=True)[:, :, :1]
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(direct))
    for steps in (20, 15, 12):       # every table entry .. the live pairs
        grouped = _paged_grouped_call_once(
            padded, ka, va, bt, lens + 1, scale=d ** -0.5, interpret=True,
            steps=steps)[:, :, :1]
        np.testing.assert_array_equal(np.asarray(plain), np.asarray(grouped))


def test_work_list_names_every_live_block_once_in_slot_order():
    from paddle_tpu.ops.pallas.decode_attention import _paged_work_list
    bt = jnp.asarray([[3, 4, 0, 0], [0, 0, 0, 0], [7, 8, 9, 5], [6, 0, 0, 0]],
                     jnp.int32)
    lens = jnp.asarray([9, 0, 40, 8], jnp.int32)    # with the step's token
    slot, blk, phys, n = (np.asarray(x) for x in _paged_work_list(
        bt, lens, 8, 11))
    assert int(n[0]) == 2 + 1 + 4 + 1      # an empty slot still has a step
    assert slot[:8].tolist() == [0, 0, 1, 2, 2, 2, 2, 3]
    assert blk[:8].tolist() == [0, 1, 0, 0, 1, 2, 3, 0]
    assert phys[:8].tolist() == [3, 4, 0, 7, 8, 9, 5, 6]
    # past the live items the last one repeats: nothing new is fetched
    assert (slot[8:] == 3).all() and (blk[8:] == 0).all() \
        and (phys[8:] == 6).all()


def test_the_gate_takes_groups_of_one_token_and_nothing_else():
    from paddle_tpu.ops.pallas.decode_attention import (
        _paged_step_bytes, paged_cut, paged_group, paged_supported)
    arena = (3073, 8, 128, 128)
    # the cell's two calls: 48 and 72 query heads over 8, bf16
    assert paged_supported((128, 48, 1, 128), arena, 2)
    assert paged_supported((128, 72, 1, 128), (512, 8, 128, 128), 2)
    # a table of 72 blocks with ~19 live a slot: the work list is bounded
    # by the pool's 3072 blocks and a step a slot, not by 128 x 72
    assert paged_cut((128, 48, 1, 128), arena, 72, 2) \
        == {"heads_per_step": 8, "grid_steps": 128 * 72}
    assert paged_cut((128, 48, 1, 128), arena, 72, 2, max_steps=3072 + 128) \
        == {"heads_per_step": 8, "grid_steps": 3200}
    assert paged_cut((128, 72, 1, 128), (512, 8, 128, 128), 4, 2,
                     max_steps=511 + 128) \
        == {"heads_per_step": 8, "grid_steps": 128 * 4}
    # G rows a key-value head, padded to the sublane tile: 6 -> 8, 9 -> 16
    assert _paged_step_bytes(8, 16, 128, 128, 2) \
        > _paged_step_bytes(8, 8, 128, 128, 2)
    assert [paged_group(h, 8) for h in (8, 48, 72, 50, 4)] == [1, 6, 9, 0, 0]
    assert not paged_supported((128, 50, 1, 128), arena, 2)  # no multiple
    assert not paged_supported((1, 48, 256, 128), arena, 2)  # a chunk: XLA
    assert not paged_supported((128, 48, 1, 64), arena, 2)   # other width
    # a multi-head call's list is as long, and its grid ends at the live
    # count (GPT-2 XL's decode step over a long table)
    assert paged_cut((32, 25, 1, 64), (1601, 25, 64, 128), 16, 2) \
        == {"heads_per_step": 25, "grid_steps": 32 * 16,
            "list_steps": 32 * 16}


def test_decode_step_reaches_the_paged_kernel_once_a_layer(interpret):
    """Every layer's decode attention is the paged kernel, the full
    layers' over the pool's table and the sliding layers' over the
    rings, and its gauges carry the group in their key."""
    net = make_net(ring_block=8, sliding_window=16)
    ids = np.random.RandomState(3).randint(1, VOCAB, 21 + 3)
    monitor.reset(prefix="pallas.")
    got = forced_logits(net, small_loop(net, block_size=8, kv_blocks=32),
                        0, ids, 21)
    # two traces of the decode step (the test's and the loop's)
    assert monitor.stat_get("pallas.hit.paged_decode_attention") \
        == 2 * net.config.num_layers
    assert not monitor.stats("pallas.gate_reject.paged_decode_attention.")
    assert monitor.stat_get(
        "pallas.paged_decode_attention.heads_per_step.b2s1g2") == 2
    assert monitor.stat_get(     # tables of 16 blocks, a pool of 32
        "pallas.paged_decode_attention.grid_steps.b2s1g2") == 2 * 16
    assert monitor.stat_get(                    # a ring of two blocks
        "pallas.paged_decode_attention.grid_steps.b2s1g3") == 2 * 2
    params, _ = net.functional_state()
    want = np.asarray(ref.forward(params, ref_config(net.config), ids,
                                  HELD))[20:]
    assert rel_err(got, want) <= 1e-4


# -- 4. the window cache ----------------------------------------------------

@pytest.mark.parametrize("count", [1, 3, 8, 9, 13, 24, 31, 32])
def test_window_fill_keeps_the_last_window_at_position_mod_window(count):
    rng = np.random.RandomState(count)
    h, d, bs, blocks = 2, 4, 4, 2
    window = bs * blocks
    chunk = rng.randn(1, 32, h, d).astype(np.float32)
    ring = window_fill(jnp.full((1, blocks, h, d, bs), 7.0, jnp.float32),
                       jnp.asarray(chunk), jnp.int32(count))
    want = np.zeros((window, h, d), np.float32)
    for p in range(max(0, count - window), count):
        want[p % window] = chunk[0, p]
    got = np.asarray(ring)[0].transpose(0, 3, 1, 2).reshape(window, h, d)
    np.testing.assert_array_equal(got, want)


def test_window_write_and_read_follow_the_stream_past_the_wrap():
    """Token by token through `window_write` / `window_attention` against
    a softmax over the last `window` keys, slots at different lengths."""
    rng = np.random.RandomState(0)
    slots, hk, g, d, bs, blocks = 3, 2, 3, 8, 4, 2
    window = bs * blocks
    shape = (slots,) + window_ring_shape(window, bs, hk, d)
    k_ring = v_ring = jnp.zeros(shape, jnp.float32)
    start = np.asarray([0, 5, 0])
    keys = rng.randn(slots, 30, hk, d).astype(np.float32)
    vals = rng.randn(slots, 30, hk, d).astype(np.float32)
    for step in range(20):
        lens = jnp.asarray(np.where(start <= step, step - start, 0),
                           jnp.int32)
        at = np.asarray(lens)
        new_k = jnp.asarray(keys[np.arange(slots), at])[:, None]
        new_v = jnp.asarray(vals[np.arange(slots), at])[:, None]
        k_ring = window_write(k_ring, lens, new_k)
        v_ring = window_write(v_ring, lens, new_v)
        q = jnp.asarray(rng.randn(slots, hk * g, 1, d), jnp.float32)
        got = np.asarray(window_attention(q, k_ring, v_ring, lens, 0.4))
        for i in range(slots):
            lo = max(0, at[i] - window + 1)
            kk, vv = keys[i, lo:at[i] + 1], vals[i, lo:at[i] + 1]
            sc = np.einsum("kgd,tkd->kgt",
                           np.asarray(q)[i, :, 0].reshape(hk, g, d), kk) * 0.4
            p = np.exp(sc - sc.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            want = np.einsum("kgt,tkd->kgd", p, vv).reshape(hk * g, d)
            np.testing.assert_allclose(got[i, :, 0], want, atol=2e-5)
    with pytest.raises(ValueError, match="no multiple"):
        window_ring_shape(10, 4, hk, d)


def test_cache_spec_pages_full_layers_and_rings_the_rest(net):
    spec = net.paged_cache_spec()
    ring = ((2, 2, 16, 4), None)
    assert spec == [CacheSpec(PagedKVCache, ((2, 16), (2, 16))),
                    *[CacheSpec(WindowKVCache, (), (ring, ring))] * 3,
                    CacheSpec(PagedKVCache, ((2, 16), (2, 16)))]
    arenas = KVBlockPool(6, 8).arenas_for(spec, slots=3)
    assert [[a.shape for a in layer] for layer in arenas] \
        == [[(7, 2, 16, 8)] * 2, *[[(3, 2, 2, 16, 4)] * 2] * 3,
            [(7, 2, 16, 8)] * 2]
    caches = paged_caches(spec, arenas, jnp.zeros((1, 2), jnp.int32),
                          jnp.zeros((1,), jnp.int32))
    assert [type(c) for c in caches] == [PagedKVCache, *[WindowKVCache] * 3,
                                         PagedKVCache]
    assert [len(a) for a in cache_arenas(caches)] == [2] * 5
    fresh = fresh_slot_rows(spec, arenas)
    assert fresh[0][0] is arenas[0][0] and fresh[1][0].shape \
        == (1, 2, 2, 16, 4)
    # the published widths: a ring of four 128-token blocks a slot
    full = LagunaConfig(num_layers=12)
    assert window_ring_shape(full.sliding_window, full.ring_block,
                             full.num_kv_heads, full.head_dim) \
        == (4, 8, 128, 128)
    assert full.layer_types.count(laguna.SLIDING) == 9 \
        and full.num_attention_heads_per_layer[:5] == [48, 72, 72, 72, 48]


def test_a_window_layers_cache_does_not_grow_and_the_pool_counts_pages(net):
    """Streams of 12 and of 60 tokens hold the same ring bytes; the
    pool's blocks are the full layers' pages only: a stream of n tokens
    owns ceil(n / block) of them, whatever the number of layers."""
    loop = ServeLoop(net, ServeConfig(max_active=2, kv_blocks=16,
                                      block_size=8, max_seq_len=64))
    rings = loop.stats()["state_bytes"]
    assert rings == 3 * 2 * 2 * (2 * 2 * 16 * 4) * 4   # layers k,v slots row
    rng = np.random.RandomState(4)
    seen = []
    loop.start()
    try:
        for n_prompt, n_new in ((6, 6), (30, 30)):
            req = loop.submit(rng.randint(1, VOCAB, n_prompt),
                              max_new_tokens=n_new)
            while not req.done:
                seen.append((loop.stats()["kv_pool_used_blocks"],
                             len(req.out)))
            assert loop.stats()["state_bytes"] == rings
            assert sum(x.nbytes for layer in loop._arenas[1:4]
                       for x in layer) == rings
    finally:
        loop.stop()
    assert max(used for used, _ in seen) == 8       # ceil(60 / 8), not x 5
    assert loop.stats()["kv_pool_used_blocks"] == 0
    assert loop.stats()["window_ring_bytes"] == rings


def test_rows_no_request_owns_write_nowhere_a_request_reads(net):
    """A decode step with slot 1 unowned: slot 0's rings and pages are
    what they were but for its own token; slot 1's write went to the
    trash block and to its own ring."""
    spec = net.paged_cache_spec()
    pool = KVBlockPool(8, 8)
    rng = np.random.RandomState(9)
    arenas = [tuple(jnp.asarray(rng.randn(*x.shape), jnp.float32)
                    for x in layer)
              for layer in pool.arenas_for(spec, slots=2)]
    table = jnp.asarray([[1, 2, 0], [0, 0, 0]], jnp.int32)
    lens = jnp.asarray([11, 0], jnp.int32)
    _, caches, *_ = net._forward_paged(
        jnp.asarray([[5], [9]], jnp.int32),
        paged_caches(spec, arenas, table, lens))
    for old, new in zip(arenas, cache_arenas(caches)):
        for a, b in zip(old, new):
            a, b = np.asarray(a), np.asarray(b)
            if a.ndim == 4:     # an arena: block 2 lane 3 of slot 0, trash
                changed = np.argwhere((a != b).any(axis=(1, 2)))
                assert {tuple(x) for x in changed} == {(2, 3), (0, 0)}
            else:               # rings: column 11 mod 8 of slot 0's, and
                np.testing.assert_array_equal(   # slot 1's own column 0
                    (a != b).any(axis=(2, 3)),
                    [[[False, False, False, True], [False] * 4],
                     [[True, False, False, False], [False] * 4]])


def test_preemption_and_reprefill_rebuild_the_rings(net):
    rng = np.random.RandomState(13)
    prompts = [rng.randint(1, VOCAB, 6) for _ in range(3)]
    roomy = ServeLoop(net, ServeConfig(max_active=4, kv_blocks=16,
                                       block_size=8, max_seq_len=32))
    want = roomy.serve(prompts, max_new_tokens=14)
    tight = ServeLoop(net, ServeConfig(max_active=4, kv_blocks=5,
                                       block_size=8, max_seq_len=32))
    monitor.reset(prefix="serve.")
    got = tight.serve(prompts, max_new_tokens=14)
    assert monitor.stat_get("serve.preempted") > 0
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert tight.stats()["kv_pool_used_blocks"] == 0


def test_a_cache_one_precision_down_is_a_different_answer():
    """`kv_round_to` (the benchmark's control) rounds what is cached and
    attended to, and nothing else."""
    ids = np.random.RandomState(0).randint(1, VOCAB, (1, 24))
    plain = np.asarray(make_net()(ids)._value)
    low = np.asarray(make_net(kv_round_to="float8_e4m3fn")(ids)._value)
    assert 1e-3 < rel_err(low, plain)


# -- 5. the shares add up ---------------------------------------------------

def test_sixteen_shares_add_up_to_the_uncut_layer():
    """256 routed experts top-10 at toy widths in sixteen shares of
    sixteen: the shares' routed parts, plus what every chip computes
    alike (the attention and the shared expert) counted once, are the
    uncut reference's LAYER output."""
    rng = np.random.RandomState(5)
    hidden, width, experts, top_k = 32, 16, 256, 10

    def normal(*shape):
        return jnp.asarray(rng.normal(0, 0.1, shape), jnp.float32)

    ffn = {"router_weight": normal(hidden, experts),
           "router_bias": jnp.zeros((experts,), jnp.float32),
           "gate": normal(experts, hidden, width),
           "up": normal(experts, hidden, width),
           "down": normal(experts, width, hidden),
           "shared_gate": normal(hidden, width),
           "shared_up": normal(hidden, width),
           "shared_down": normal(width, hidden)}
    w = {"attn_norm": jnp.ones(hidden), "ffn_norm": jnp.ones(hidden),
         "attn.qkv": normal(hidden, (6 + 4) * 8), "attn.g": normal(hidden, 6),
         "attn.o": normal(6 * 8, hidden),
         **{"ffn." + k: v for k, v in ffn.items()}}
    cfg = dict(ref_config(LagunaConfig.tiny()), num_experts=experts,
               num_experts_per_tok=top_k, num_key_value_heads=2, head_dim=8,
               layer_types=[ref.FULL, ref.SLIDING],
               num_attention_heads_per_layer=[4, 6], mlp_only_layers=[])
    x = jnp.asarray(rng.normal(0, 1, (50, hidden)), jnp.float32)
    pos = jnp.arange(50, dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        whole = ref.block(w, cfg, x, pos, 1, (0, experts))
        alike = ref.block(w, cfg, x, pos, 1, (0, 0))  # attention + shared
        f = ref.rms_norm(x + ref.attention(
            ref.sub_weights(w, "attn."), cfg,
            ref.rms_norm(x, w["attn_norm"], 1e-6), pos, ref.SLIDING, 6),
            w["ffn_norm"], 1e-6)
        shared = ref.shared_part(ffn, f)
    total, pairs = alike, 0
    for rank in range(16):
        held = (16 * rank, 16)
        layer = nn.RoutedExperts(hidden, width, experts, top_k, held=held,
                                 routed_scaling_factor=2.5,
                                 shared_width=width, score_func="softmax")
        mine = {k: (v[held[0]:held[0] + 16] if k in ("gate", "up", "down")
                    else v) for k, v in ffn.items()}
        layer.load_functional_state(mine)
        y, counts, _ = layer.routed(f)
        total = total + (y - shared)     # the share's routed part alone
        pairs += int(counts.sum())
        # the share is the reference's, given the same held range
        with jax.default_matmul_precision("highest"):
            assert rel_err(y - shared, ref.routed_part(mine, cfg, f, held)) \
                <= 1e-5
    assert pairs == 50 * top_k       # every pair is held by exactly one
    assert rel_err(total, whole) <= 1e-5


def test_router_is_a_softmax_renormalised_over_the_chosen_times_2_5(net):
    ffn = net.blocks[1].ffn
    assert (ffn.score_func, ffn.norm_topk_prob, ffn.scaling, ffn.top_k,
            ffn.first, ffn.count) == ("softmax", True, 2.5, 3, 4, 8)
    x = jnp.asarray(np.random.RandomState(2).randn(9, 64), jnp.float32)
    idx, weights = ffn.route(x)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 2.5, rtol=1e-6)
    scores = jax.nn.softmax(x @ ffn.router_weight._value, axis=-1)
    np.testing.assert_array_equal(
        np.asarray(idx), np.asarray(jax.lax.top_k(scores, 3)[1]))
    assert not np.asarray(ffn.router_bias._value).any()   # no selection bias
    assert isinstance(net.blocks[0].ffn, decoder.DenseFFN)  # mlp_only_layers


# -- 6. the counters --------------------------------------------------------

def test_counters_tell_what_the_decode_steps_read(net):
    # one step in flight: no step runs ahead of a retirement
    loop = ServeLoop(net, ServeConfig(max_active=4, kv_blocks=32,
                                      block_size=8, max_seq_len=64,
                                      max_inflight=1))
    rng = np.random.RandomState(14)
    monitor.reset(prefix="serve.")
    lens = (5, 11, 19)
    loop.serve([rng.randint(1, VOCAB, n) for n in lens], max_new_tokens=6)
    st = loop.stats()
    assert set(laguna.ATTN_STATS) | set(decoder.MOE_STATS) <= set(st)
    # a decode step reads the stream up to and with its own token: the
    # request's tokens 2..6 are decode steps at lengths n .. n + 4
    seen = [n + j + 1 for n in lens for j in range(5)]
    assert st["attn_full_decode_tokens_read"] == 2 * sum(seen)
    assert st["attn_window_decode_tokens_read"] \
        == 3 * sum(min(x, 8) for x in seen)
    assert st["window_ring_bytes"] == st["state_bytes"] > 0
    assert st["moe_prefill_tokens"] == st["prefill_tokens"] == 35
    assert st["moe_decode_layer_steps"] == 4 * st["steps"]
    assert st["prefill_rows"] == 8 + 16 + 32 == st["prefill_live_rows"]
    assert monitor.stat_get("serve.attn_window_decode_tokens_read") \
        == st["attn_window_decode_tokens_read"]
    assert monitor.stat_get("serve.window_ring_bytes") \
        == st["window_ring_bytes"]
    # tools/obs_report.py says the same beside its serving gauges
    report = obs_report.serving_section(
        {"values": monitor.stats("serve.")}, [])
    full = st["attn_full_decode_tokens_read"] / st["steps"]
    assert f"  attn: decode: {full:.1f} cached tokens read a step in the " \
        "full layers" in report
    assert f"rings {st['window_ring_bytes'] / 1e6:.3f} MB" in report
    assert "  moe: decode: " in report
    gpt = GPT(GPTConfig.tiny())
    gpt.eval()
    plain = ServeLoop(gpt, ServeConfig(max_active=2, kv_blocks=8,
                                       block_size=16, max_seq_len=64))
    monitor.reset(prefix="serve.")
    plain.serve([rng.randint(1, 1024, 5)], max_new_tokens=3)
    assert not set(laguna.ATTN_STATS) & set(plain.stats())
    assert "attn:" not in obs_report.serving_section(
        {"values": monitor.stats("serve.")}, [])
