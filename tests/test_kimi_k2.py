"""Kimi-K2 style decoder behind ServeLoop against the plain float32
reference (text/models/reference/kimi_k2.py): the paged latent cache,
the two latent-attention paths, the expert layer that is told which
experts it holds (routing, the held share, droplessness), YaRN, and the
cache spec the pool builds its arenas from."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core import monitor
from paddle_tpu.inference import ServeConfig, ServeLoop
from paddle_tpu.nn.kv_pool import (CacheSpec, KVBlockPool, PagedKVCache,
                                   PagedLatentCache, cache_arenas,
                                   paged_caches)
from paddle_tpu.text.models import (GPT, GPTConfig, KimiK2, KimiK2Config,
                                    decoder, kimi_k2)
from paddle_tpu.text.models.decoder import (Rows, yarn_inv_freq,
                                            yarn_mscale)
from paddle_tpu.text.models.kimi_k2 import LatentAttention
from paddle_tpu.text.models.reference import kimi_k2 as ref
from test_olmo_hybrid import forced_logits as loop_forced_logits, small_loop

HELD = (4, 8)            # experts 4..11 of the router's 16
PUBLISHED_YARN = {"type": "yarn", "factor": 64, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096}


def ref_config(cfg, held):
    """The reference's dict of published keys for a KimiK2Config."""
    return dict(
        num_hidden_layers=cfg.num_layers,
        first_k_dense_replace=cfg.first_k_dense_replace,
        num_attention_heads=cfg.num_heads,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, rms_norm_eps=cfg.rms_norm_eps,
        rope_theta=cfg.rope_theta, rope_scaling=cfg.rope_scaling,
        num_experts_per_tok=cfg.num_experts_per_tok,
        routed_scaling_factor=cfg.routed_scaling_factor,
        n_routed_experts=held[1], router_width=cfg.num_experts)


def make_net(dtype="float32", **kw):
    paddle.seed(7)
    net = KimiK2(KimiK2Config.tiny(experts_held=HELD, dtype=dtype, **kw))
    net.eval()
    params, _ = net.functional_state()
    rng = np.random.RandomState(11)
    for name in params:   # selection with a bias is what is compared
        if name.endswith("router_bias"):
            params[name] = jnp.asarray(rng.normal(0, 0.01, 16), jnp.float32)
    net.load_functional_state(params)
    return net


@pytest.fixture(scope="module")
def net():
    return make_net()


def rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def forced_logits(net, ids, prompt_len, bucket, block_size=16):
    """Teacher-forced logits through what ServeLoop's programs trace: a
    bucket-padded prefill of ids[:prompt_len] (`_build_prefill`'s call
    of `_forward_paged`), then one decode step a further id
    (`build_decode_step`'s), over a paged pool. -> [len(ids) -
    prompt_len + 1, vocab]: row j predicts ids[prompt_len + j]."""
    params, buffers = net.functional_state()
    dtype = jnp.bfloat16 if net.config.dtype == "bfloat16" else jnp.float32
    pool = KVBlockPool(16, block_size)
    table = np.zeros((1, 8), np.int32)
    blocks = pool.alloc(pool.blocks_for(len(ids) + 1))
    table[0, :len(blocks)] = blocks
    arenas = pool.arenas_for(net.paged_cache_spec(), dtype)

    @jax.jit
    def step(params, arenas, tokens, lengths, last_index):
        net.load_functional_state(params, buffers)
        logits, caches, *_ = net._forward_paged(
            tokens, paged_caches(net.paged_cache_spec(), arenas,
                                 jnp.asarray(table), lengths),
            last_index=last_index)
        return logits, cache_arenas(caches)

    try:
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :prompt_len] = ids[:prompt_len]
        logits, arenas = step(params, arenas, jnp.asarray(padded),
                              jnp.zeros((1,), jnp.int32),
                              jnp.asarray([prompt_len - 1], jnp.int32))
        out = [logits[0]]
        for n in range(prompt_len, len(ids)):
            logits, arenas = step(params, arenas,
                                  jnp.asarray(ids[n:n + 1][None], jnp.int32),
                                  jnp.asarray([n], jnp.int32), None)
            out.append(logits[0])
    finally:
        net.load_functional_state(params, buffers)
    return np.stack([np.asarray(x, np.float32) for x in out])


# -- 1. served logits against the reference ---------------------------------

@pytest.mark.parametrize("dtype,limit", [("float32", 1e-4),
                                         # bf16 weights and activations:
                                         # 8 bits of mantissa through 3
                                         # layers reads ~1e-2
                                         ("bfloat16", 6e-2)])
def test_served_logits_match_reference(dtype, limit):
    net = make_net(dtype)
    params, _ = net.functional_state()
    ids = np.random.RandomState(0).randint(1, 256, 21 + 9)
    got = forced_logits(net, ids, prompt_len=21, bucket=32)
    want = np.asarray(ref.forward(params, ref_config(net.config, HELD), ids,
                                  HELD))[20:]
    assert got.shape == want.shape == (10, 256)
    for step in range(10):     # the prefill's logits, then 9 decode steps
        assert rel_err(got[step], want[step]) <= limit, step


# 1, tile, tile + 1, bucket - 1, bucket: prompt lengths at the edges of a
# 16-row tile and of the bucket of 256
TILE_EDGES = [1, 16, 17, 255, 256]


def check_live_tiles(net, monkeypatch, prompt_len):
    """With tiles of 16 rows a bucket of 256 runs its row-wise work and
    its queries' tiles over ceil(prompt_len / 16) tiles (`while`s and
    conditionals under jit) and leaves the other rows zero; logits and
    cached latents are those of the exact length computed whole. For both
    latent nets (tests/test_longcat_flash.py too)."""
    ids = np.random.RandomState(prompt_len).randint(1, 256, prompt_len)
    spec = net.paged_cache_spec()
    arenas = KVBlockPool(16, 16).arenas_for(spec)
    table = jnp.asarray(np.arange(1, 17, dtype=np.int32)[None])
    last = jnp.asarray([prompt_len - 1], jnp.int32)

    def caches():
        return paged_caches(spec, arenas, table, jnp.zeros((1,), jnp.int32))

    def cached(caches):     # [layers, 256 tokens, width] by position
        return np.stack([np.asarray(c.kv[1:17, 0]).transpose(0, 2, 1)
                         .reshape(256, -1) for c in caches])

    assert net.prefill_tile(256) is None        # 256 rows: one tile, whole
    exact, exact_caches, *_ = net._forward_paged(
        jnp.asarray(ids[None]), caches(), last_index=last)
    monkeypatch.setattr(decoder.PagedDecoder, "PREFILL_TILE", 16)
    # one tile, and two (the smallest bucket that holds its prompt: both
    # live), run whole
    assert [net.prefill_tile(b) for b in (16, 32, 64, 256)] \
        == [None, None, 16, 16]
    padded = np.zeros((1, 256), np.int32)
    padded[0, :prompt_len] = ids
    try:
        got, got_caches, *_ = jax.jit(
            lambda ids, last: net._forward_paged(ids, caches(),
                                                 last_index=last))(
            jnp.asarray(padded), last)
    finally:
        net.load_functional_state(*net.functional_state())
    assert rel_err(got, exact) < 1e-4
    assert rel_err(cached(got_caches)[:, :prompt_len],
                   cached(exact_caches)[:, :prompt_len]) < 1e-4
    live = (prompt_len - 1) // 16 + 1
    x, *_ = net._blocks(
        jnp.asarray(padded), jnp.arange(256)[None], caches(),
        Rows(valid=jnp.arange(256)[None] < prompt_len, live=jnp.int32(live),
             tile=16))
    assert float(jnp.abs(x[:, :prompt_len]).max(axis=-1).min()) > 0
    assert not np.asarray(x[:, live * 16:]).any()


@pytest.mark.parametrize("prompt_len", TILE_EDGES)
def test_a_prefill_computes_only_the_tiles_that_hold_a_token(
        net, monkeypatch, prompt_len):
    check_live_tiles(net, monkeypatch, prompt_len)


def test_served_logits_match_reference_through_live_tiles(monkeypatch):
    """ServeLoop's own prefill program over 3 tiles of a bucket of 4."""
    monkeypatch.setattr(decoder.PagedDecoder, "PREFILL_TILE", 16)
    net = make_net()
    params, _ = net.functional_state()
    ids = np.random.RandomState(1).randint(1, 256, 35 + 9)
    got = loop_forced_logits(net, small_loop(net), 1, ids, 35)  # bucket 64
    want = np.asarray(ref.forward(params, ref_config(net.config, HELD), ids,
                                  HELD))[34:]
    assert rel_err(got, want) < 1e-4


@pytest.mark.parametrize("live", [None, 1, 3, 4])
def test_causal_key_tiles_equal_the_whole_key_form(live):
    """A tile of queries against the keys up to its own tile is the
    attention over all the keys, masked; under `live` the tiles past it
    come out zero."""
    rng = np.random.RandomState(5)
    b, s, h, dn, dr, dv = 2, 64, 4, 16, 8, 16
    q_nope, k_nope = rng.randn(2, b, s, h, dn).astype(np.float32)
    q_r = rng.randn(b, s, h, dr).astype(np.float32)
    k_r = rng.randn(b, s, dr).astype(np.float32)
    v = rng.randn(b, s, h, dv).astype(np.float32)
    scores = (np.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + np.einsum("bqhd,bkd->bhqk", q_r, k_r)) * 0.2
    scores = np.where(np.tril(np.ones((s, s), bool)), scores, -np.inf)
    p = np.exp(scores - scores.max(axis=-1, keepdims=True))
    want = np.einsum("bhqk,bkhd->bqhd", p / p.sum(axis=-1, keepdims=True), v)
    got = kimi_k2._mla_chunk_attention(
        *map(jnp.asarray, (q_nope, q_r, k_nope, k_r, v)),
        None if live is None else jnp.int32(live), scale=0.2, q_block=16)
    rows = s if live is None else live * 16
    np.testing.assert_allclose(got[:, :rows], want[:, :rows], atol=1e-5)
    assert not np.asarray(got[:, rows:]).any()
    whole = kimi_k2._mla_chunk_attention(       # one tile: the old form
        *map(jnp.asarray, (q_nope, q_r, k_nope, k_r, v)), scale=0.2,
        q_block=64)
    np.testing.assert_allclose(whole, want, atol=1e-5)


def test_serve_loop_tokens_are_the_references_greedy(net):
    params, _ = net.functional_state()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, 256, n) for n in (5, 17, 30, 9)]
    loop = ServeLoop(net, ServeConfig(max_active=2, kv_blocks=32,
                                      block_size=16, max_seq_len=128))
    outs = loop.serve(prompts, max_new_tokens=10)
    for prompt, out in zip(prompts, outs):
        logits = np.asarray(ref.forward(
            params, ref_config(net.config, HELD),
            np.concatenate([prompt, out]), HELD))
        np.testing.assert_array_equal(
            out, logits[len(prompt) - 1:-1].argmax(-1))


def test_decode_step_reaches_the_grouped_expert_kernel_once_a_layer():
    """With bf16 weights whose widths are whole lane tiles, every serve
    program's expert layer is the grouped Pallas kernel (ops/pallas/
    grouped_ffn.py): one hit an expert layer a trace, the prefill's and
    the decode step's, and nothing is rejected."""
    net = make_net("bfloat16", hidden_size=128, moe_intermediate_size=128)
    ids = np.random.RandomState(3).randint(1, 256, 21 + 3)
    off = forced_logits(net, ids, prompt_len=21, bucket=32)
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    monitor.reset(prefix="pallas.")
    try:
        got = forced_logits(net, ids, prompt_len=21, bucket=32)
    finally:
        paddle.set_flags({"FLAGS_pallas_interpret": False})
    layers = net.config.num_layers - net.config.first_k_dense_replace
    assert monitor.stat_get("pallas.hit.grouped_expert_ffn") == 2 * layers
    assert not monitor.stats("pallas.gate_reject.grouped_expert_ffn.")
    assert monitor.stat_get("pallas.grouped_expert_ffn.rows_per_block.t1") \
        == 16
    assert rel_err(got, off) <= 2e-2


def test_uncut_model_matches_uncut_reference():
    paddle.seed(3)
    cfg = KimiK2Config.tiny()            # every expert held
    net = KimiK2(cfg)
    net.eval()
    ids = np.random.RandomState(2).randint(1, 256, (2, 40))
    got = np.asarray(net(ids)._value)
    params, _ = net.functional_state()
    for row in range(2):
        want = ref.forward(params, ref_config(cfg, (0, 16)), ids[row])
        assert rel_err(got[row], want) <= 1e-5


# -- 2. the share adds up ---------------------------------------------------

def expert_weights(rng, hidden, width, experts, shared):
    def normal(*shape):
        return jnp.asarray(rng.normal(0, 0.1, shape), jnp.float32)
    return {"ffn.router_weight": normal(hidden, experts),
            "ffn.router_bias": normal(experts) * 0.1,
            "ffn.gate": normal(experts, hidden, width),
            "ffn.up": normal(experts, hidden, width),
            "ffn.down": normal(experts, width, hidden),
            "ffn.shared_gate": normal(hidden, shared),
            "ffn.shared_up": normal(hidden, shared),
            "ffn.shared_down": normal(shared, hidden)}


def share(w, held):
    """The leaves a chip holding `held` has of the whole layer's `w`."""
    first, count = held
    return {k: (v[first:first + count]
                if k in ("ffn.gate", "ffn.up", "ffn.down") else v)
            for k, v in w.items()}


def held_layer(w, held, top_k, experts, scaling=2.827):
    """A RoutedExperts holding `held` of the experts in `w`."""
    hidden, width = w["ffn.gate"].shape[1:]
    layer = nn.RoutedExperts(hidden, width, experts, top_k, held=held,
                             routed_scaling_factor=scaling,
                             shared_width=w["ffn.shared_gate"].shape[1])
    layer.load_functional_state({k[4:]: v
                                 for k, v in share(w, held).items()})
    return layer


ROUTER = {"num_experts_per_tok": 8, "routed_scaling_factor": 2.827}


def test_32_shares_add_up_to_the_uncut_layer():
    rng = np.random.RandomState(5)
    w = expert_weights(rng, 32, 16, 64, 24)
    x = jnp.asarray(rng.normal(0, 1, (50, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(w, ROUTER, x, (0, 64))
        shared = ref.swiglu(x, w["ffn.shared_gate"], w["ffn.shared_up"],
                            w["ffn.shared_down"])
    total, pairs = shared, 0
    for rank in range(32):       # every chip computes the shared expert
        y, counts, _ = held_layer(w, (2 * rank, 2), 8, 64).routed(x)
        total = total + (y - shared)
        pairs += int(counts.sum())
    assert pairs == 50 * 8       # every pair is held by exactly one rank
    assert rel_err(total, whole) <= 1e-5


# -- 3. the router ----------------------------------------------------------

def test_router_selects_on_score_plus_bias_and_weighs_by_score():
    rng = np.random.RandomState(6)
    w = expert_weights(rng, 32, 16, 16, 24)
    bias = np.zeros(16, np.float32)
    bias[3] = 10.0               # always chosen, whatever its score
    w["ffn.router_bias"] = jnp.asarray(bias)
    layer = held_layer(w, (0, 4), 4, 16)
    x = jnp.asarray(rng.normal(0, 1, (40, 32)), jnp.float32)
    idx, weights = (np.asarray(a) for a in layer.route(x))
    scores = 1 / (1 + np.exp(-np.asarray(x) @ np.asarray(
        w["ffn.router_weight"])))
    assert (idx == 3).any(axis=1).all()
    want = np.sort(np.argsort(-(scores + bias), axis=1)[:, :4], axis=1)
    np.testing.assert_array_equal(np.sort(idx, axis=1), want)
    chosen = np.take_along_axis(scores, idx, axis=1)
    # weights from the scores alone (the bias 10 is not in them), over
    # the four chosen although only experts 0..3 are held, times 2.827
    np.testing.assert_allclose(
        weights, 2.827 * chosen / chosen.sum(1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(weights.sum(1), 2.827, rtol=1e-5)


def test_partial_share_keeps_the_full_normalisation():
    rng = np.random.RandomState(8)
    w = expert_weights(rng, 32, 16, 16, 24)
    x = jnp.asarray(rng.normal(0, 1, (30, 32)), jnp.float32)
    layer = held_layer(w, (5, 3), 4, 16)
    idx, weights = layer.route(x)
    want = ref.swiglu(x, w["ffn.shared_gate"], w["ffn.shared_up"],
                      w["ffn.shared_down"])
    for e in (5, 6, 7):
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        want = want + w_e[:, None] * ref.swiglu(
            x, w["ffn.gate"][e], w["ffn.up"][e], w["ffn.down"][e])
    y, counts, _ = layer.routed(x)
    assert rel_err(y, want) <= 1e-5
    assert int(counts.sum()) == int(((idx >= 5) & (idx < 8)).sum())


# -- 4. dropless ------------------------------------------------------------

@pytest.mark.parametrize("favoured,pairs_on_held", [
    ((4, 12, 13, 14), 300),     # every token sends one pair to expert 4
    ((4, 5, 6, 7), 1200),       # every pair of every token is held
    ((0, 1, 12, 13), 0),        # no token chooses a held expert
])
def test_forced_routing_drops_nothing(favoured, pairs_on_held):
    rng = np.random.RandomState(9)
    w = expert_weights(rng, 32, 16, 16, 24)
    w["ffn.router_weight"] = jnp.zeros((32, 16), jnp.float32)
    bias = np.zeros(16, np.float32)
    bias[list(favoured)] = 1.0
    w["ffn.router_bias"] = jnp.asarray(bias)
    x = jnp.asarray(rng.normal(0, 1, (300, 32)), jnp.float32)
    y, counts, _ = held_layer(w, (4, 4), 4, 16).routed(x)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(share(w, (4, 4)),
                                dict(ROUTER, num_experts_per_tok=4), x,
                                (4, 4))
    assert int(counts.sum()) == pairs_on_held
    assert rel_err(y, want) <= 1e-5


def test_pad_rows_route_nowhere():
    rng = np.random.RandomState(10)
    w = expert_weights(rng, 32, 16, 16, 24)
    layer = held_layer(w, (0, 16), 4, 16)
    x = jnp.asarray(rng.normal(0, 1, (24, 32)), jnp.float32)
    valid = jnp.arange(24) < 10
    y, counts, _ = layer.routed(x, valid)
    assert int(counts.sum()) == 10 * 4
    assert rel_err(y[:10], layer.routed(x[:10])[0]) <= 1e-6


def test_held_range_is_checked():
    with pytest.raises(ValueError):
        nn.RoutedExperts(8, 8, 16, 4, held=(12, 8))


# -- 5. the two attention paths, YaRN ---------------------------------------

def test_absorbed_decode_equals_decompressed_attention():
    paddle.seed(4)
    cfg = KimiK2Config.tiny()
    attn = LatentAttention(cfg)
    rng = np.random.RandomState(12)
    x = jnp.asarray(rng.normal(0, 1, (2, 17, 64)), jnp.float32)
    inv_freq, _ = yarn_inv_freq(8, cfg.rope_theta, cfg.rope_scaling)
    ang = jnp.arange(17, dtype=jnp.float32)[None, :, None] * inv_freq
    ang = jnp.broadcast_to(jnp.concatenate([ang, ang], -1), (2, 17, 8))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    whole, _ = attn(x, cos, sin)                    # decompressed, no cache
    pool = KVBlockPool(8, 16)
    tables = jnp.asarray([pool.alloc(2), pool.alloc(2)], jnp.int32)
    (arena,), = pool.arenas_for(
        [CacheSpec(PagedLatentCache, ((1, 40),))])
    cache = PagedLatentCache(arena, tables, jnp.zeros((2,), jnp.int32))
    chunk, cache = attn(x[:, :16], cos[:, :16], sin[:, :16], cache)
    step, cache = attn(x[:, 16:], cos[:, 16:], sin[:, 16:], cache)
    assert rel_err(chunk, whole[:, :16]) <= 1e-5
    assert rel_err(step, whole[:, 16:]) <= 1e-5     # absorbed, from cache
    np.testing.assert_array_equal(cache.lengths, [17, 17])


def test_yarn_frequencies_and_scale_at_the_published_settings():
    inv_freq, factor = yarn_inv_freq(64, 50000.0, PUBLISHED_YARN)
    inv_freq = np.asarray(inv_freq, np.float64)
    base = 50000.0 ** (-np.arange(0, 64, 2) / 64)
    # correction dimensions: 64 ln(4096 / (32 * 2 pi)) / (2 ln 50000) =
    # 8.91 -> 8 and 64 ln(4096 / (2 pi)) / (2 ln 50000) = 19.16 -> 20
    np.testing.assert_allclose(inv_freq[:9], base[:9], rtol=1e-5)
    np.testing.assert_allclose(inv_freq[20:], base[20:] / 64, rtol=1e-5)
    np.testing.assert_allclose(inv_freq[14], base[14] * (0.5 + 0.5 / 64),
                               rtol=1e-5)
    assert factor == 1.0                        # mscale == mscale_all_dim
    m = 0.1 * math.log(64) + 1
    assert abs(m - 1.4159) < 1e-4 and yarn_mscale(64, 1) == m
    attn = LatentAttention(KimiK2Config.tiny(
        qk_nope_head_dim=128, qk_rope_head_dim=64, hidden_size=128,
        rope_scaling=PUBLISHED_YARN))
    assert abs(attn.scale - 192 ** -0.5 * m * m) < 1e-9
    assert abs(attn.scale - 0.14468) < 1e-5
    np.testing.assert_allclose(
        ref.inv_freq({"qk_rope_head_dim": 64, "rope_theta": 50000,
                      "rope_scaling": PUBLISHED_YARN}), inv_freq, rtol=1e-6)


# -- 6. the pool's one interface, preemption on the latent cache ------------

def test_cache_spec_builds_the_arenas():
    gpt = GPT(GPTConfig.tiny())
    kimi = KimiK2(KimiK2Config.tiny())
    assert gpt.paged_cache_spec() \
        == [CacheSpec(PagedKVCache, ((2, 32), (2, 32)))] * 2
    assert kimi.paged_cache_spec() \
        == [CacheSpec(PagedLatentCache, ((1, 40),))] * 3
    pool = KVBlockPool(6, 16)
    for net_, shapes in ((gpt, [(7, 2, 32, 16)] * 2),
                         (kimi, [(7, 1, 40, 16)])):
        arenas = pool.arenas_for(net_.paged_cache_spec())
        assert len(arenas) == net_.config.num_layers
        assert [a.shape for a in arenas[0]] == shapes
    legacy = pool.arenas(2, 2, 32)      # the (layers, heads, dim) form
    assert [a.shape for a in legacy[1]] == [(7, 2, 32, 16)] * 2
    table, lens = jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32)
    both = gpt.paged_cache_spec() + kimi.paged_cache_spec()
    caches = paged_caches(both, pool.arenas_for(both), table, lens)
    assert [type(c) for c in caches] == [PagedKVCache] * 2 \
        + [PagedLatentCache] * 3
    assert [len(a) for a in cache_arenas(caches)] == [2, 2, 1, 1, 1]


def test_block_size_follows_the_widest_arena(net, monkeypatch):
    seen = []
    monkeypatch.setattr(
        "paddle_tpu.nn.kv_pool.pick_block_size",
        lambda max_seq, heads, dim, dtype: seen.append((heads, dim)) or 16)
    ServeConfig(max_active=2, kv_blocks=8, max_seq_len=64).resolve(
        net, jnp.float32)
    ServeConfig(max_active=2, kv_blocks=8, max_seq_len=64).resolve(
        GPT(GPTConfig.tiny()), jnp.float32)
    assert seen == [(1, 40), (2, 32)]


def test_preemption_and_reprefill_on_the_latent_cache(net):
    rng = np.random.RandomState(13)
    prompts = [rng.randint(1, 256, 6) for _ in range(3)]
    roomy = ServeLoop(net, ServeConfig(max_active=4, kv_blocks=16,
                                       block_size=8, max_seq_len=16))
    want = roomy.serve(prompts, max_new_tokens=8)
    tight = ServeLoop(net, ServeConfig(max_active=4, kv_blocks=3,
                                       block_size=8, max_seq_len=16))
    monitor.reset(prefix="serve.")
    got = tight.serve(prompts, max_new_tokens=8)
    assert monitor.stat_get("serve.preempted") > 0
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert tight.stats()["kv_pool_used_blocks"] == 0


def test_expert_counters_only_from_a_net_with_expert_layers(net):
    loop = ServeLoop(net, ServeConfig(max_active=4, kv_blocks=32,
                                      block_size=16, max_seq_len=64))
    rng = np.random.RandomState(14)
    loop.serve([rng.randint(1, 256, n) for n in (5, 11, 19)],
               max_new_tokens=6)
    st = loop.stats()
    assert set(decoder.MOE_STATS) <= set(st)
    # pad rows of a bucketed prompt and empty decode slots are not routed
    assert st["moe_prefill_tokens"] == st["prefill_tokens"] == 35
    assert st["moe_decode_layer_steps"] == 2 * st["steps"]
    pairs, tokens = st["moe_decode_pairs_held"], st["moe_decode_tokens"]
    assert 0 < pairs <= tokens * 2 * 4
    assert st["moe_decode_peak_pairs"] <= pairs
    assert st["moe_decode_experts_touched"] <= 8 * st["moe_decode_layer_steps"]
    assert monitor.stat_get("serve.moe_decode_pairs_held") == pairs
    gpt = GPT(GPTConfig.tiny())
    gpt.eval()
    plain = ServeLoop(gpt, ServeConfig(max_active=2, kv_blocks=8,
                                       block_size=16, max_seq_len=64))
    plain.serve([rng.randint(1, 1024, 5)], max_new_tokens=3)
    assert not set(decoder.MOE_STATS) & set(plain.stats())
