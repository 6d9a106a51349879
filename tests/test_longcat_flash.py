"""LongCat-Flash style decoder behind ServeLoop against the plain float32
reference (text/models/reference/longcat_flash.py): the shortcut-
connected expert block over TWO paged latent caches a layer, the latent
attention's two scale factors, the softmax router over routed and
zero-compute experts, the held share, droplessness, the cache spec and
the counters."""
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
from paddle_tpu.nn.kv_pool import (CacheSpec, KVBlockPool, PagedLatentCache,
                                   cache_arenas, paged_caches)
from paddle_tpu.text.models import (GPT, GPTConfig, KimiK2Config,
                                    LongCatFlash, LongCatFlashConfig,
                                    decoder, longcat_flash)
from paddle_tpu.text.models.decoder import _rms, _rope, yarn_inv_freq
from paddle_tpu.text.models.kimi_k2 import LatentAttention
from paddle_tpu.text.models.reference import longcat_flash as ref

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import obs_report  # noqa: E402
from test_kimi_k2 import (TILE_EDGES, check_live_tiles,  # noqa: E402
                          forced_logits, rel_err)
from test_olmo_hybrid import (  # noqa: E402
    forced_logits as loop_forced_logits, small_loop)

HELD = (4, 8)            # routed experts 4..11 of 16; 8 zero experts after
ROUTER = {"moe_topk": 12, "routed_scaling_factor": 6.0,
          "zero_expert_num": 8}


def ref_config(cfg):
    """The reference's dict of published keys for a LongCatFlashConfig."""
    return dict(
        num_layers=cfg.num_layers, num_attention_heads=cfg.num_heads,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        mla_scale_q_lora=cfg.mla_scale_q_lora,
        mla_scale_kv_lora=cfg.mla_scale_kv_lora,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        moe_topk=cfg.num_experts_per_tok,
        routed_scaling_factor=cfg.routed_scaling_factor,
        zero_expert_num=cfg.zero_experts,
        router_width=cfg.num_experts + cfg.zero_experts)


def make_net(dtype="float32", **kw):
    paddle.seed(7)
    net = LongCatFlash(LongCatFlashConfig.tiny(experts_held=HELD,
                                               dtype=dtype, **kw))
    net.eval()
    params, _ = net.functional_state()
    rng = np.random.RandomState(11)
    for name in params:   # selection with a bias is what is compared: a
        # softmax score over 24 experts is ~0.04, so a bias of 0.004
        if name.endswith("router_bias"):
            params[name] = jnp.asarray(rng.normal(0, 0.004, 24), jnp.float32)
    net.load_functional_state(params)
    return net


@pytest.fixture(scope="module")
def net():
    return make_net()


# -- 1. served logits against the reference ---------------------------------

@pytest.mark.parametrize("dtype,limit", [("float32", 1e-4),
                                         # bf16 weights and activations:
                                         # 8 bits of mantissa through 2
                                         # layers of two sublayers each
                                         # reads 1e-2 to 3e-2
                                         ("bfloat16", 8e-2)])
def test_served_logits_match_reference(dtype, limit):
    net = make_net(dtype)
    params, _ = net.functional_state()
    ids = np.random.RandomState(0).randint(1, 256, 21 + 9)
    got = forced_logits(net, ids, prompt_len=21, bucket=32)
    want = np.asarray(ref.forward(params, ref_config(net.config), ids,
                                  HELD))[20:]
    assert got.shape == want.shape == (10, 256)
    for step in range(10):     # the prefill's logits, then 9 decode steps
        assert rel_err(got[step], want[step]) <= limit, step


@pytest.mark.parametrize("prompt_len", TILE_EDGES)
def test_a_prefill_computes_only_the_tiles_that_hold_a_token(
        net, monkeypatch, prompt_len):
    """Two latent attentions and two dense FFNs a layer under the tiles,
    the expert layer once over the bucket."""
    check_live_tiles(net, monkeypatch, prompt_len)


def test_served_logits_match_reference_through_live_tiles(monkeypatch):
    """ServeLoop's own prefill program over 3 tiles of a bucket of 4."""
    monkeypatch.setattr(decoder.PagedDecoder, "PREFILL_TILE", 16)
    net = make_net()
    params, _ = net.functional_state()
    ids = np.random.RandomState(1).randint(1, 256, 35 + 9)
    got = loop_forced_logits(net, small_loop(net), 1, ids, 35)  # bucket 64
    want = np.asarray(ref.forward(params, ref_config(net.config), ids,
                                  HELD))[34:]
    assert rel_err(got, want) < 1e-4


def test_serve_loop_tokens_are_the_references_greedy(net):
    params, _ = net.functional_state()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, 256, n) for n in (5, 17, 30, 9)]
    loop = ServeLoop(net, ServeConfig(max_active=2, kv_blocks=32,
                                      block_size=16, max_seq_len=128))
    outs = loop.serve(prompts, max_new_tokens=10)
    for prompt, out in zip(prompts, outs):
        logits = np.asarray(ref.forward(
            params, ref_config(net.config),
            np.concatenate([prompt, out]), HELD))
        np.testing.assert_array_equal(
            out, logits[len(prompt) - 1:-1].argmax(-1))


def test_uncut_model_matches_uncut_reference():
    paddle.seed(3)
    cfg = LongCatFlashConfig.tiny()      # every routed expert held
    net = LongCatFlash(cfg)
    net.eval()
    ids = np.random.RandomState(2).randint(1, 256, (2, 40))
    got = np.asarray(net(ids)._value)
    params, _ = net.functional_state()
    for row in range(2):
        want = ref.forward(params, ref_config(cfg), ids[row])
        assert rel_err(got[row], want) <= 1e-5


def test_decode_step_reaches_the_latent_kernel_twice_a_layer():
    """Over a pool of 128-token blocks the decode step's attention is the
    Pallas latent kernel, once a sublayer (8 a trace at the benchmark's
    four layers), and nothing is rejected."""
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    monitor.reset(prefix="pallas.")
    try:
        net = make_net()
        ids = np.random.RandomState(3).randint(1, 256, 21 + 3)
        got = forced_logits(net, ids, prompt_len=21, bucket=32,
                            block_size=128)
    finally:
        paddle.set_flags({"FLAGS_pallas_interpret": False})
    assert monitor.stat_get("pallas.hit.latent_paged_attention") \
        == 2 * net.config.num_layers
    assert not monitor.stats("pallas.gate_reject.latent_paged_attention.")
    params, _ = net.functional_state()
    want = np.asarray(ref.forward(params, ref_config(net.config), ids,
                                  HELD))[20:]
    assert rel_err(got, want) <= 1e-4


# -- 2. the shares add up ---------------------------------------------------

def expert_weights(rng, hidden, width, experts, zero):
    def normal(*shape):
        return jnp.asarray(rng.normal(0, 0.1, shape), jnp.float32)
    return {"router_weight": normal(hidden, experts + zero),
            "router_bias": normal(experts + zero) * 0.02,
            "gate": normal(experts, hidden, width),
            "up": normal(experts, hidden, width),
            "down": normal(experts, width, hidden)}


def share(w, held):
    """The leaves a chip holding `held` has of the whole layer's `w`."""
    first, count = held
    return {k: (v[first:first + count] if k in ("gate", "up", "down") else v)
            for k, v in w.items()}


def held_layer(w, held, top_k, zero=8, scaling=6.0):
    """A RoutedExperts holding `held` of the routed experts in `w`."""
    experts, hidden, width = w["gate"].shape
    layer = nn.RoutedExperts(hidden, width, experts, top_k, held=held,
                             routed_scaling_factor=scaling,
                             score_func="softmax", norm_topk_prob=False,
                             zero_experts=zero)
    layer.load_functional_state(share(w, held))
    return layer


def block_weights(rng, hidden=32):
    """One shortcut-connected layer's leaves at toy size, by the names
    the reference's `block` reads."""
    def normal(*shape):
        return jnp.asarray(rng.normal(0, 0.1, shape), jnp.float32)
    w = {"experts." + k: v
         for k, v in expert_weights(rng, hidden, 16, 16, 8).items()}
    for i in (0, 1):
        p = f"sub.{i}."
        w.update({p + "attn_norm": jnp.ones(hidden), p + "ffn_norm":
                  jnp.ones(hidden),
                  p + "attn.q_a": normal(hidden, 16),
                  p + "attn.q_norm": jnp.ones(16),
                  p + "attn.q_b": normal(16, 2 * 12),
                  p + "attn.kv_a": normal(hidden, 8 + 4),
                  p + "attn.kv_norm": jnp.ones(8),
                  p + "attn.kv_b": normal(8, 2 * 16),
                  p + "attn.o": normal(2 * 8, hidden),
                  p + "ffn.gate": normal(hidden, 48),
                  p + "ffn.up": normal(hidden, 48),
                  p + "ffn.down": normal(48, hidden)})
    return w


def test_decode_step_reaches_the_grouped_expert_kernel_once_a_layer():
    """With bf16 weights whose widths are whole lane tiles, every serve
    program's expert layer is the grouped Pallas kernel (ops/pallas/
    grouped_ffn.py): one hit a layer a trace, the prefill's and the
    decode step's, and nothing is rejected."""
    net = make_net("bfloat16", hidden_size=128, moe_intermediate_size=128)
    ids = np.random.RandomState(3).randint(1, 256, 21 + 3)
    off = forced_logits(net, ids, prompt_len=21, bucket=32)
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    monitor.reset(prefix="pallas.")
    try:
        got = forced_logits(net, ids, prompt_len=21, bucket=32)
    finally:
        paddle.set_flags({"FLAGS_pallas_interpret": False})
    assert monitor.stat_get("pallas.hit.grouped_expert_ffn") \
        == 2 * net.config.num_layers
    assert not monitor.stats("pallas.gate_reject.grouped_expert_ffn.")
    assert monitor.stat_get("pallas.grouped_expert_ffn.rows_per_block.t1") \
        == 16
    assert rel_err(got, off) <= 2e-2


def test_four_shares_add_up_to_the_uncut_layer():
    """16 routed + 8 zero experts in four shares of four: the four
    shares' routed parts, plus what every chip computes alike (both
    sublayers and the identity term of the zero experts) counted once,
    are the uncut reference's LAYER output."""
    rng = np.random.RandomState(5)
    w = block_weights(rng)
    cfg = dict(ROUTER, num_attention_heads=2, qk_nope_head_dim=8,
               qk_rope_head_dim=4, v_head_dim=8, q_lora_rank=16,
               kv_lora_rank=8, rms_norm_eps=1e-5, rope_theta=1e4,
               mla_scale_q_lora=True, mla_scale_kv_lora=True)
    x = jnp.asarray(rng.normal(0, 1, (50, 32)), jnp.float32)
    pos = jnp.arange(50, dtype=jnp.int32)
    experts = ref.sub_weights(w, "experts.")
    with jax.default_matmul_precision("highest"):
        whole = ref.block(w, cfg, x, pos, (0, 16))
        # what every chip computes alike: the layer with no routed expert
        # held (count 0) is both sublayers plus the identity term
        alike = ref.block(w, cfg, x, pos, (0, 0))
        f = ref.rms_norm(x + ref.attention(
            ref.sub_weights(w, "sub.0.attn."), cfg,
            ref.rms_norm(x, w["sub.0.attn_norm"], 1e-5), pos),
            w["sub.0.ffn_norm"], 1e-5)
        idx, weights = ref.route(experts, cfg, f)
        identity = jnp.sum(jnp.where(idx >= 16, weights, 0.0), -1)[:, None] * f
    total, pairs, zero, real = alike, 0, None, None
    for rank in range(4):
        y, counts, (real, zero, _) = held_layer(
            experts, (4 * rank, 4), 12).routed(f)
        total = total + (y - identity)   # the share's routed part alone
        pairs += int(counts.sum())
    # every routed pair is held by exactly one rank, the zero pairs by none
    assert pairs == int(real) and int(real) + int(zero) == 50 * 12
    assert 0 < int(zero) < 50 * 12
    assert rel_err(total, whole) <= 1e-5


# -- 3. the router ----------------------------------------------------------

def test_router_is_a_softmax_selected_on_bias_weighed_by_six_times_score():
    rng = np.random.RandomState(6)
    w = expert_weights(rng, 32, 16, 16, 8)
    bias = np.zeros(24, np.float32)
    bias[3] = 10.0               # always chosen, whatever its score
    bias[20] = 10.0              # a zero-compute expert likewise
    w["router_bias"] = jnp.asarray(bias)
    layer = held_layer(w, (0, 4), 6)
    x = jnp.asarray(rng.normal(0, 1, (40, 32)), jnp.float32)
    idx, weights = (np.asarray(a) for a in layer.route(x))
    logits = np.asarray(x, np.float64) @ np.asarray(w["router_weight"],
                                                    np.float64)
    scores = np.exp(logits - logits.max(1, keepdims=True))
    scores /= scores.sum(1, keepdims=True)       # over all 24, not the held
    assert (idx == 3).any(axis=1).all() and (idx == 20).any(axis=1).all()
    want = np.sort(np.argsort(-(scores + bias), axis=1)[:, :6], axis=1)
    np.testing.assert_array_equal(np.sort(idx, axis=1), want)
    # weights are 6 x the scores alone (the bias 10 is not in them) and
    # are NOT renormalised: they sum to 6 x the chosen scores' mass, < 6
    np.testing.assert_allclose(
        weights, 6.0 * np.take_along_axis(scores, idx, axis=1), rtol=1e-5)
    assert (weights.sum(1) < 6.0).all()
    with jax.default_matmul_precision("highest"):
        ridx, rweights = ref.route(w, dict(ROUTER, moe_topk=6), x)
    np.testing.assert_array_equal(np.sort(idx, 1), np.sort(ridx, 1))
    np.testing.assert_allclose(np.sort(weights, 1), np.sort(rweights, 1),
                               rtol=1e-5)


def test_partial_share_keeps_the_full_softmax():
    rng = np.random.RandomState(8)
    w = expert_weights(rng, 32, 16, 16, 8)
    x = jnp.asarray(rng.normal(0, 1, (30, 32)), jnp.float32)
    layer = held_layer(w, (5, 3), 6)
    idx, weights = layer.route(x)
    whole_idx, whole_weights = held_layer(w, (0, 16), 6).route(x)
    np.testing.assert_array_equal(idx, whole_idx)
    np.testing.assert_array_equal(weights, whole_weights)
    want = jnp.sum(jnp.where(idx >= 16, weights, 0.0), -1)[:, None] * x
    for e in (5, 6, 7):
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        want = want + w_e[:, None] * ref.swiglu(
            x, w["gate"][e], w["up"][e], w["down"][e])
    y, counts, pairs = layer.routed(x)
    assert rel_err(y, want) <= 1e-5
    assert int(counts.sum()) == int(((idx >= 5) & (idx < 8)).sum())
    assert int(pairs[0]) == int((idx < 16).sum())
    assert int(pairs[1]) == int((idx >= 16).sum())


# -- 4. zero-compute experts, droplessness ----------------------------------

def forced(rng, favoured):
    """Expert weights whose router scores nothing and whose bias picks
    `favoured`: every token chooses exactly those."""
    w = expert_weights(rng, 32, 16, 16, 8)
    w["router_weight"] = jnp.zeros((32, 24), jnp.float32)
    bias = np.zeros(24, np.float32)
    bias[list(favoured)] = 1.0
    w["router_bias"] = jnp.asarray(bias)
    return w


def test_a_token_of_zero_experts_only_costs_one_multiply_add():
    rng = np.random.RandomState(9)
    w = forced(rng, range(16, 22))          # six zero-compute experts
    x = jnp.asarray(rng.normal(0, 1, (40, 32)), jnp.float32)
    layer = held_layer(w, (0, 16), 6)       # every routed expert held
    y, counts, (real, zero, real_sq) = layer.routed(x)
    # a zero router scores every expert 1/24: six of them weigh 6 x 6/24
    assert rel_err(y, 1.5 * x) <= 1e-6
    assert int(counts.sum()) == 0           # no row in any block
    assert (int(real), int(zero), int(real_sq)) == (0, 40 * 6, 0)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(w, dict(ROUTER, moe_topk=6), x, (0, 16))
    assert rel_err(y, want) <= 1e-6


@pytest.mark.parametrize("favoured,pairs_on_held,zero_pairs", [
    ((4, 12, 13, 14, 16, 17), 300, 600),  # one pair a token on expert 4
    ((4, 5, 6, 7, 20, 23), 1200, 600),    # every routed pair is held
    ((0, 1, 12, 13, 2, 3), 0, 0),         # no held expert, no zero expert
])
def test_forced_routing_drops_nothing(favoured, pairs_on_held, zero_pairs):
    rng = np.random.RandomState(9)
    w = forced(rng, favoured)
    x = jnp.asarray(rng.normal(0, 1, (300, 32)), jnp.float32)
    y, counts, pairs = held_layer(w, (4, 4), 6).routed(x)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(share(w, (4, 4)), dict(ROUTER, moe_topk=6),
                                x, (4, 4))
    assert int(counts.sum()) == pairs_on_held
    assert int(pairs[1]) == zero_pairs
    assert int(pairs[0]) == 300 * 6 - zero_pairs
    assert int(pairs[2]) == 300 * (6 - zero_pairs // 300) ** 2
    scale = float(np.abs(np.asarray(want)).max()) or 1.0   # want may be 0
    assert float(np.abs(np.asarray(y) - np.asarray(want)).max()) \
        <= 1e-5 * scale


def test_pad_rows_route_nowhere_and_count_nowhere():
    rng = np.random.RandomState(10)
    w = expert_weights(rng, 32, 16, 16, 8)
    layer = held_layer(w, (0, 16), 6)
    x = jnp.asarray(rng.normal(0, 1, (24, 32)), jnp.float32)
    valid = jnp.arange(24) < 10
    y, counts, pairs = layer.routed(x, valid)
    alone = layer.routed(x[:10])
    assert int(pairs[0]) + int(pairs[1]) == 10 * 6
    assert int(counts.sum()) == int(pairs[0]) == int(alone[2][0])
    assert rel_err(y[:10], alone[0]) <= 1e-6
    assert not np.asarray(y[10:]).any()     # not even the identity term


def test_kimis_defaults_are_sigmoid_normalised_and_no_zero_experts():
    layer = nn.RoutedExperts(8, 8, 16, 4, held=(0, 4))
    assert (layer.score_func, layer.norm_topk_prob, layer.zero_experts) \
        == ("sigmoid", True, 0)
    assert tuple(layer.router_weight.shape) == (8, 16)
    with pytest.raises(ValueError):
        nn.RoutedExperts(8, 8, 16, 4, score_func="tanh")


# -- 5. the two attention factors -------------------------------------------

def cos_sin(cfg, b, s):
    inv_freq, _ = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, None)
    ang = jnp.arange(s, dtype=jnp.float32)[None, :, None] * inv_freq
    ang = jnp.broadcast_to(jnp.concatenate([ang, ang], -1),
                           (b, s, cfg.qk_rope_head_dim))
    return jnp.cos(ang), jnp.sin(ang)


def test_absorbed_decode_equals_decompressed_attention_with_the_factors():
    paddle.seed(4)
    cfg = LongCatFlashConfig.tiny()
    # the family's factors at the toy's sizes: (64 / 32)^1/2, (64 / 16)^1/2
    attn = LatentAttention(cfg, q_scale=2 ** 0.5, kv_scale=2.0)
    rng = np.random.RandomState(12)
    x = jnp.asarray(rng.normal(0, 1, (2, 17, 64)), jnp.float32)
    cos, sin = cos_sin(cfg, 2, 17)
    whole, _ = attn(x, cos, sin)                    # decompressed, no cache
    pool = KVBlockPool(8, 16)
    tables = jnp.asarray([pool.alloc(2), pool.alloc(2)], jnp.int32)
    (arena,), = pool.arenas_for([CacheSpec(PagedLatentCache, ((1, 24),))])
    cache = PagedLatentCache(arena, tables, jnp.zeros((2,), jnp.int32))
    chunk, cache = attn(x[:, :16], cos[:, :16], sin[:, :16], cache)
    step, cache = attn(x[:, 16:], cos[:, 16:], sin[:, 16:], cache)
    assert rel_err(chunk, whole[:, :16]) <= 1e-5
    assert rel_err(step, whole[:, 16:]) <= 1e-5     # absorbed, from cache
    # and the factors are in it: the reference's attention on the same
    params = {k: v._value for k, v in attn.named_parameters()}
    with jax.default_matmul_precision("highest"):
        on = ref.attention(params, ref_config(cfg), x[0], jnp.arange(17))
    assert rel_err(whole[0], on) <= 1e-5
    plain = LatentAttention(cfg)            # the same leaves, factors 1.0
    plain.load_functional_state(params)
    assert rel_err(plain(x, cos, sin)[0][0], on) > 1e-2


def test_factors_at_one_leave_kimis_projection_bit_for_bit():
    """`LatentAttention` at its defaults computes what it computed before
    it had factors: the projection written out as the parent had it."""
    paddle.seed(5)
    cfg = KimiK2Config.tiny(dtype="bfloat16")
    attn = LatentAttention(cfg)
    assert (attn.q_scale, attn.kv_scale) == (1.0, 1.0)
    rng = np.random.RandomState(13)
    x = jnp.asarray(rng.normal(0, 1, (2, 9, 64)), jnp.bfloat16)
    cos, sin = cos_sin(cfg, 2, 9)
    q_nope, q_r, latent = attn._project(x, cos, sin)
    c_q = _rms(x @ attn.q_a._value, attn.q_norm._value, attn.eps)
    q = (c_q @ attn.q_b._value).reshape(2, 9, attn.heads, attn.dn + attn.dr)
    kva = x @ attn.kv_a._value
    x32 = kva[..., :attn.rank].astype(jnp.float32)
    c_kv = (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True)
                                + attn.eps)
            * attn.kv_norm._value.astype(jnp.float32)).astype(x.dtype)
    np.testing.assert_array_equal(q_nope, q[..., :attn.dn])
    np.testing.assert_array_equal(
        q_r, _rope(q[..., attn.dn:], cos[:, :, None], sin[:, :, None]))
    np.testing.assert_array_equal(latent, jnp.concatenate(
        [c_kv, _rope(kva[..., attn.rank:], cos, sin)], -1))


# -- 6. two caches a layer: the spec, preemption ----------------------------

def test_cache_spec_builds_two_arenas_a_layer(net):
    spec = net.paged_cache_spec()
    assert spec == [CacheSpec(PagedLatentCache, ((1, 24),))] * 4
    pool = KVBlockPool(6, 16)
    arenas = pool.arenas_for(spec)
    assert len(arenas) == 2 * net.config.num_layers
    assert all([a.shape for a in layer] == [(7, 1, 24, 16)]
               for layer in arenas)
    full = LongCatFlashConfig(num_layers=4)
    assert (full.kv_lora_rank + full.qk_rope_head_dim, 2 * full.num_layers) \
        == (576, 8)
    assert KVBlockPool(1536, 128).arena_shape(1, 576) == (1537, 1, 576, 128)
    table, lens = jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32)
    caches = paged_caches(spec, arenas, table, lens)
    assert [type(c) for c in caches] == [PagedLatentCache] * 4
    assert [len(a) for a in cache_arenas(caches)] == [1] * 4


def test_preemption_and_reprefill_over_two_caches_a_layer(net):
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


# -- 7. the counters --------------------------------------------------------

def test_counters_tell_real_pairs_from_zero_pairs(net):
    loop = ServeLoop(net, ServeConfig(max_active=4, kv_blocks=32,
                                      block_size=16, max_seq_len=64))
    rng = np.random.RandomState(14)
    loop.serve([rng.randint(1, 256, n) for n in (5, 11, 19)],
               max_new_tokens=6)
    st = loop.stats()
    assert set(longcat_flash.SCMOE_STATS) <= set(st)
    layers, top_k = net.config.num_layers, net.config.num_experts_per_tok
    # pad rows of a bucketed prompt and empty decode slots are not routed
    assert st["moe_prefill_tokens"] == st["prefill_tokens"] == 35
    assert st["moe_decode_layer_steps"] == layers * st["steps"]
    for kind in ("decode", "prefill"):
        tokens = st[f"moe_{kind}_tokens"]
        real, zero = st[f"moe_{kind}_pairs_real"], st[f"moe_{kind}_pairs_zero"]
        assert real + zero == tokens * top_k * layers
        assert 0 < zero < real                   # 8 of 24 are zero experts
        assert 0 < st[f"moe_{kind}_pairs_held"] <= real
        # Σ r^2 >= (Σ r)^2 / n, with equality only if every token's count
        # of real pairs were the same
        assert st[f"moe_{kind}_pairs_real_sq"] * tokens * layers > real ** 2
    assert st["moe_decode_peak_pairs"] <= st["moe_decode_pairs_held"]
    assert monitor.stat_get("serve.moe_decode_pairs_zero") \
        == st["moe_decode_pairs_zero"]
    assert monitor.stat_get("serve.steps") == st["steps"]
    # tools/obs_report.py says the same beside its serving gauges
    report = obs_report.serving_section(
        {"values": monitor.stats("serve.")}, [])
    real = st["moe_decode_pairs_real"] / (st["moe_decode_tokens"] * layers)
    zero = st["moe_decode_pairs_zero"] / (st["moe_decode_tokens"] * layers)
    assert abs(real + zero - top_k) < 1e-9
    assert f"{real:.3f} real + {zero:.3f} zero a token a layer" in report
    assert f"{100 * zero / top_k:.1f}% of pairs on zero-compute experts" \
        in report
    assert "  moe: decode: " in report and "experts touched a layer-step" \
        in report
    gpt = GPT(GPTConfig.tiny())
    gpt.eval()
    plain = ServeLoop(gpt, ServeConfig(max_active=2, kv_blocks=8,
                                       block_size=16, max_seq_len=64))
    monitor.reset(prefix="serve.")
    plain.serve([rng.randint(1, 1024, 5)], max_new_tokens=3)
    assert not set(longcat_flash.SCMOE_STATS) & set(plain.stats())
    assert "moe:" not in obs_report.serving_section(
        {"values": monitor.stats("serve.")}, [])
