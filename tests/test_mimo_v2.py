"""MiMo-V2-Flash behind ServeLoop (text/models/mimo_v2.py): keys deeper than
values, 4 | 8 key-value heads by layer kind, sliding layers whose softmax
starts from a sink logit a query head and whose ring is ONE block a slot,
no shared expert; the sink / two-width form of the grouped paged kernel;
against the plain float32 reference (text/models/reference/mimo_v2.py).
Toy size, CPU: keys of 24 over values of 16, 16 query heads over 4 | 8
(G = 4 | 2), window 8 = the pool's block, layers F | S S S S F S, layer 0
dense, 16 experts top-3."""
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
                                   paged_attention, paged_attention_ref,
                                   paged_caches, window_attention,
                                   window_fill, window_ring_shape,
                                   window_write, write_kv)
from paddle_tpu.text.models import MiMoV2Config, MiMoV2Flash
from paddle_tpu.text.models import decoder, laguna, mimo_v2
from paddle_tpu.text.models.reference import mimo_v2 as ref

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import obs_report  # noqa: E402
from test_olmo_hybrid import forced_logits, rel_err, small_loop  # noqa: E402

HELD = (4, 8)            # routed experts 4..11 of 16
VOCAB = 256
SOURCE_KEYS = (
    "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
    "num_attention_heads", "num_key_value_heads", "swa_num_attention_heads",
    "swa_num_key_value_heads", "head_dim", "v_head_dim", "sliding_window",
    "rope_theta", "swa_rope_theta", "partial_rotary_factor",
    "attention_value_scale", "add_swa_attention_sink_bias",
    "add_full_attention_sink_bias", "n_routed_experts",
    "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
    "layernorm_epsilon")


def ref_config(cfg):
    """The reference's dict of published keys for a MiMoV2Config."""
    return {k: getattr(cfg, k) for k in SOURCE_KEYS}


def make_net(dtype="float32", **kw):
    paddle.seed(7)
    # std 0.1: at 64 wide the attention and the router then move the
    # logits by as much as the embedding does
    net = MiMoV2Flash(MiMoV2Config.tiny(experts_held=HELD, dtype=dtype,
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
    # stream, the softmax and the sinks float32. Seven layers at 64 wide
    # read 0.01-0.03 over token draws; a routing decision that bf16 flips
    # near the third score moves one position by more, hence the room
    ("bfloat16", 0.1)])
def test_served_logits_match_reference(dtype, limit):
    """ServeLoop's own programs, a prompt longer than the window (21 of
    8), then 19 decode steps: the one-block ring wraps five times."""
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
    window, then wraps, one decode step at a time; float32: what the
    served path and the reference differ by is the order of the sums."""
    params, _ = net.functional_state()
    ids = np.random.RandomState(prompt_len).randint(1, VOCAB, prompt_len + 12)
    got = forced_logits(net, small_loop(net, block_size=8, kv_blocks=32),
                        0, ids, prompt_len)
    want = np.asarray(ref.forward(params, ref_config(net.config), ids,
                                  HELD))[prompt_len - 1:]
    assert rel_err(got, want) <= 1e-4


def test_served_logits_match_reference_through_live_tiles(monkeypatch):
    """ServeLoop's own prefill program over 3 tiles of a bucket of 4 (the
    tile is the nets with window layers' one constant), a sliding layer's
    tiles meeting the band only and starting from the sinks."""
    monkeypatch.setattr(decoder.PagedDecoder, "PREFILL_TILE", 16)
    net = make_net()
    assert [net.prefill_tile(b) for b in (16, 32, 64, 256)] \
        == [None, None, 16, 16]
    params, _ = net.functional_state()
    ids = np.random.RandomState(1).randint(1, VOCAB, 35 + 9)
    got = forced_logits(net, small_loop(net, block_size=8, kv_blocks=32),
                        1, ids, 35)                         # bucket 64
    want = np.asarray(ref.forward(params, ref_config(net.config), ids,
                                  HELD))[34:]
    assert rel_err(got, want) < 1e-4


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
    cfg = MiMoV2Config.tiny(init_std=0.1)     # every routed expert held
    net = MiMoV2Flash(cfg)
    net.eval()
    ids = np.random.RandomState(2).randint(1, VOCAB, (2, 40))
    got = np.asarray(net(ids)._value)
    params, _ = net.functional_state()
    for row in range(2):
        want = ref.forward(params, ref_config(cfg), ids[row])
        assert rel_err(got[row], want) <= 1e-4


def _sinks_out(net):
    """What benchmark/control_run_sink.py does to the served net: the
    program has no switch for it."""
    for name, p in net.named_parameters():
        if name.endswith("attn.sinks"):
            p._value = jnp.full_like(p._value, -1e30)


@pytest.mark.parametrize("control,floor", [
    # a sink at log 4 holds a third of a full 8-key window's mass: a
    # program whose sinks weigh nothing is a different model, not a
    # rounding
    ("sinks_out", 2e-2),
    ({"kv_round_to": "float8_e4m3fn"}, 1e-3)])
def test_the_benchmarks_controls_are_different_answers(control, floor):
    """Sinks at -1e30 and `kv_round_to` change what is attended to and
    nothing else: the same seeded weights, another answer."""
    ids = np.random.RandomState(0).randint(1, VOCAB, (1, 24))
    plain = make_net()
    low = make_net(**({} if control == "sinks_out" else control))
    for (name, a), (_, b) in zip(plain.named_parameters(),
                                 low.named_parameters()):
        np.testing.assert_array_equal(np.asarray(a._value),
                                      np.asarray(b._value), err_msg=name)
    if control == "sinks_out":
        _sinks_out(low)
    assert floor < rel_err(np.asarray(low(ids)._value),
                           np.asarray(plain(ids)._value))


# -- 2. the layer's parts ---------------------------------------------------

def test_the_published_shape():
    """The source's numbers as `MiMoV2Config()` holds them, and what the
    decoder makes of them by layer kind."""
    cfg = MiMoV2Config()
    assert cfg.layer_types[:7] == [laguna.FULL] + [laguna.SLIDING] * 4 \
        + [laguna.FULL, laguna.SLIDING]
    assert cfg.layer_types.count(laguna.FULL) == 9 \
        and len(cfg.layer_types) == 48 and cfg.layer_types[-1] == laguna.FULL
    assert cfg.moe_layer_freq == [0] + [1] * 47
    assert (cfg.heads(laguna.FULL), cfg.heads(laguna.SLIDING)) \
        == ((64, 4), (64, 8))
    assert (cfg.has_sinks(laguna.FULL), cfg.has_sinks(laguna.SLIDING)) \
        == (False, True)
    for kind, theta in ((laguna.FULL, 5e6), (laguna.SLIDING, 1e4)):
        r, freq, factor = laguna._rotary(cfg, kind)
        assert (r, factor) == (64, 1.0)      # int(192 * 0.334), no scaling
        np.testing.assert_allclose(
            np.asarray(freq), theta ** (-np.arange(0, 64, 2) / 64),
            rtol=1e-6)
    seven = MiMoV2Config(num_hidden_layers=7)
    assert seven.hybrid_layer_pattern == [0, 1, 1, 1, 1, 0, 1] \
        and seven.moe_layer_freq == [0, 1, 1, 1, 1, 1, 1]
    with pytest.raises(ValueError, match="7 layers need"):
        MiMoV2Config(num_hidden_layers=7, moe_layer_freq=[0, 1])


def test_rotary_turns_the_first_dims_and_the_value_is_scaled():
    cfg = MiMoV2Config.tiny(init_std=0.1)
    paddle.seed(1)
    attn = mimo_v2.SinkGroupedAttention(cfg, laguna.SLIDING)
    assert (attn.rot, attn.heads, attn.kv) == (8, 16, 8)
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(1, 5, 64), jnp.float32)
    pos = jnp.arange(5)[None]
    cos, sin = laguna._cos_sin(cfg, laguna.SLIDING, pos)
    q, k, v = attn.project(a, cos, sin)
    assert (q.shape, k.shape, v.shape) \
        == ((1, 5, 16, 24), (1, 5, 8, 24), (1, 5, 8, 16))
    qkv = np.asarray(a[0] @ attn.qkv._value)
    want_q = np.asarray(ref.rope(jnp.asarray(
        qkv[:, :16 * 24].reshape(5, 16, 24)), pos[0], 100.0, 0.334))
    np.testing.assert_allclose(np.asarray(q[0]), want_q, atol=1e-5)
    # position 0 is not turned; past the first 8 dims nothing ever is
    np.testing.assert_allclose(np.asarray(q[0, :, :, 8:]),
                               qkv[:, :16 * 24].reshape(5, 16, 24)[..., 8:],
                               atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(v[0]),
        0.707 * qkv[:, (16 + 8) * 24:].reshape(5, 8, 16), atol=1e-6)


def test_a_net_without_a_shared_expert_holds_no_shared_leaf(net):
    """`shared_width=0`: no shared leaf anywhere, the router Kimi's
    (sigmoid, selection bias, renormalised, factor 1), layer 0 dense, the
    sinks in the sliding layers only and float32 whatever the net's
    dtype."""
    names = [n for n, _ in net.named_parameters()]
    assert not [n for n in names if "shared" in n]
    ffn = net.blocks[1].ffn
    assert (ffn.score_func, ffn.norm_topk_prob, ffn.scaling, ffn.top_k,
            ffn.first, ffn.count, ffn.shared_width) \
        == ("sigmoid", True, 1.0, 3, 4, 8, 0)
    x = jnp.asarray(np.random.RandomState(2).randn(9, 64), jnp.float32)
    _, weights = ffn.route(x)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, rtol=1e-6)
    y, counts, _ = ffn.routed(x)
    assert y.shape == (9, 64) and counts.shape == (8,)
    assert isinstance(net.blocks[0].ffn, decoder.DenseFFN)
    assert [n for n in names if n.endswith("sinks")] \
        == [f"blocks.{i}.attn.sinks" for i in (1, 2, 3, 4, 6)]
    low = make_net("bfloat16")
    assert low.blocks[1].attn.sinks._value.dtype == jnp.float32
    assert low.blocks[1].attn.qkv._value.dtype == jnp.bfloat16
    assert low.blocks[0].attn.sink_logits() is None


@pytest.mark.parametrize("window,live,sinks,dv", [
    (None, None, False, 8), (None, 3, True, 4), (8, None, True, 4),
    (8, 2, True, 8), (16, 4, False, 4), (40, None, True, 12)])
def test_chunk_attention_tiles_equal_the_dense_mask(window, live, sinks, dv):
    """Tiles of 16 queries against the key tiles that meet the mask,
    values `dv` wide under keys of 8, a row's softmax started from its
    head's sink: the dense masked softmax with the sink as one more
    column; under `live` the tiles past it come out zero."""
    rng = np.random.RandomState(5)
    b, s, hk, g, d = 2, 64, 2, 3, 8
    q = rng.randn(b, s, hk * g, d).astype(np.float32)
    k = rng.randn(b, s, hk, d).astype(np.float32)
    v = rng.randn(b, s, hk, dv).astype(np.float32)
    sink = rng.randn(hk * g).astype(np.float32) + 1.0 if sinks else None
    i, j = np.arange(s)[:, None], np.arange(s)[None]
    seen = (j <= i) if window is None else (j <= i) & (j > i - window)
    scores = np.einsum("bqkgd,btkd->bkgqt", q.reshape(b, s, hk, g, d), k) * 0.3
    scores = np.where(seen, scores, -np.inf)
    top = scores.max(axis=-1, keepdims=True)
    p = np.exp(scores - top)
    denom = p.sum(axis=-1, keepdims=True)
    if sinks:
        denom = denom + np.exp(sink.reshape(1, hk, g, 1, 1) - top)
    want = np.einsum("bkgqt,btkd->bqkgd", p / denom, v) \
        .reshape(b, s, hk * g, dv)
    got = np.asarray(laguna._gqa_chunk_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        None if live is None else jnp.int32(live),
        None if sink is None else jnp.asarray(sink), scale=0.3,
        window=window, q_block=16))
    rows = s if live is None else 16 * live
    np.testing.assert_allclose(got[:, :rows], want[:, :rows], atol=2e-5)
    assert got.shape == want.shape and not got[:, rows:].any()


# -- 3. the sink / two-width form of the paged kernel -----------------------

def filled_pool(rng, hk, d, dv, bs, fills, dtype=jnp.float32):
    MB, NB = 4, 14
    pool = KVBlockPool(NB, bs)
    (ka, va), = pool.arenas_for(
        [CacheSpec(PagedKVCache, ((hk, d), (hk, dv)))], dtype)
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
                          jnp.asarray(rng.randn(1, ln, hk, dv), dtype))
    return ka, va, bt


def dense_attention(q, ka, va, bt, lens, scale, sinks):
    """The definition, in numpy float64, a slot and a head at a time:
    softmax over the slot's first lens + 1 cached tokens with the head's
    sink as one more term of the denominator."""
    b, h = q.shape[:2]
    hk, bs = ka.shape[1], ka.shape[3]
    out = np.zeros((b, h, 1, va.shape[2]))
    for i in range(b):
        n = int(lens[i]) + 1
        rows = np.asarray(bt[i])
        keys = np.concatenate([np.asarray(ka)[r] for r in rows], -1)[..., :n]
        vals = np.concatenate([np.asarray(va)[r] for r in rows], -1)[..., :n]
        for j in range(h):
            sc = np.asarray(q, np.float64)[i, j, 0] @ keys[j // (h // hk)] \
                * scale
            e = np.exp(sc - sc.max())
            denom = e.sum() + (0.0 if sinks is None else
                               np.exp(float(sinks[j]) - sc.max()))
            out[i, j, 0] = vals[j // (h // hk)] @ (e / denom)
    return out


@pytest.mark.parametrize("group,hk,d,dv,bs,sinks", [
    (8, 8, 24, 16, 8, True), (16, 4, 24, 16, 8, False),
    (16, 4, 24, 16, 8, True), (2, 8, 24, 16, 16, True),
    (8, 8, 192, 128, 128, True), (16, 4, 192, 128, 128, True),
    (16, 4, 192, 128, 128, False), (8, 8, 128, 128, 128, True),
    (3, 5, 64, 32, 128, True)])
def test_sink_paged_kernel_parity_ragged_lengths(interpret, group, hk, d,
                                                 dv, bs, sinks):
    """One token a slot over a K arena `d` deep and a V arena `dv` deep,
    G query heads a key-value head as the rows of one product (G = 8 and
    16 at the published 192 over 128), with and without sinks, slots at
    ragged fills (a block partly full, full, a partly full last block, a
    full table, empty): the kernel, interpreted, against
    `paged_attention_ref`, and that against the definition."""
    from paddle_tpu.ops.pallas.decode_attention import (
        paged_cut, paged_decode_attention, paged_supported)
    rng = np.random.RandomState(group + dv)
    fills = [bs // 2 + 1, bs, 2 * bs + 5, 4 * bs, 0]
    ka, va, bt = filled_pool(rng, hk, d, dv, bs, fills)
    assert (ka.shape[2], va.shape[2]) == (d, dv)
    b = len(fills)
    shape = (b, group * hk, 1, d)
    assert paged_supported(shape, tuple(ka.shape), ka.dtype.itemsize, dv)
    assert paged_cut(shape, tuple(ka.shape), 4, ka.dtype.itemsize,
                     max_steps=14 + b, d_v=dv) \
        == {"heads_per_step": hk, "grid_steps": 19}
    q = jnp.asarray(rng.randn(*shape), jnp.float32)
    sink = jnp.asarray(rng.randn(group * hk) + 2.0, jnp.float32) \
        if sinks else None
    lens = jnp.asarray([max(ln - 1, 0) for ln in fills], jnp.int32)
    scale = d ** -0.5
    out = paged_decode_attention(q, ka, va, bt, lens, scale, sinks=sink)
    want = paged_attention_ref(q, ka, va, bt, lens, scale, sink)
    assert out.shape == want.shape == (b, group * hk, 1, dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(want)[:4],
        dense_attention(q, ka, va, bt, lens, scale, sink)[:4], atol=2e-5)
    # bounded at the live pairs themselves the list still holds them all
    bounded = paged_decode_attention(q, ka, va, bt, lens, scale,
                                     max_steps=12, sinks=sink)
    np.testing.assert_array_equal(np.asarray(bounded), np.asarray(out))
    if sinks:       # a sink is mass taken from the values: never a no-op
        bare = paged_decode_attention(q, ka, va, bt, lens, scale)
        assert rel_err(bare, out) > 1e-2
        # a sink far below every score is no sink
        gone = paged_decode_attention(q, ka, va, bt, lens, scale,
                                      sinks=jnp.full_like(sink, -1e9))
        np.testing.assert_allclose(np.asarray(gone), np.asarray(bare),
                                   atol=1e-6)


def test_without_sinks_and_at_one_width_the_kernel_is_the_program_it_was():
    """`sinks=None`, `d_v == d`: the grouped call traces to the eight
    operands, the three blocked ones among them, and the body it had
    before it took either (no third blocked operand, no branch on it);
    with sinks to a ninth operand and a body that reads it. (Laguna's
    whole decode step is counted in tests/test_chip_smoke.py.)"""
    import functools
    from paddle_tpu.ops.pallas.decode_attention import (
        _paged_grouped_call_once)
    rng = np.random.RandomState(3)
    ka, va, bt = filled_pool(rng, 2, 16, 16, 8, [5, 8, 21, 32, 0])
    q = jnp.asarray(rng.randn(5, 2, 8, 16), jnp.float32)
    lens = jnp.asarray([5, 8, 21, 32, 1], jnp.int32)
    call = functools.partial(_paged_grouped_call_once, scale=0.25,
                             interpret=False, steps=19)

    def pallas_eqn(*sinks):
        def find(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    return eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    found = find(sub)
                    if found is not None:
                        return found
        return find(jax.make_jaxpr(call)(q, ka, va, bt, lens, *sinks).jaxpr)

    plain, given_none = pallas_eqn(), pallas_eqn(None)
    body = str(plain.params["jaxpr"])
    assert body == str(given_none.params["jaxpr"])
    # lengths, slot, blk, phys, n_live (scalar prefetch), then q, K, V
    assert [v.aval.shape for v in plain.invars] == [
        (5,), (19,), (19,), (19,), (1,), (5, 2, 8, 16), (15, 2, 16, 8),
        (15, 2, 16, 8)]
    with_sinks = pallas_eqn(jnp.zeros((2, 8, 1), jnp.float32))
    assert [v.aval.shape for v in with_sinks.invars][8:] == [(2, 8, 1)]
    assert str(with_sinks.params["jaxpr"]) != body


def test_the_gate_reads_both_arenas_and_where_sinks_may_go():
    from paddle_tpu.ops.pallas.decode_attention import (
        _paged_step_bytes, paged_cut, paged_heads_per_step, paged_supported)
    full, ring = (6145, 4, 192, 128), (128, 8, 192, 128)
    # the cell's two calls: 64 query heads over 4 and over 8, bf16
    assert paged_supported((128, 64, 1, 192), full, 2, 128)
    assert paged_supported((128, 64, 1, 192), ring, 2, 128)
    assert not paged_supported((128, 64, 1, 128), full, 2, 128)  # q's depth
    assert not paged_supported((128, 64, 1, 192), full, 2, 100)  # sublanes
    assert not paged_supported((1, 64, 256, 192), full, 2, 128)  # a chunk
    # a table of 112 blocks: the work list is the pool's 6144 blocks and a
    # step a slot; a ring of one block: a step a slot
    assert paged_cut((128, 64, 1, 192), full, 112, 2,
                     max_steps=6144 + 128, d_v=128) \
        == {"heads_per_step": 4, "grid_steps": 6272}
    assert paged_cut((128, 64, 1, 192), ring, 1, 2, max_steps=127 + 128,
                     d_v=128) == {"heads_per_step": 8, "grid_steps": 128}
    # values narrower than keys cost a step less; `d_v` left out is `d`
    assert _paged_step_bytes(4, 16, 192, 128, 2, 128) \
        < _paged_step_bytes(4, 16, 192, 128, 2) \
        == _paged_step_bytes(4, 16, 192, 128, 2, 192)
    assert paged_heads_per_step(25, 8, 64, 128, 2) \
        == paged_heads_per_step(25, 8, 64, 128, 2, d_v=64) == 25
    # what the gate says of a V arena that does not fit, and of sinks
    # where the kernel takes none; the fallback serves them all
    from paddle_tpu.nn.kv_pool import _paged_kernel_eligible
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    try:
        rng = np.random.RandomState(0)
        ka, va, bt = filled_pool(rng, 2, 16, 8, 8, [5, 9])
        lens = jnp.asarray([4, 8], jnp.int32)
        q4 = jnp.asarray(rng.randn(2, 4, 1, 16), jnp.float32)
        sink4 = jnp.ones((4,), jnp.float32)
        for q, v, sinks, reason in (
                (q4, va[:, :1], None, "value_arena"),       # other heads
                (q4, va.astype(jnp.bfloat16), None, "value_arena"),
                (q4[:, :2], va, sink4[:2], "sinks"),   # one head a group
                (q4, va, sink4[:3], "sinks")):         # not one a head
            monitor.reset(prefix="pallas.")
            assert not _paged_kernel_eligible(q, ka, v, False, sinks)
            assert monitor.stats("pallas.gate_reject.") == {
                f"pallas.gate_reject.paged_decode_attention.{reason}": 1}
        monitor.reset(prefix="pallas.")
        one = paged_attention(q4[:, :2], ka, va, bt, lens, 0.25,
                              sinks=sink4[:2])         # rejected: the oracle
        assert monitor.stat_get("pallas.hit.paged_decode_attention") == 0
        np.testing.assert_allclose(
            np.asarray(one), dense_attention(q4[:, :2], ka, va, bt, lens,
                                             0.25, sink4[:2]), atol=2e-5)
        out = paged_attention(q4, ka, va, bt, lens, 0.25, sinks=sink4)
        assert monitor.stat_get("pallas.hit.paged_decode_attention") == 1
        assert monitor.stats("pallas.paged_decode_attention.") == {
            "pallas.paged_decode_attention.heads_per_step.b2s1g2": 2,
            "pallas.paged_decode_attention.grid_steps.b2s1g2": 8,
            "pallas.paged_decode_attention.value_dim.b2s1g2": 8,
            "pallas.paged_decode_attention.sinks.b2s1g2": 1}
        assert out.shape == (2, 4, 1, 8)
    finally:
        paddle.set_flags({"FLAGS_pallas_interpret": False})


def test_decode_step_reaches_the_sink_kernel_once_a_layer(interpret):
    """Every layer's decode attention is the paged kernel, the two full
    layers' over the pool's table at G = 4 and the five sliding layers'
    over one-block rings at G = 2 with sinks; the gauges say which."""
    net = make_net()
    ids = np.random.RandomState(3).randint(1, VOCAB, 21 + 3)
    monitor.reset(prefix="pallas.")
    got = forced_logits(net, small_loop(net, block_size=8, kv_blocks=32),
                        0, ids, 21)
    # two traces of the decode step (the test's and the loop's)
    assert monitor.stat_get("pallas.hit.paged_decode_attention") == 2 * 7
    assert not monitor.stats("pallas.gate_reject.paged_decode_attention.")
    assert monitor.stats("pallas.paged_decode_attention.") == {
        # tables of 16 blocks, a pool of 32
        "pallas.paged_decode_attention.heads_per_step.b2s1g4": 4,
        "pallas.paged_decode_attention.grid_steps.b2s1g4": 2 * 16,
        "pallas.paged_decode_attention.value_dim.b2s1g4": 16,
        "pallas.paged_decode_attention.sinks.b2s1g4": 0,
        # a ring of one block: a step a slot
        "pallas.paged_decode_attention.heads_per_step.b2s1g2": 8,
        "pallas.paged_decode_attention.grid_steps.b2s1g2": 2,
        "pallas.paged_decode_attention.value_dim.b2s1g2": 16,
        "pallas.paged_decode_attention.sinks.b2s1g2": 1}
    report = obs_report.pallas_rates({"values": monitor.stats("pallas.")})
    assert "cut:b2s1g2=8heads/stepx2steps,values 16 deep,sinks" in report
    assert "cut:b2s1g4=4heads/stepx32steps,values 16 deep" in report \
        and "cut:b2s1g4=4heads/stepx32steps,values 16 deep,sinks" \
        not in report
    params, _ = net.functional_state()
    want = np.asarray(ref.forward(params, ref_config(net.config), ids,
                                  HELD))[20:]
    assert rel_err(got, want) <= 1e-4


# -- 4. the one-block ring --------------------------------------------------

@pytest.mark.parametrize("count", [1, 3, 8, 9, 13, 24, 31, 32])
def test_window_fill_keeps_the_last_window_of_a_one_block_ring(count):
    rng = np.random.RandomState(count)
    h, d, bs = 2, 4, 8                      # window = block = 8
    chunk = rng.randn(1, 32, h, d).astype(np.float32)
    ring = window_fill(jnp.full((1, 1, h, d, bs), 7.0, jnp.float32),
                       jnp.asarray(chunk), jnp.int32(count))
    want = np.zeros((bs, h, d), np.float32)
    for p in range(max(0, count - bs), count):
        want[p % bs] = chunk[0, p]
    got = np.asarray(ring)[0].transpose(0, 3, 1, 2).reshape(bs, h, d)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("sinks", [False, True])
def test_a_stream_past_the_window_reads_exactly_its_last_window(sinks):
    """Token by token through `window_write` / `window_attention` over a
    K ring 6 deep and a V ring 4 deep of ONE block: a softmax over the
    last 8 keys (and the sink), slots at different lengths, far past the
    wrap. A token older than the window moves nothing."""
    rng = np.random.RandomState(0)
    slots, hk, g, d, dv, bs = 3, 2, 3, 6, 4, 8
    k_ring = jnp.zeros((slots,) + window_ring_shape(bs, bs, hk, d))
    v_ring = jnp.zeros((slots,) + window_ring_shape(bs, bs, hk, dv))
    assert (k_ring.shape, v_ring.shape) \
        == ((3, 1, 2, 6, 8), (3, 1, 2, 4, 8))
    sink = jnp.asarray(rng.randn(hk * g) + 1.0, jnp.float32) \
        if sinks else None
    start = np.asarray([0, 5, 0])
    keys = rng.randn(slots, 30, hk, d).astype(np.float32)
    vals = rng.randn(slots, 30, hk, dv).astype(np.float32)
    for step in range(28):
        lens = jnp.asarray(np.where(start <= step, step - start, 0),
                           jnp.int32)
        at = np.asarray(lens)
        k_ring = window_write(
            k_ring, lens, jnp.asarray(keys[np.arange(slots), at])[:, None])
        v_ring = window_write(
            v_ring, lens, jnp.asarray(vals[np.arange(slots), at])[:, None])
        q = jnp.asarray(rng.randn(slots, hk * g, 1, d), jnp.float32)
        got = np.asarray(window_attention(q, k_ring, v_ring, lens, 0.4,
                                          sink))
        for i in range(slots):
            lo = max(0, at[i] - bs + 1)
            sc = np.einsum("kgd,tkd->kgt",
                           np.asarray(q)[i, :, 0].reshape(hk, g, d),
                           keys[i, lo:at[i] + 1]) * 0.4
            top = sc.max(-1, keepdims=True)
            p = np.exp(sc - top)
            denom = p.sum(-1, keepdims=True)
            if sinks:
                denom = denom + np.exp(
                    np.asarray(sink).reshape(hk, g, 1) - top)
            want = np.einsum("kgt,tkd->kgd", p / denom,
                             vals[i, lo:at[i] + 1]).reshape(hk * g, dv)
            np.testing.assert_allclose(got[i, :, 0], want, atol=2e-5)
    with pytest.raises(ValueError, match="no multiple"):
        window_ring_shape(4, 8, hk, d)      # a window under its block


def test_cache_spec_pages_two_layers_and_rings_five(net):
    spec = net.paged_cache_spec()
    ring = (((1, 8, 24, 8), None), ((1, 8, 16, 8), None))
    paged = CacheSpec(PagedKVCache, ((4, 24), (4, 16)))
    assert spec == [paged, *[CacheSpec(WindowKVCache, (), ring)] * 4, paged,
                    CacheSpec(WindowKVCache, (), ring)]
    arenas = KVBlockPool(6, 8).arenas_for(spec, slots=3)
    assert [[a.shape for a in layer] for layer in arenas][:2] \
        == [[(7, 4, 24, 8), (7, 4, 16, 8)],
            [(3, 1, 8, 24, 8), (3, 1, 8, 16, 8)]]
    caches = paged_caches(spec, arenas, jnp.zeros((1, 2), jnp.int32),
                          jnp.zeros((1,), jnp.int32))
    assert [type(c) for c in caches] == [
        PagedKVCache, *[WindowKVCache] * 4, PagedKVCache, WindowKVCache]
    assert [len(a) for a in cache_arenas(caches)] == [2] * 7
    # the published widths over a pool block of 128: pages [n, 4, 192 |
    # 128, 128], a ring of ONE block [1, 8, 192 | 128, 128] a slot
    full = MiMoV2Flash.paged_cache_spec(type("N", (), {
        "config": MiMoV2Config(num_hidden_layers=7)})())
    assert full[0] == CacheSpec(PagedKVCache, ((4, 192), (4, 128)))
    assert full[1].slots == (((1, 8, 192, 128), None),
                             ((1, 8, 128, 128), None))


def test_the_rings_do_not_grow_and_the_pool_counts_pages(net):
    """The three ring rules at a ring of one block: streams of 12 and of
    60 tokens hold the same ring bytes; the pool's blocks are the two
    full layers' pages only: a stream of n tokens owns ceil(n / block)
    of them, whatever the number of layers."""
    loop = ServeLoop(net, ServeConfig(max_active=2, kv_blocks=16,
                                      block_size=8, max_seq_len=64))
    rings = loop.stats()["state_bytes"]
    assert rings == 5 * 2 * (8 * 24 + 8 * 16) * 8 * 4   # layers slots k+v
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
    finally:
        loop.stop()
    assert max(used for used, _ in seen) == 8       # ceil(60 / 8), not x 7
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
                    [[[False, False, False, True, False, False, False,
                       False]], [[True] + [False] * 7]])


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


# -- 5. the shares add up ---------------------------------------------------

def test_sixteen_shares_add_up_to_the_uncut_layer():
    """256 routed experts top-8 at toy widths in sixteen shares of
    sixteen: the shares' routed parts, plus what every chip computes
    alike (here the attention alone: there is no shared expert), are the
    uncut reference's LAYER output."""
    rng = np.random.RandomState(5)
    hidden, width, experts, top_k = 32, 16, 256, 8

    def normal(*shape):
        return jnp.asarray(rng.normal(0, 0.1, shape), jnp.float32)

    ffn = {"router_weight": normal(hidden, experts),
           "router_bias": normal(experts),      # a selection bias that picks
           "gate": normal(experts, hidden, width),
           "up": normal(experts, hidden, width),
           "down": normal(experts, width, hidden)}
    w = {"attn_norm": jnp.ones(hidden), "ffn_norm": jnp.ones(hidden),
         "attn.qkv": normal(hidden, (8 + 4) * 12 + 4 * 8),
         "attn.o": normal(8 * 8, hidden), "attn.sinks": normal(8) + 1.0,
         **{"ffn." + k: v for k, v in ffn.items()}}
    cfg = dict(ref_config(MiMoV2Config.tiny()), n_routed_experts=experts,
               num_experts_per_tok=top_k, swa_num_attention_heads=8,
               swa_num_key_value_heads=4, head_dim=12, v_head_dim=8,
               hybrid_layer_pattern=[0, 1], moe_layer_freq=[0, 1])
    x = jnp.asarray(rng.normal(0, 1, (50, hidden)), jnp.float32)
    pos = jnp.arange(50, dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        whole = ref.block(w, cfg, x, pos, 1, (0, experts))
        alike = ref.block(w, cfg, x, pos, 1, (0, 0))   # the attention
        f = ref.rms_norm(alike, w["ffn_norm"], 1e-5)
    total, pairs = alike, 0
    for rank in range(16):
        held = (16 * rank, 16)
        layer = nn.RoutedExperts(hidden, width, experts, top_k, held=held,
                                 shared_width=0)
        mine = {k: (v[held[0]:held[0] + 16] if k in ("gate", "up", "down")
                    else v) for k, v in ffn.items()}
        layer.load_functional_state(mine)
        y, counts, _ = layer.routed(f)
        total = total + y
        pairs += int(counts.sum())
        # the share is the reference's, given the same held range
        with jax.default_matmul_precision("highest"):
            assert rel_err(y, ref.routed_part(mine, cfg, f, held)) <= 1e-5
    assert pairs == 50 * top_k       # every pair is held by exactly one
    assert rel_err(total, whole) <= 1e-5


# -- 6. the counters, and the bytes -----------------------------------------

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
    seen = [n + j + 1 for n in lens for j in range(5)]
    assert st["attn_full_decode_tokens_read"] == 2 * sum(seen)
    assert st["attn_window_decode_tokens_read"] \
        == 5 * sum(min(x, 8) for x in seen)
    assert st["window_ring_bytes"] == st["state_bytes"] > 0
    assert st["moe_prefill_tokens"] == st["prefill_tokens"] == 35
    assert st["moe_decode_layer_steps"] == 6 * st["steps"]
    # a net with no shared expert has the serving section's lines all the
    # same (tools/obs_report.py reads no shared-expert counter)
    report = obs_report.serving_section(
        {"values": monitor.stats("serve.")}, [])
    assert "  moe: decode: " in report and "  attn: decode: " in report
    assert f"rings {st['window_ring_bytes'] / 1e6:.3f} MB" in report


def test_bytes_module_counts_the_nets_leaves_and_the_pools_bytes():
    """benchmark/lib/bytes_mimo_v2.py against a built net (the tiny one,
    read through the same keys as the configuration file's) and the
    pool's allocation; the published numbers by hand."""
    from benchmark.lib import bytes_mimo_v2 as nbytes
    net = make_net()
    c = dict(ref_config(net.config), hidden_size=64, vocab_size=VOCAB,
             intermediate_size=96, moe_intermediate_size=32,
             n_routed_experts=HELD[1],
             share={"router_width": 16, "experts_held": list(HELD)})
    leaves = {n: int(np.prod(p.shape)) for n, p in net.named_parameters()}
    small = sum(v for n, v in leaves.items()
                if n.endswith(("norm", "sinks", "router_bias")))
    assert nbytes.held_params(c) == sum(leaves.values()) - small
    assert nbytes.attention_params(c, False) \
        == leaves["blocks.0.attn.qkv"] + leaves["blocks.0.attn.o"]
    assert nbytes.attention_params(c, True) \
        == leaves["blocks.1.attn.qkv"] + leaves["blocks.1.attn.o"]
    spec = net.paged_cache_spec()
    arenas = KVBlockPool(6, 8).arenas_for(spec, jnp.float32, slots=3)
    paged = sum(x.nbytes for layer, s in zip(arenas, spec)
                for x in layer[:len(s.arenas)])
    rings = sum(x.nbytes for layer, s in zip(arenas, spec)
                for x in layer[len(s.arenas):])
    assert paged == 7 * 8 * nbytes.paged_bytes_per_token(c, 4)
    assert rings == 3 * nbytes.ring_bytes_per_slot(c, 4)
    # the published widths, seven layers, 16 experts held, 1/8 vocabulary
    pub = dict(ref_config(MiMoV2Config(num_hidden_layers=7)),
               hidden_size=4096, vocab_size=19072, intermediate_size=16384,
               moe_intermediate_size=2048, n_routed_experts=16,
               share={"router_width": 256, "experts_held": [0, 16]})
    assert nbytes.attention_params(pub, False) == 89_128_960
    assert nbytes.attention_params(pub, True) == 94_371_840
    assert nbytes.expert_params(pub) == 25_165_824
    assert nbytes.paged_bytes_per_token(pub) == 5120
    assert nbytes.ring_bytes_per_slot(pub) == 5 * 128 * 5120
    assert 3.42e9 < nbytes.held_params(pub) < 3.44e9
    # a call of the kernel: keys and values once a group, q in, out, sinks
    ops, moved = nbytes.sink_gqa_call_cost(pub, True, 128, 128 * 128)
    assert moved == 128 * 128 * 5120 \
        + 128 * 64 * (192 + 128) * 2 + 64 * 4
    assert ops == 2 * 64 * (192 + 128) * 128 * 128
    _, moved = nbytes.sink_gqa_call_cost(pub, False, 128, 1000)
    assert moved == 1000 * 2560 + 128 * 64 * (192 + 128) * 2
