"""OLMo-hybrid decoder behind ServeLoop (text/models/olmo_hybrid.py): the
gated delta-rule kernels against the token-by-token recurrence, served
logits against the plain float32 reference (text/models/reference/
olmo_hybrid.py), and the pool's second kind of state: one matrix a slot
beside the arenas paged by token."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import monitor
from paddle_tpu.inference import ServeConfig, ServeLoop
from paddle_tpu.nn.kv_pool import (CacheSpec, KVBlockPool, PagedKVCache,
                                   SlotStateCache, cache_arenas,
                                   fresh_slot_rows, paged_caches,
                                   put_slot_rows)
from paddle_tpu.ops.pallas import gated_delta as gd
from paddle_tpu.text.models import (GPT, GPTConfig, OlmoHybrid,
                                    OlmoHybridConfig, olmo_hybrid)
from paddle_tpu.text.models.decoder import Rows
from paddle_tpu.text.models.reference import olmo_hybrid as ref

VOCAB = 128


def ref_config(cfg):
    """The reference's dict of published keys for an OlmoHybridConfig."""
    return dict(
        layer_types=list(cfg.layer_types),
        num_attention_heads=cfg.num_heads,
        linear_num_value_heads=cfg.linear_num_heads,
        linear_key_head_dim=cfg.linear_key_head_dim,
        linear_value_head_dim=cfg.linear_value_head_dim,
        linear_allow_neg_eigval=cfg.linear_allow_neg_eigval,
        rms_norm_eps=cfg.rms_norm_eps)


def make_net(dtype="float32", **kw):
    paddle.seed(7)
    # std 0.02 at hidden 64 leaves every pre-activation near zero (the
    # L2 norms would read their eps, the gates their centre): 0.1 spreads
    # them as the published widths do, and 4 x on what decides beta more
    kw.setdefault("init_std", 0.1)
    net = OlmoHybrid(OlmoHybridConfig.tiny(dtype=dtype, **kw))
    net.eval()
    params, _ = net.functional_state()
    for name in params:
        if name.endswith("mixer.b"):
            params[name] = params[name] * 4.0
    net.load_functional_state(params)
    return net


@pytest.fixture(scope="module")
def net():
    return make_net()


def rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def draw(rng, s, n=2, dk=8, dv=16, b=1):
    """q, k normalised as the layer does, v, log alpha, beta in (0, 2)."""
    q, k = rng.randn(2, b, s, n, dk).astype(np.float32)
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.randn(b, s, n, dv).astype(np.float32)
    g = -np.exp(rng.uniform(0, 2.7, (b, s, n))) \
        * rng.uniform(0.001, 0.2, (b, s, n))
    beta = 2 / (1 + np.exp(-rng.randn(b, s, n)))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]


def token_scan(q, k, v, g, beta):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([ref.delta_rule(q[i], k[i], v[i], jnp.exp(g[i]),
                                         beta[i]) for i in range(len(q))])


@pytest.fixture
def interpret():
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    monitor.reset(prefix="pallas.")
    yield
    paddle.set_flags({"FLAGS_pallas_interpret": False})


# -- 1. the kernels ----------------------------------------------------------

@pytest.mark.parametrize("s", [1, 63, 64, 65, 130])
def test_chunked_form_equals_the_token_scan(s):
    q, k, v, g, beta = draw(np.random.RandomState(s), s, b=2)
    o, state = gd.gdn_chunk_scan_ref(q, k, v, g, beta)
    assert o.shape == (2, s, 2, 16) and state.shape == (2, 2, 8, 16)
    np.testing.assert_allclose(o, token_scan(q, k, v, g, beta), atol=2e-5)


def test_chunked_form_stands_correlated_keys():
    """Every key the same and beta near 2: (I + A)^-1 by forward
    substitution stays exact where a Neumann product would cancel."""
    q, k, v, g, beta = draw(np.random.RandomState(3), 128)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    beta, g = jnp.full_like(beta, 1.98), jnp.zeros_like(g)
    o, _ = gd.gdn_chunk_scan_ref(q, k, v, g, beta)
    assert rel_err(o, token_scan(q, k, v, g, beta)) < 1e-3


@pytest.mark.parametrize("s", [64, 130])
def test_chunk_scan_kernel_equals_its_fallback(interpret, s):
    args = draw(np.random.RandomState(s + 1), s, n=6)
    want = gd.gdn_chunk_scan_ref(*args)
    got = gd.gdn_chunk_scan(*args)
    assert monitor.stat_get("pallas.hit.gdn_chunk_scan") == 1
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_step_kernel_equals_its_fallback(interpret, dtype):
    rng = np.random.RandomState(5)
    q, k, v, g, beta = (x[:, 0] for x in draw(rng, 1, b=4))
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    state = jnp.asarray(rng.randn(4, 8, 32), jnp.float32)
    want = gd.gdn_step_ref(state, q, k, v, jnp.exp(g), beta)
    got = gd.gdn_step(state, q, k, v, jnp.exp(g), beta)
    assert monitor.stat_get("pallas.hit.gdn_step") == 1
    # float32 q and k are spread as two bfloat16 terms: 16 bits of them
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-6 if dtype == "bfloat16"
                                   else 5e-5)


def test_gate_rejects_are_counted_off_the_chip():
    monitor.reset(prefix="pallas.")
    args = draw(np.random.RandomState(0), 8)
    gd.gdn_chunk_scan(*args)
    assert monitor.stat_get("pallas.gate_reject.gdn_chunk_scan.backend") == 1
    assert monitor.stat_get("pallas.hit.gdn_chunk_scan") == 0


def test_step_continues_the_chunk_scans_state():
    q, k, v, g, beta = draw(np.random.RandomState(9), 70)
    _, state = gd.gdn_chunk_scan_ref(*(x[:, :69] for x in (q, k, v, g, beta)))
    o, after = gd.gdn_step_ref(gd.state_layout(state), q[:, 69], k[:, 69],
                               v[:, 69], jnp.exp(g[:, 69]), beta[:, 69])
    whole, end = gd.gdn_chunk_scan_ref(q, k, v, g, beta)
    np.testing.assert_allclose(o, whole[:, 69], atol=1e-5)
    np.testing.assert_allclose(after, gd.state_layout(end), atol=1e-5)


def test_masked_tokens_change_nothing():
    q, k, v, g, beta = draw(np.random.RandomState(2), 96)
    live = jnp.arange(96)[None, :, None] < 41
    _, padded = gd.gdn_chunk_scan_ref(q, k, v, jnp.where(live, g, 0.0),
                                      jnp.where(live, beta, 0.0))
    _, exact = gd.gdn_chunk_scan_ref(*(x[:, :41] for x in (q, k, v, g, beta)))
    np.testing.assert_allclose(padded, exact, atol=1e-6)


# -- 2. served logits against the reference ----------------------------------

def serve_state(net, slots=2, blocks=16, block_size=16):
    dtype = jnp.bfloat16 if net.config.dtype == "bfloat16" else jnp.float32
    pool = KVBlockPool(blocks, block_size)
    spec = net.paged_cache_spec()
    return pool, spec, pool.arenas_for(spec, dtype, slots=slots)


def forced_logits(net, loop, slot, ids, prompt_len):
    """Teacher-forced logits of ServeLoop's OWN programs: `_prefill_jit`
    fills `slot` with ids[:prompt_len] padded to its bucket, then
    `_step_jit` advances every slot one id at a time. Those programs
    return tokens, so each logit comes from a program traced here that
    reads the same arenas and returns NO cache: the state advances once a
    position. -> [len(ids) - prompt_len + 1, vocab]: row j predicts
    ids[prompt_len + j]."""
    spec, A, MB = net.paged_cache_spec(), loop._A, loop._MB
    params, buffers = loop._params, loop._buffers
    blocks = loop._pool.alloc(loop._pool.blocks_for(len(ids) + 1))
    table = np.zeros((A, MB), np.int32)
    table[slot, :len(blocks)] = blocks
    key = np.zeros((2,), np.uint32)

    @jax.jit
    def logits_only(params, arenas, table, tokens, lengths, last_index):
        net.load_functional_state(params, buffers)
        if last_index is not None:
            arenas = fresh_slot_rows(spec, arenas)
        return net._forward_paged(
            tokens, paged_caches(spec, arenas, table, lengths),
            last_index=last_index)[0]

    try:
        bucket = loop._bucket(prompt_len)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :prompt_len] = ids[:prompt_len]
        row = jnp.asarray(table[slot:slot + 1])
        out = [logits_only(params, loop._arenas, row, jnp.asarray(padded),
                           jnp.zeros((1,), jnp.int32),
                           jnp.asarray([prompt_len - 1], jnp.int32))[0]]
        (loop._arenas, loop._tokens), *_ = loop._prefill_jit(
            params, buffers, loop._arenas, loop._tokens, row,
            jnp.asarray(padded), jnp.int32(prompt_len), jnp.asarray(key),
            jnp.int32(slot))
        for n in range(prompt_len, len(ids)):
            tokens = np.zeros((A,), np.int32)
            tokens[slot] = ids[n]
            lengths = np.zeros((A,), np.int32)
            lengths[slot] = n
            args = (jnp.asarray(table), jnp.asarray(lengths))
            out.append(logits_only(params, loop._arenas, args[0],
                                   jnp.asarray(tokens)[:, None], args[1],
                                   None)[slot])
            loop._arenas, *_ = loop._step_jit(
                params, buffers, loop._arenas, *args, jnp.asarray(tokens),
                jnp.asarray(np.tile(key, (A, 1))))
    finally:
        net.load_functional_state(params, buffers)
        loop._pool.free(blocks)
    return np.stack([np.asarray(x, np.float32) for x in out])


def small_loop(net, **kw):
    cfg = dict(max_active=2, kv_blocks=16, block_size=16, max_seq_len=128)
    cfg.update(kw)
    return ServeLoop(net, ServeConfig(**cfg))


@pytest.mark.parametrize("dtype,limit,periods", [
    ("float32", 1e-4, 2),
    # bf16 weights and matrix inputs. At 64 wide a delta-rule layer hands
    # on about twice the error it is given (its write is a residual, v -
    # S^T k, which cancels): one period reads 0.04-0.2 over token draws,
    # two 0.2-0.7, the published widths 0.015 a layer (PERF.md section 6)
    ("bfloat16", 0.15, 1)])
def test_served_logits_match_reference(dtype, limit, periods):
    net = make_net(dtype, layer_types=["linear_attention"] * 3
                   + ["full_attention"]) if periods == 1 else make_net(dtype)
    params, _ = net.functional_state()
    ids = np.random.RandomState(0).randint(1, VOCAB, 21 + 9)
    got = forced_logits(net, small_loop(net), 1, ids, 21)   # bucket 32
    want = ref.forward(params, ref_config(net.config), ids)[20:]
    assert got.shape == want.shape == (10, VOCAB)
    assert rel_err(got, want) < limit


@pytest.mark.parametrize("prompt_len", [1, 3, 63, 64, 65, 130])
def test_bucket_padding_does_not_move_the_state(net, prompt_len):
    """Lengths around the scan's chunk (64) and under the convolution's
    width (4): the state, the convolution's inputs and the logits of the
    padded bucket are those of the exact length."""
    ids = np.random.RandomState(prompt_len).randint(1, VOCAB, prompt_len)
    _, spec, arenas = serve_state(net, slots=1)
    table = jnp.asarray(np.arange(1, 17, dtype=np.int32)[None])
    seen = []
    for width in (prompt_len, 256):
        padded = np.zeros((1, width), np.int32)
        padded[0, :prompt_len] = ids
        logits, caches, counted = net._forward_paged(
            jnp.asarray(padded),
            paged_caches(spec, fresh_slot_rows(spec, arenas), table,
                         jnp.zeros((1,), jnp.int32)),
            last_index=jnp.asarray([prompt_len - 1], jnp.int32))
        seen.append((logits, caches[0].state, caches[0].conv))
        assert [int(x) for x in counted] == [width, 6]
    for exact, padded in zip(*seen):
        np.testing.assert_allclose(padded, exact, atol=2e-5)
    want = ref.forward(net.functional_state()[0], ref_config(net.config),
                       ids)[-1]
    assert rel_err(seen[1][0][0], want) < 1e-4


@pytest.mark.parametrize("prompt_len", [1, 16, 17, 63, 65, 130, 256])
def test_a_prefill_computes_only_the_tiles_that_hold_a_token(
        net, monkeypatch, prompt_len):
    """With tiles of 16 rows a bucket of 256 runs its row-wise work over
    ceil(prompt_len / 16) tiles (a `while` under jit) and leaves the other
    rows zero; logits, state and the convolution's inputs are those of the
    exact length computed whole."""
    ids = np.random.RandomState(prompt_len).randint(1, VOCAB, prompt_len)
    _, spec, arenas = serve_state(net, slots=1)
    table = jnp.asarray(np.arange(1, 17, dtype=np.int32)[None])
    last = jnp.asarray([prompt_len - 1], jnp.int32)

    def caches():
        return paged_caches(spec, fresh_slot_rows(spec, arenas), table,
                            jnp.zeros((1,), jnp.int32))

    exact, exact_caches, _ = net._forward_paged(
        jnp.asarray(ids[None]), caches(), last_index=last)
    monkeypatch.setattr(OlmoHybrid, "PREFILL_TILE", 16)
    padded = np.zeros((1, 256), np.int32)
    padded[0, :prompt_len] = ids
    try:
        got, got_caches, _ = jax.jit(
            lambda ids, last: net._forward_paged(ids, caches(),
                                                 last_index=last))(
            jnp.asarray(padded), last)
    finally:
        net.load_functional_state(*net.functional_state())
    # a jitted pass and an eager one of these 8 layers differ by 1e-4
    np.testing.assert_allclose(got, exact, atol=3e-4)
    for a, b in zip(got_caches[0][:2], exact_caches[0][:2]):
        np.testing.assert_allclose(a, b, atol=3e-4)   # state, conv inputs
    live = (prompt_len - 1) // 16 + 1
    x, *_ = net._blocks(
        jnp.asarray(padded), None, caches(),
        Rows(valid=jnp.arange(256)[None] < prompt_len, last=last,
             live=jnp.int32(live), tile=16))
    assert float(jnp.abs(x[:, :prompt_len]).min()) > 0
    assert not np.asarray(x[:, live * 16:]).any()


def test_served_logits_match_reference_through_live_tiles(monkeypatch):
    """ServeLoop's own prefill program over 3 tiles of a bucket of 4."""
    monkeypatch.setattr(OlmoHybrid, "PREFILL_TILE", 16)
    net = make_net()
    params, _ = net.functional_state()
    ids = np.random.RandomState(1).randint(1, VOCAB, 35 + 9)
    got = forced_logits(net, small_loop(net), 1, ids, 35)   # bucket 64
    want = ref.forward(params, ref_config(net.config), ids)[34:]
    assert rel_err(got, want) < 1e-4


def test_serve_loop_tokens_are_the_references_greedy(net):
    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, VOCAB, n) for n in (5, 17, 30)]
    outs = small_loop(net).serve(prompts, max_new_tokens=6)
    params, _ = net.functional_state()
    for prompt, out in zip(prompts, outs):
        ids = np.concatenate([prompt, out])
        logits = ref.forward(params, ref_config(net.config), ids)
        want = np.asarray(jnp.argmax(logits[len(prompt) - 1:-1], axis=-1))
        np.testing.assert_array_equal(out, want)


# -- 3. a slot's state from admission to retirement --------------------------

def test_reused_slot_gives_the_logits_of_a_fresh_server(net):
    rng = np.random.RandomState(6)
    first, second = rng.randint(1, VOCAB, 40), rng.randint(1, VOCAB, 25)
    used = small_loop(net)
    forced_logits(net, used, 0, first, 30)
    state = used._arenas[0][0]
    assert float(jnp.abs(state[0]).max()) > 0      # the slot was left dirty
    got = forced_logits(net, used, 0, second, 15)
    want = forced_logits(net, small_loop(net), 0, second, 15)
    np.testing.assert_array_equal(got, want)


def test_a_second_request_in_one_slot_is_served_as_alone(net):
    rng = np.random.RandomState(8)
    prompts = [rng.randint(1, VOCAB, n) for n in (12, 7, 20)]
    one_slot = small_loop(net, max_active=1).serve(prompts, max_new_tokens=5)
    for prompt, out in zip(prompts, one_slot):
        alone = small_loop(net, max_active=1).serve([prompt],
                                                    max_new_tokens=5)[0]
        np.testing.assert_array_equal(out, alone)


def test_preempted_request_continues_with_the_same_tokens(net):
    rng = np.random.RandomState(13)
    prompts = [rng.randint(1, VOCAB, 6) for _ in range(3)]
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


def test_idle_slots_keep_their_state(net):
    """A decode step leaves the rows of slots that no request owns (their
    table starts at the trash block) as they were."""
    _, spec, arenas = serve_state(net, slots=3)
    arenas = [tuple(a + 1 if a.ndim == 3 else a for a in layer)
              for layer in arenas]           # the per-slot arrays: nonzero
    table = np.zeros((3, 8), np.int32)
    table[1, 0] = 1
    _, caches, _ = net._forward_paged(
        jnp.asarray([[5], [6], [7]], jnp.int32),
        paged_caches(spec, arenas, jnp.asarray(table),
                     jnp.asarray([0, 4, 0], jnp.int32)))
    before, after = arenas[0][0], caches[0].state
    np.testing.assert_array_equal(after[0], before[0])
    np.testing.assert_array_equal(after[2], before[2])
    assert float(jnp.abs(after[1] - before[1]).max()) > 0


def test_two_sigmoid_reaches_beta_above_one(net):
    mixer = net.blocks[0].mixer
    assert mixer.beta_scale == 2.0
    x = jnp.take(net.embed._value,
                 jnp.asarray(np.random.RandomState(1).randint(1, VOCAB, 64)),
                 axis=0)
    beta = 2.0 * jax.nn.sigmoid(x @ mixer.b._value)
    assert float(beta.max()) > 1.0 and float(beta.min()) < 1.0
    assert 0.0 < float(beta.min()) and float(beta.max()) < 2.0
    a_log, dt_bias = mixer.A_log._value, mixer.dt_bias._value
    assert a_log.dtype == dt_bias.dtype == jnp.float32
    assert bool((jnp.exp(a_log) >= 1).all() & (jnp.exp(a_log) <= 16).all())
    dt = jax.nn.softplus(dt_bias)
    assert bool((dt >= 0.000999).all() & (dt <= 0.1001).all())


# -- 4. the pool's one interface ---------------------------------------------

def test_pool_builds_both_kinds_of_state_from_one_spec(net):
    state = CacheSpec(SlotStateCache, (), (((8, 32), "float32"),
                                           ((3, 64), None)))
    paged = CacheSpec(PagedKVCache, ((2, 32), (2, 32)))
    assert net.paged_cache_spec() == [state, state, state, paged] * 2
    pool, spec, arenas = serve_state(net, slots=3, blocks=6)
    assert [[a.shape for a in layer] for layer in arenas[2:4]] \
        == [[(3, 8, 32), (3, 3, 64)], [(7, 2, 32, 16)] * 2]
    assert arenas[0][0].dtype == jnp.float32
    bf16 = pool.arenas_for(spec, jnp.bfloat16, slots=3)
    assert [a.dtype for a in bf16[0]] == [jnp.float32, jnp.bfloat16]
    assert bf16[3][0].dtype == jnp.bfloat16
    table, lens = jnp.zeros((3, 2), jnp.int32), jnp.zeros((3,), jnp.int32)
    caches = paged_caches(spec, arenas, table, lens)
    assert [type(c) for c in caches[2:4]] == [SlotStateCache, PagedKVCache]
    assert [len(a) for a in cache_arenas(caches)] == [2, 2, 2, 2] * 2


def test_prefill_rows_go_in_and_out_of_a_slot(net):
    _, spec, arenas = serve_state(net, slots=3)
    arenas = [tuple(a + 1 for a in layer) for layer in arenas]
    rows = fresh_slot_rows(spec, arenas)
    assert rows[0][0].shape == (1, 8, 32) and not rows[0][0].any()
    assert rows[3][0] is arenas[3][0]               # arenas pass as they are
    written = [tuple(a + 2 for a in layer) for layer in rows]
    back = put_slot_rows(spec, arenas, written, jnp.int32(1))
    np.testing.assert_array_equal(back[0][0][1], written[0][0][0])
    np.testing.assert_array_equal(back[0][0][0], arenas[0][0][0])
    np.testing.assert_array_equal(back[0][1][2], arenas[0][1][2])
    assert back[3][1] is written[3][1]


def test_resolve_ignores_the_state_when_it_sizes_a_block(net, monkeypatch):
    seen = []
    monkeypatch.setattr(
        "paddle_tpu.nn.kv_pool.pick_block_size",
        lambda max_seq, heads, dim, dtype: seen.append((heads, dim)) or 16)
    ServeConfig(max_active=2, kv_blocks=8, max_seq_len=64).resolve(
        net, jnp.float32)
    assert seen == [(2, 32)]


def test_linear_counters_and_state_gauges(net):
    loop = small_loop(net, max_active=3)
    rng = np.random.RandomState(14)
    loop.serve([rng.randint(1, VOCAB, n) for n in (5, 11, 19)],
               max_new_tokens=6)
    st = loop.stats()
    assert set(olmo_hybrid.LINEAR_STATS) <= set(st)
    assert st["linear_prefill_tokens"] == st["prefill_tokens"] == 35
    assert st["linear_prefill_pad_tokens"] == (8 + 16 + 32) - 35
    assert st["linear_decode_layer_steps"] == 6 * st["steps"]
    assert st["state_bytes"] == 6 * 3 * (8 * 32 * 4 + 3 * 64 * 4)
    assert st["state_slots_used"] == 0              # all retired
    assert monitor.stat_get("serve.state_bytes") == st["state_bytes"]
    assert monitor.stat_get("serve.linear_prefill_tokens") == 35


def test_nets_that_cache_by_token_serve_as_before():
    gpt = GPT(GPTConfig.tiny())
    gpt.eval()
    assert all(layer.slots == () for layer in gpt.paged_cache_spec())
    plain = ServeLoop(gpt, ServeConfig(max_active=2, kv_blocks=8,
                                       block_size=16, max_seq_len=64))
    out = plain.serve([np.random.RandomState(3).randint(1, 1024, 5)],
                      max_new_tokens=3)
    assert len(out[0]) == 3
    st = plain.stats()
    assert st["state_bytes"] == 0 and st["state_slots_used"] == 0
    assert not set(olmo_hybrid.LINEAR_STATS) & set(st)


def test_layer_types_assemble_the_stack():
    cfg = OlmoHybridConfig.tiny(layer_types=["full_attention",
                                             "linear_attention"])
    net = OlmoHybrid(cfg)
    assert [type(b.mixer).__name__ for b in net.blocks] \
        == ["FullAttention", "LinearAttention"]
    assert cfg.num_layers == 2
    assert OlmoHybridConfig().layer_types.count("linear_attention") == 24
    with pytest.raises(ValueError, match="unknown layer type"):
        OlmoHybrid(OlmoHybridConfig.tiny(layer_types=["sliding"]))
    ids = np.random.RandomState(0).randint(1, VOCAB, (2, 12))
    want = [ref.forward(net.functional_state()[0], ref_config(cfg), row)
            for row in ids]
    assert rel_err(net(jnp.asarray(ids))._value, jnp.stack(want)) < 1e-4
