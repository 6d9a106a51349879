"""The Pallas latent-attention kernel of the decode step
(ops/pallas/decode_attention.latent_paged_decode_attention), interpreted:
parity with the plain-XLA form it replaces (`nn/kv_pool._latent_attn_paged`)
over ragged slots, the gate and its counters, the cut at the Kimi cell's
shapes, the plan its index maps read, and the served model's logits with
the kernel on against the kernel gated off."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import monitor
from paddle_tpu.nn.kv_pool import (KVBlockPool, _latent_attn_paged,
                                   latent_paged_attention)
from paddle_tpu.ops.pallas.decode_attention import (
    _latent_block_plan, latent_paged_blocks_per_step, latent_paged_cut,
    latent_paged_decode_attention, latent_paged_supported)

BS = 128
HIT = "pallas.hit.latent_paged_attention"
REJECT = "pallas.gate_reject.latent_paged_attention."


@pytest.fixture
def interpret():
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    monitor.reset(prefix="pallas.")
    yield
    paddle.set_flags({"FLAGS_pallas_interpret": False})


def rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def draw(lengths, table_blocks, dtype, h=8, dim=40, seed=0, parked=()):
    """q, a latent arena, tables and lengths for slots of `lengths`
    tokens already cached (the step's token is one of the live columns):
    each slot owns the blocks its tokens reach, scattered over the arena,
    the rest of its table the trash block; a parked slot's whole table."""
    rng = np.random.RandomState(seed)
    b = len(lengths)
    pool = KVBlockPool(b * table_blocks, BS)
    arena = jnp.asarray(
        rng.normal(0, 1, pool.arena_shape(1, dim)), dtype)
    q = jnp.asarray(rng.normal(0, 1, (b, h, 1, dim)), dtype)
    ids = rng.permutation(b * table_blocks) + 1
    tables = np.zeros((b, table_blocks), np.int32)
    for i, n in enumerate(lengths):
        if i in parked:
            continue
        used = min(n // BS + 1, table_blocks)
        tables[i, :used] = ids[i * table_blocks:i * table_blocks + used]
    return q, arena, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)


def plain(q, arena, tables, lengths, value_dim, scale=0.2):
    return _latent_attn_paged(q, arena, tables, lengths, scale=scale,
                              value_dim=value_dim)


# a slot of one token, the lengths around a block boundary, a full table
RAGGED3 = [0, 127, 128, 129, 383, 200]


@pytest.mark.parametrize("lengths,table_blocks", [
    (RAGGED3, 3),
    ([0, 127, 128, 129, 1500, 24 * BS - 1], 24),
    ([24 * BS - 1], 24),          # one slot, its table full
    ([77], 3),                    # one slot
    ([5] * 64, 3),                # 64 slots
], ids=["table3", "table24", "one_full", "one", "64slots"])
def test_kernel_matches_the_plain_path_in_float32(interpret, lengths,
                                                  table_blocks):
    q, arena, tables, lens = draw(lengths, table_blocks, jnp.float32)
    got = latent_paged_attention(q, arena, tables, lens, 0.2, 32)
    assert got.shape == (len(lengths), 8, 1, 32) and got.dtype == q.dtype
    assert rel_err(got, plain(q, arena, tables, lens, 32)) <= 2e-6
    assert monitor.stat_get(HIT) == 1
    assert not monitor.stats(REJECT)


@pytest.mark.parametrize("lengths,table_blocks",
                         [(RAGGED3, 3), ([1, 1023, 1024, 3071], 24)],
                         ids=["table3", "table24"])
def test_kernel_matches_the_plain_path_in_bfloat16(interpret, lengths,
                                                   table_blocks):
    """Both round the probabilities to bfloat16 before the second
    product, the plain path after it has normalised them and the kernel
    before (online softmax): 2**-8 of a probability either way, and the
    result rounded to bfloat16 once more. 2e-2 of the largest value holds
    both; against the float32 arithmetic on the same operands the kernel
    is no further off than the plain path."""
    q, arena, tables, lens = draw(lengths, table_blocks, jnp.bfloat16,
                                  dim=48)
    got = latent_paged_attention(q, arena, tables, lens, 0.2, 32)
    want = plain(q, arena, tables, lens, 32)
    assert got.dtype == jnp.bfloat16
    assert rel_err(got, want) <= 2e-2
    exact = plain(q.astype(jnp.float32), arena.astype(jnp.float32), tables,
                  lens, 32)
    assert rel_err(got, exact) <= max(1.5 * rel_err(want, exact), 1e-2)


def test_parked_slot_reads_the_trash_block(interpret):
    """A slot the scheduler parked has an all-zero table and length 0:
    it attends the trash block's first column, as the plain path does,
    and the slots beside it are not disturbed."""
    q, arena, tables, lens = draw([300, 0, 131], 3, jnp.float32,
                                  parked=(1,))
    assert not np.asarray(tables[1]).any()
    got = latent_paged_attention(q, arena, tables, lens, 0.2, 32)
    assert rel_err(got, plain(q, arena, tables, lens, 32)) <= 2e-6
    # one live column: the context IS that column's values
    np.testing.assert_allclose(
        np.asarray(got[1, :, 0]),
        np.broadcast_to(np.asarray(arena[0, 0, :32, 0]), (8, 32)),
        rtol=1e-6)


def test_values_are_a_prefix_of_the_keys(interpret):
    q, arena, tables, lens = draw(RAGGED3, 3, jnp.float32)
    for value_dim in (8, 40):
        got = latent_paged_decode_attention(q, arena, tables, lens, 0.2,
                                            value_dim)
        assert got.shape == (6, 8, 1, value_dim)
        assert rel_err(got, plain(q, arena, tables, lens,
                                  value_dim)) <= 2e-6


@pytest.mark.parametrize("reason,change", [
    ("shape", dict(s=2)),              # a chunk: more than one query row
    ("shape", dict(bs=64)),            # a block that is no lane tile
    ("shape", dict(value_dim=36)),     # values that end inside a tile
    ("flag_off", dict(flag=False)),
    ("backend", dict(interpret=False)),
], ids=["s2", "bs64", "value36", "flag_off", "backend"])
def test_gate_rejects_onto_the_plain_path(reason, change):
    monitor.reset(prefix="pallas.")
    s, bs = change.get("s", 1), change.get("bs", BS)
    value_dim = change.get("value_dim", 32)
    rng = np.random.RandomState(1)
    arena = jnp.asarray(rng.normal(0, 1, (7, 1, 40, bs)), jnp.float32)
    q = jnp.asarray(rng.normal(0, 1, (2, 8, s, 40)), jnp.float32)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    lens = jnp.asarray([bs + 3, 2 * bs], jnp.int32)
    paddle.set_flags({
        "FLAGS_pallas_interpret": change.get("interpret", True),
        "FLAGS_use_paged_attention": change.get("flag", True)})
    try:
        got = latent_paged_attention(q, arena, tables, lens, 0.2, value_dim)
    finally:
        paddle.set_flags({"FLAGS_pallas_interpret": False,
                          "FLAGS_use_paged_attention": True})
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(plain(q, arena, tables, lens,
                                          value_dim)))
    assert monitor.stat_get(REJECT + reason) == 1
    assert monitor.stat_get(HIT) == 0


def test_supported_reads_the_shapes():
    q, arena = (64, 64, 1, 576), (1025, 1, 576, 128)
    assert latent_paged_supported(q, arena, 2, 512)
    assert latent_paged_supported(q, arena, 4, 576)
    assert not latent_paged_supported((64, 64, 2, 576), arena, 2, 512)
    assert not latent_paged_supported(q, (1025, 64, 576, 128), 2, 512)
    assert not latent_paged_supported(q, (1025, 1, 512, 128), 2, 512)
    assert not latent_paged_supported(q, (1025, 1, 576, 64), 2, 512)
    assert not latent_paged_supported(q, (1025, 1, 576, 192), 2, 512)
    assert not latent_paged_supported(q, arena, 2, 520)   # bf16 tile: 16
    assert latent_paged_supported(q, arena, 4, 520)       # float32: 8
    assert not latent_paged_supported(q, arena, 2, 640)   # past the keys
    # a block no step can hold (blocks, q and out double-buffered)
    assert not latent_paged_supported((1, 64, 1, 16384),
                                      (9, 1, 16384, 256), 2, 512)


def test_cut_at_the_kimi_cells_shapes():
    """64 slots, 64 heads, 576 wide, 24-block tables of 128, bf16: the
    whole table in one grid step a slot, 147 456 B a live block."""
    q, arena = (64, 64, 1, 576), (1025, 1, 576, 128)
    assert latent_paged_cut(q, arena, 24, 2, 512) == {
        "blocks_per_step": 24, "grid_steps": 64, "live_bytes": 147456}
    # a table wider than a step may be is shared evenly by the steps it
    # needs; a short table is one step a slot
    assert latent_paged_blocks_per_step(64, 576, 512, 128, 40, 2) == 20
    assert latent_paged_blocks_per_step(64, 576, 512, 128, 3, 2) == 3
    assert latent_paged_cut(q, arena, 3, 2, 512)["grid_steps"] == 64
    # float32 blocks are twice the bytes: 18 fit the budget, so two steps
    # of twelve
    assert latent_paged_cut(q, arena, 24, 4, 512) == {
        "blocks_per_step": 12, "grid_steps": 128, "live_bytes": 294912}


def test_plan_repeats_a_dead_operands_last_block():
    """Operand g of grid step (slot, ik) holds logical block ik * 2 + g:
    a live one's id from the table, a dead one's the id the operand held
    last in grid order, the slot before's too, so that Pallas fetches
    nothing for it; before its first live block, the trash block."""
    tables = jnp.asarray([[11, 0, 0, 0], [21, 22, 23, 0], [31, 0, 0, 0],
                          [41, 42, 43, 44]], jnp.int32)
    lens = jnp.asarray([5, 2 * BS + 1, 0, 9 * BS], jnp.int32)
    plan = np.asarray(_latent_block_plan(tables, lens, BS, 2)).reshape(
        2, 4, 2)                      # [operand, slot, step]
    np.testing.assert_array_equal(
        plan[0], [[11, 11], [21, 23], [31, 31], [41, 43]])
    np.testing.assert_array_equal(
        plan[1], [[0, 0], [22, 22], [22, 22], [42, 44]])


@pytest.mark.parametrize("dtype,limit", [("float32", 1e-4),
                                         ("bfloat16", 6e-2)])
def test_served_logits_with_the_kernel_equal_those_without(interpret, dtype,
                                                           limit):
    """KimiK2 through what ServeLoop's programs trace, over a pool of
    128-token blocks: a prefill and nine decode steps, the decode steps'
    attention on the kernel (one hit a layer a trace), then the same
    with the kernel gated off. Within the tolerance the served logits
    are held to against the reference (tests/test_kimi_k2.py)."""
    from test_kimi_k2 import forced_logits, make_net
    net = make_net(dtype)
    ids = np.random.RandomState(0).randint(1, 256, 21 + 9)
    on = forced_logits(net, ids, prompt_len=21, bucket=32, block_size=BS)
    # one prefill trace (nothing new hit) and one decode-step trace
    assert monitor.stat_get(HIT) == net.config.num_layers
    assert not monitor.stats(REJECT)
    paddle.set_flags({"FLAGS_use_paged_attention": False})
    try:
        off = forced_logits(net, ids, prompt_len=21, bucket=32,
                            block_size=BS)
    finally:
        paddle.set_flags({"FLAGS_use_paged_attention": True})
    assert monitor.stat_get(REJECT + "flag_off") == net.config.num_layers
    assert monitor.stat_get(HIT) == net.config.num_layers
    assert on.shape == off.shape == (10, 256)
    for step in range(10):
        assert rel_err(on[step], off[step]) <= limit, step


def test_kernel_engages_in_serve_and_says_its_cut(interpret):
    """Behind ServeLoop, over a pool of 128-token blocks: the decode
    step's attention is the kernel (one hit a layer a trace, no
    rejection), the tokens are the float32 reference's greedy ones, and
    the cut is there as gauges, on the kernel's span and in the report
    of a dump."""
    import os
    import sys

    from paddle_tpu.core import trace
    from paddle_tpu.inference import ServeConfig, ServeLoop
    from paddle_tpu.text.models.reference import kimi_k2 as ref
    from test_kimi_k2 import HELD, make_net, ref_config
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import obs_report
    net = make_net()
    params, _ = net.functional_state()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, 256, n) for n in (5, 130, 30)]
    loop = ServeLoop(net, ServeConfig(max_active=2, kv_blocks=8,
                                      block_size=BS, max_seq_len=256))
    trace.reset()
    outs = loop.serve(prompts, max_new_tokens=6)
    for prompt, out in zip(prompts, outs):
        logits = np.asarray(ref.forward(
            params, ref_config(net.config, HELD),
            np.concatenate([prompt, out]), HELD))
        np.testing.assert_array_equal(
            out, logits[len(prompt) - 1:-1].argmax(-1))
    assert monitor.stat_get(HIT) == net.config.num_layers
    assert not monitor.stats(REJECT)
    cut = {"blocks_per_step": 2, "grid_steps": 2, "live_bytes": 40 * BS * 4}
    for name, value in cut.items():
        assert monitor.stat_get(
            f"pallas.latent_paged_attention.{name}.b2") == value
    spans = [sp.attrs for sp in trace.recent()
             if sp.name == "pallas/latent_paged_attention"]
    assert any(cut.items() <= attrs.items() for attrs in spans), spans
    report = obs_report.pallas_rates({"values": monitor.stats("pallas.")})
    assert "cut:b2=2blocks/stepx2steps,20KB/live block" in report
