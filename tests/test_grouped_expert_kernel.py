"""The grouped SwiGLU kernel of the held experts (ops/pallas/
grouped_ffn.py) against the plain `fori_loop` form of
nn/layer/experts.py, in Pallas interpret mode: parity in float32 and
bf16 over the shapes and routings that stress the block plan, the
gate's rejections with their reasons, the cut, and the served logits of
the two nets that hold experts with the kernel on against gated off."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core import monitor
from paddle_tpu.nn.layer import experts
from paddle_tpu.ops.pallas import grouped_ffn as G

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import obs_report  # noqa: E402
from test_kimi_k2 import forced_logits, rel_err  # noqa: E402

H, I, N = 128, 512, 4          # two tiles of the expert width a block


@pytest.fixture
def interpret():
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    monitor.reset(prefix="pallas.")
    yield
    paddle.set_flags({"FLAGS_pallas_interpret": False})


def _weights(rng, n=N):
    def draw(*shape):
        return rng.normal(0, 0.1, shape).astype(np.float32)
    return draw(n, H, I), draw(n, H, I), draw(n, I, H)


def _routing(rng, T, K, width):
    idx = np.stack([rng.choice(width, K, replace=False) for _ in range(T)])
    return idx.astype(np.int32), rng.uniform(0.1, 1.0, (T, K)).astype(
        np.float32)


def _case(name):
    """-> (x, idx, weights, valid, gate, up, down, first), float32."""
    rng = np.random.default_rng(sum(name.encode()))
    T, K, width, first = 64, 4, 8, 0
    if name.startswith("tokens_"):
        T = int(name.split("_")[1])
    if name == "partial_share":
        first, width = 3, 12               # experts 3..6 of 12 held
    if name == "zero_compute_ids":
        width = 16                         # ids 4..15 are nobody's
    K = min(K, width)
    idx, w = _routing(rng, T, K, width)
    valid = np.ones((T,), bool)
    if name == "block_boundaries":
        # rows of 128: expert 0 nobody's, expert 1 exactly M pairs, expert
        # 2 M + 1 (a second block of one row), expert 3 one pair
        T, K = 256, 2
        idx = np.stack([np.where(np.arange(T) < 128, 1, 7),
                        np.where(np.arange(T) < 129, 2, 6)], 1)
        idx[200, 0] = 3
        idx = idx.astype(np.int32)
        w = rng.uniform(0.1, 1.0, (T, K)).astype(np.float32)
        valid = np.ones((T,), bool)
    if name == "one_expert":
        idx[:] = np.arange(K)[None] + 8    # nobody's ...
        idx[:, 0] = 2                      # ... but every token's first pair
    if name == "pad_rows":
        valid = rng.uniform(size=T) > 0.4
        valid[-7:] = False
    x = rng.normal(0, 1.0, (T, H)).astype(np.float32)
    return (x, idx, w, valid, *_weights(rng), first)


CASES = ["tokens_1", "tokens_64", "tokens_128", "tokens_256",
         "block_boundaries", "one_expert", "pad_rows", "partial_share",
         "zero_compute_ids"]


def _both(case, dtype):
    x, idx, w, valid, gate, up, down, first = case
    args = (jnp.asarray(x, dtype), jnp.asarray(idx), jnp.asarray(w),
            jnp.asarray(valid), jnp.asarray(gate, dtype),
            jnp.asarray(up, dtype), jnp.asarray(down, dtype), first)
    return (experts._routed_expert_ffn(*args),
            experts._grouped_expert_ffn(*args))


@pytest.mark.parametrize("name", CASES)
def test_float32_parity_with_the_loop(name, interpret):
    (want, want_counts), (got, got_counts) = _both(_case(name), jnp.float32)
    np.testing.assert_array_equal(got_counts, want_counts)
    assert got.shape == want.shape and got.dtype == jnp.float32
    scale = float(jnp.abs(want).max())
    assert scale > 0 or name == "tokens_1"
    assert float(jnp.abs(got - want).max()) <= 2e-6 * max(scale, 1e-30)


@pytest.mark.parametrize("name", CASES)
def test_bf16_no_further_from_float32_than_the_loop(name, interpret):
    case = _case(name)
    rounded = tuple(np.asarray(jnp.asarray(a, jnp.bfloat16).astype(
        jnp.float32)) if i in (0, 4, 5, 6) else a
        for i, a in enumerate(case))
    (exact, _), _ = _both(rounded, jnp.float32)
    (plain, counts), (kernel, kernel_counts) = _both(case, jnp.bfloat16)
    np.testing.assert_array_equal(kernel_counts, counts)
    scale = max(float(jnp.abs(exact).max()), 1e-30)
    plain_err = float(jnp.abs(plain - exact).max()) / scale
    kernel_err = float(jnp.abs(kernel - exact).max()) / scale
    assert kernel_err <= 1.5 * plain_err + 1e-6, (kernel_err, plain_err)


def test_block_boundaries_case_has_the_counts_it_names():
    _, idx, _, _, *_ = _case("block_boundaries")
    counts = np.bincount(idx.ravel(), minlength=8)
    assert list(counts[:4]) == [0, 128, 129, 1]
    assert experts.block_rows(idx.shape[0]) == 128


def _layer(dtype="bfloat16", hidden=H):
    paddle.seed(5)
    return nn.RoutedExperts(hidden, I, 16, 4, held=(4, 8), dtype=dtype,
                            init_std=0.1)


def _x(T, hidden, dtype):
    return jnp.asarray(np.random.default_rng(T).normal(size=(T, hidden)),
                       dtype)


def test_an_admitted_call_counts_a_hit_and_leaves_its_cut(interpret):
    layer = _layer()
    x = _x(64, H, jnp.bfloat16)
    got = layer.routed(x)
    assert monitor.stat_get("pallas.hit.grouped_expert_ffn") == 1
    assert not monitor.stats("pallas.gate_reject.grouped_expert_ffn.")
    cut = {k.split(".")[2]: v for k, v in
           monitor.stats("pallas.grouped_expert_ffn.").items()
           if k.endswith(".t64")}
    assert cut == {"rows_per_block": 64, "tile_bytes": 3 * H * 256 * 2,
                   "grid_steps": (64 * 4 // 64 + 8) * (I // 256)}
    paddle.set_flags({"FLAGS_pallas_interpret": False})
    want = layer.routed(x)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])
    assert rel_err(got[0], want[0]) <= 1e-2     # two bf16 roundings apart
    line = obs_report.pallas_rates({"values": monitor.stats("pallas.")})
    assert "grouped_expert_ffn" in line
    assert "cut:t64=64rows/blockx24steps,197KB/step" in line


@pytest.mark.parametrize("reason,flag,dtype,hidden,tokens", [
    ("backend", False, "bfloat16", H, 64),
    ("dtype", True, "float32", H, 64),
    ("shape", True, "bfloat16", 64, 64),
])
def test_a_rejected_call_takes_the_loop_and_says_why(reason, flag, dtype,
                                                     hidden, tokens):
    layer = _layer(dtype, hidden=hidden)
    x = _x(tokens, hidden, jnp.dtype(dtype))
    want = experts._routed_expert_ffn(
        x, *layer.route(x), jnp.ones((tokens,), bool), layer.gate._value,
        layer.up._value, layer.down._value, layer.first)
    paddle.set_flags({"FLAGS_pallas_interpret": flag})
    monitor.reset(prefix="pallas.")
    try:
        y, counts, _ = layer.routed(x)
    finally:
        paddle.set_flags({"FLAGS_pallas_interpret": False})
    assert monitor.stats("pallas.gate_reject.grouped_expert_ffn.") == {
        f"pallas.gate_reject.grouped_expert_ffn.{reason}": 1}
    assert not monitor.stat_get("pallas.hit.grouped_expert_ffn")
    np.testing.assert_array_equal(counts, want[1])
    np.testing.assert_array_equal(y, want[0].astype(x.dtype))


def test_tokens_and_vmem_are_gated_by_shape_alone(interpret):
    """The gate reads shapes only. The shares' decode steps and their
    buckets up to 512 tokens are admitted; a longer prefill is not (a
    block's work on y grows with the tokens: `tokens`), nor are widths
    whose x, y and tiles do not fit VMEM (`vmem`)."""
    bf16 = jnp.bfloat16
    for T, H_, n, reason in ((64, 7168, 12, None), (512, 7168, 12, None),
                             (128, 6144, 16, None), (512, 6144, 16, None),
                             (1024, 7168, 12, "tokens"),
                             (2048, 6144, 16, "tokens"),
                             (512, 16384, 8, "vmem")):
        monitor.reset(prefix="pallas.")
        x = jax.ShapeDtypeStruct((T, H_), bf16)
        gate = jax.ShapeDtypeStruct((n, H_, 2048), bf16)
        assert bool(experts._grouped_kernel_eligible(x, gate)) \
            == (reason is None)
        assert monitor.stats("pallas.gate_reject.grouped_expert_ffn.") == (
            {f"pallas.gate_reject.grouped_expert_ffn.{reason}": 1}
            if reason else {})
    assert G.grouped_ffn_cut(128, 12, 16, 6144, 2048, 128, 2) == {
        "rows_per_block": 128, "tile_bytes": 3 * 6144 * 256 * 2,
        "grid_steps": (12 + 16) * 8}
    assert G.grouped_ffn_cut(64, 8, 12, 7168, 2048, 64, 2) == {
        "rows_per_block": 64, "tile_bytes": 3 * 7168 * 256 * 2,
        "grid_steps": (8 + 12) * 8}


def test_the_plan_puts_every_held_pair_in_a_row_of_its_experts_blocks(
        interpret):
    """What a call hands the kernel: each held pair's row in the blocks'
    padded order (an expert's pairs in token order from its first
    block's first row, as the stable sort of the plain form has them),
    -1 for a pair nobody holds here, and a block -> expert map whose
    blocks past the last one in use repeat its expert, so that their
    index maps fetch nothing."""
    seen = {}
    real = G.grouped_ffn

    def spy(x, pair_row, w, blk_expert, n_live, *rest, rows):
        seen.update(pair_row=pair_row, blk_expert=blk_expert, n_live=n_live,
                    rows=rows)
        return real(x, pair_row, w, blk_expert, n_live, *rest, rows=rows)

    x, idx, w, valid, gate, up, down, first = _case("block_boundaries")
    G.grouped_ffn = spy
    try:
        with jax.disable_jit():
            experts._grouped_expert_ffn.__wrapped__(
                jnp.asarray(x), jnp.asarray(idx), jnp.asarray(w),
                jnp.asarray(valid), jnp.asarray(gate), jnp.asarray(up),
                jnp.asarray(down), first)
    finally:
        G.grouped_ffn = real
    rows, n_live = seen["rows"], int(seen["n_live"])
    pair_row = np.asarray(seen["pair_row"])
    be = np.asarray(seen["blk_expert"])
    # experts 1, 2, 2, 3: 128 pairs, 129 (two blocks), 1
    assert rows == 128 and n_live == 4
    assert be.shape == (256 * 2 // 128 + N,)
    assert list(be[:4]) == [1, 2, 2, 3] and (be[4:] == 3).all()
    assert (pair_row[idx >= N] == -1).all()
    held = pair_row[idx < N]
    assert len(set(held)) == 128 + 129 + 1 and held.min() == 0
    np.testing.assert_array_equal(pair_row[idx == 1], np.arange(128))
    np.testing.assert_array_equal(pair_row[idx == 2],
                                  128 + np.arange(129))
    assert pair_row[200, 0] == 3 * 128


def _tiny(model):
    from paddle_tpu.text.models import (KimiK2, KimiK2Config, LongCatFlash,
                                        LongCatFlashConfig)
    cls, cfg = {"KimiK2": (KimiK2, KimiK2Config),
                "LongCatFlash": (LongCatFlash, LongCatFlashConfig)}[model]
    paddle.seed(7)
    net = cls(cfg.tiny(experts_held=(4, 8), dtype="bfloat16",
                       hidden_size=128, moe_intermediate_size=128))
    net.eval()
    return net


def _expert_layers(net):
    return sum(isinstance(m, nn.RoutedExperts) for m in net.sublayers())


@pytest.mark.parametrize("model", ["KimiK2", "LongCatFlash"])
def test_served_logits_kernel_on_against_gated_off(model):
    net = _tiny(model)
    ids = np.random.RandomState(3).randint(1, 256, 21 + 4)
    off = forced_logits(net, ids, prompt_len=21, bucket=32)
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    monitor.reset(prefix="pallas.")
    try:
        on = forced_logits(net, ids, prompt_len=21, bucket=32)
    finally:
        paddle.set_flags({"FLAGS_pallas_interpret": False})
    # the prefill and the decode step are a trace each
    assert monitor.stat_get("pallas.hit.grouped_expert_ffn") \
        == 2 * _expert_layers(net)
    assert not monitor.stats("pallas.gate_reject.grouped_expert_ffn.")
    # bf16 through the layers, the pairs of a token summed in another
    # order: the served logits are a rounding apart, not a layer
    assert rel_err(on, off) <= 2e-2
