"""Where dropout's bits are drawn when a step is partitioned along `dp`
(ops/norm_ops.py `_keep_mask`): a `dp` shard draws the bits of its own
rows under a shard_map over `dp`; no mesh, eager, `dp` already manual and
a leading dimension `dp` does not divide keep the global draw. On the
8 host devices tests/conftest.py sets."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core import monitor, rng as _rng
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.ops import norm_ops

COUNTERS = ("dropout.local_draw", "dropout.local_draw_fallback.manual",
            "dropout.local_draw_fallback.indivisible")


@pytest.fixture
def mesh_of():
    """Declare a mesh for the test; put back the one it found."""
    prev = mesh_mod.get_mesh()

    def declare(shape):
        mesh_mod.reset_mesh()
        return mesh_mod.init_mesh(shape) if shape else None

    yield declare
    mesh_mod.reset_mesh()
    if prev is not None:
        mesh_mod.set_mesh(prev)


def _counts():
    return {k: monitor.stat_get(k) for k in COUNTERS}


def _moved(before):
    return {k: v - before[k] for k, v in _counts().items() if v != before[k]}


def _dropout_fn(p=0.1):
    """A fresh function object a call: jax keeps a trace by function, and
    the draw reads the mesh at trace time."""
    def f(key, x):
        with _rng.rng_state(key):
            return norm_ops._dropout.raw(x, p, "upscale_in_train")
    return f


def _random_words(hlo):
    """Largest u32 array among a compiled module's generator instructions
    (the CPU expands RngBitGenerator into Philox over u32[n / 4, 4]; the
    TPU keeps the one instruction): the words of the largest draw."""
    sizes = [int(np.prod([int(d) for d in dims.split(",")]))
             for line in hlo.splitlines()
             if "rng_bit_generator" in line or "rng-bit-generator" in line
             for dims in re.findall(r"u32\[([\d,]+)\]", line)]
    return max(sizes)


B, S, H, HEADS = 16, 8, 32, 4
BERT_DRAWS = {"hidden": (B, S, H), "ffn": (B, S, 4 * H),
              "attention_out": (B, HEADS, S, H // HEADS)}


@pytest.mark.parametrize("mesh_shape", [{"dp": 4}, {"dp": 2, "tp": 2}],
                         ids=["dp4", "dp2_tp2"])
@pytest.mark.parametrize("draw", sorted(BERT_DRAWS))
def test_batch_sharded_step_draws_local_words(mesh_of, mesh_shape, draw):
    mesh = mesh_of(mesh_shape)
    shape = BERT_DRAWS[draw]
    before = _counts()
    step = jax.jit(_dropout_fn(),
                   in_shardings=(NamedSharding(mesh, P()),
                                 NamedSharding(mesh, P("dp"))))
    hlo = step.lower(jax.random.PRNGKey(0),
                     jnp.ones(shape, jnp.float32)).compile().as_text()
    n = int(np.prod(shape))
    assert _random_words(hlo) == n // mesh_shape["dp"]
    assert _moved(before) == {"dropout.local_draw": 1}


def test_no_mesh_lowers_to_the_global_draw(mesh_of, monkeypatch):
    mesh_of(None)
    key, x = jax.random.PRNGKey(3), jnp.ones((B, S, H), jnp.float32)
    before = _counts()
    now = jax.jit(_dropout_fn()).lower(key, x).as_text()
    mask = jax.jit(lambda k: norm_ops._keep_mask(k, 0.9, x.shape))(key)
    eager = norm_ops._keep_mask(key, 0.9, x.shape)
    monkeypatch.setattr(norm_ops, "_keep_mask", norm_ops._keep_mask_global)
    assert jax.jit(_dropout_fn()).lower(key, x).as_text() == now
    want = norm_ops._keep_mask_global(key, 0.9, x.shape)
    assert np.array_equal(mask, want) and np.array_equal(eager, want)
    assert _moved(before) == {}


def test_eager_draw_under_a_mesh_stays_global(mesh_of):
    mesh_of({"dp": 4})
    key = jax.random.PRNGKey(3)
    before = _counts()
    assert np.array_equal(norm_ops._keep_mask(key, 0.9, (B, S, H)),
                          norm_ops._keep_mask_global(key, 0.9, (B, S, H)))
    assert _moved(before) == {}


@pytest.mark.parametrize("keep", [0.9, 0.5])
def test_shards_differ_and_keep_share(mesh_of, keep):
    mesh_of({"dp": 4})
    shape = (64, 128, 512)            # 4.2 M elements
    mask = np.asarray(jax.jit(
        lambda k: norm_ops._keep_mask(k, keep, shape))(jax.random.PRNGKey(7)))
    assert abs(mask.mean() - keep) < 0.002
    shards = np.split(mask, 4)
    for i in range(4):
        assert abs(shards[i].mean() - keep) < 0.004
        for j in range(i):
            assert not np.array_equal(shards[i], shards[j])
    # a shard's rows are the global drawer's on the folded key
    want = norm_ops._keep_mask_global(
        jax.random.fold_in(jax.random.PRNGKey(7), 2), keep, (16, 128, 512))
    assert np.array_equal(shards[2], want)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "recompute"])
def test_grad_is_the_forward_mask(mesh_of, remat):
    """Also through jax.checkpoint (distributed/recompute.py): the fold is
    a pure function of the key and the axis index, so the replay in the
    backward pass draws the mask the forward pass drew."""
    mesh = mesh_of({"dp": 4})
    f = _dropout_fn(0.25)
    if remat:
        f = jax.checkpoint(f)
    x = jnp.asarray(np.random.RandomState(0).randn(B, S, H), jnp.float32)
    g = jnp.asarray(np.random.RandomState(1).randn(B, S, H), jnp.float32)
    shard = NamedSharding(mesh, P("dp"))

    def loss(key, x):
        y = f(key, x)
        return jnp.sum(y * g), y

    (_, y), dx = jax.jit(jax.value_and_grad(loss, argnums=1, has_aux=True),
                         in_shardings=(NamedSharding(mesh, P()), shard))(
        jax.random.PRNGKey(5), x)
    mask = np.asarray(y) != 0
    assert 0.6 < mask.mean() < 0.9
    np.testing.assert_allclose(np.asarray(dx), mask * np.asarray(g) / 0.75,
                               rtol=1e-6)


def test_nested_under_another_manual_axis(mesh_of):
    """`pp` manual, `dp` left to GSPMD (a pipeline stage's body): the draw
    nests its shard_map in the enclosing one."""
    mesh = mesh_of({"pp": 2, "dp": 2})
    f = _dropout_fn()

    def stages(key, x):
        return mesh_mod.shard_map(f, mesh=mesh, in_specs=(P(), P("pp")),
                                  out_specs=P("pp"), axis_names={"pp"})(
            key, x)

    before = _counts()
    x = jnp.ones((B, S, H), jnp.float32)
    hlo = jax.jit(stages).lower(jax.random.PRNGKey(0), x).compile().as_text()
    assert _random_words(hlo) == x.size // 4
    assert _moved(before) == {"dropout.local_draw": 1}


def _localsgd_model():
    """tests/test_round4_fixes.py's LocalSGD model with a dropout in it."""
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(4, 4), nn.Dropout(0.5), nn.Linear(4, 4))
    model = paddle.Model(net)
    strat = fleet.DistributedStrategy()
    strat.localsgd = True
    strat.localsgd_configs = {"k_steps": 2}
    opt = fleet.distributed_optimizer(
        paddle.optimizer.SGD(learning_rate=0.1, parameters=net.parameters()),
        strat)
    model.prepare(optimizer=opt, loss=nn.MSELoss())
    return model


def test_localsgd_step_keeps_the_global_draw(mesh_of):
    mesh_of({"dp": 4})
    model = _localsgd_model()
    r = np.random.RandomState(0)
    x, y = (r.randn(16, 4).astype("float32") for _ in range(2))
    before = _counts()
    loss = model.train_batch([x], [y])
    assert model._engine._localsgd is not None
    assert np.isfinite(loss[0])
    moved = _moved(before)
    assert set(moved) == {"dropout.local_draw_fallback.manual"}, moved


def test_indivisible_rows_keep_the_global_draw(mesh_of):
    mesh_of({"dp": 4})
    before = _counts()
    key, shape = jax.random.PRNGKey(1), (6, S, H)
    mask = jax.jit(lambda k: norm_ops._keep_mask(k, 0.9, shape))(key)
    assert np.array_equal(mask, norm_ops._keep_mask_global(key, 0.9, shape))
    assert _moved(before) == {"dropout.local_draw_fallback.indivisible": 1}


def _bert_fit(steps=2):
    from paddle_tpu.hapi.callbacks import Callback
    from paddle_tpu.text.datasets import LMDataset
    from paddle_tpu.text.models.bert import (Bert, BertConfig,
                                             BertPretrainingCriterion)

    cfg = BertConfig.tiny()           # 2 layers, dropout 0.1 / 0.1
    assert cfg.num_hidden_layers == 2 and cfg.hidden_dropout_prob == 0.1
    paddle.seed(11)
    net = Bert(cfg)
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.AdamW(learning_rate=1e-2,
                                         parameters=net.parameters()),
                  BertPretrainingCriterion(cfg.vocab_size))
    losses = []

    class Rec(Callback):
        def on_train_batch_end(self, step, logs=None):
            losses.append(float(logs["loss"]))

    # one batch, seen once an epoch: the second step's loss has to fall
    data = LMDataset(vocab_size=cfg.vocab_size, seq_len=16, n=8, mode="mlm",
                     seed=3)
    model.fit(data, batch_size=8, epochs=steps, shuffle=False,
              drop_last=True, num_workers=0, verbose=0, callbacks=[Rec()])
    return model, losses


def test_bert_fit_on_dp4_with_dropout(mesh_of):
    mesh_of({"dp": 4})
    before = _counts()
    model, losses = _bert_fit()
    assert model._engine._train_fn._cache_size() == 1
    assert len(losses) == 2 and np.isfinite(losses).all()
    assert losses[1] < losses[0]
    moved = _moved(before)
    # embeddings + 4 a layer, once a compiled step
    assert moved == {"dropout.local_draw": 1 + 4 * 2}, moved
    _, again = _bert_fit()
    assert again == losses


def test_one_device_fit_counts_nothing(mesh_of):
    mesh_of(None)
    before = _counts()
    _, losses = _bert_fit()
    assert np.isfinite(losses).all()
    assert _moved(before) == {}
