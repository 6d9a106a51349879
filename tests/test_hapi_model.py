"""End-to-end Model.fit tests — the 'book tests' analog
(reference python/paddle/fluid/tests/book/test_recognize_digits.py:
small model trained a few iterations, loss must drop, save/load roundtrip).
"""
import numpy as np
import pytest
from span_util import self_times

import paddle_tpu as paddle
from paddle_tpu import Model, nn, optimizer
from paddle_tpu.hapi.callbacks import EarlyStopping, History
from paddle_tpu.io import DataLoader, TensorDataset
from paddle_tpu.metric import Accuracy
from paddle_tpu.vision.datasets import MNIST
from paddle_tpu.vision.models import LeNet


def small_mnist(n=512, mode="train"):
    ds = MNIST(mode=mode)
    from paddle_tpu.io import Subset
    return Subset(ds, range(n))


def test_model_fit_mnist_lenet():
    paddle.seed(1)
    model = Model(LeNet())
    model.prepare(
        optimizer=optimizer.Adam(learning_rate=0.001,
                                 parameters=model.parameters()),
        loss=nn.CrossEntropyLoss(),
        metrics=Accuracy())
    hist = History()
    train = small_mnist(512)
    model.fit(train, batch_size=64, epochs=2, verbose=0, callbacks=[hist],
              shuffle=True, drop_last=True)
    losses = hist.history["loss"]
    assert losses[-1] < losses[0], f"loss did not drop: {losses}"
    logs = model.evaluate(small_mnist(256, "test"), batch_size=64, verbose=0)
    assert logs["acc"] > 0.3  # synthetic digits are very separable
    assert logs["loss"] < 2.5


def test_model_save_load_roundtrip(tmp_path):
    paddle.seed(2)
    model = Model(LeNet())
    model.prepare(optimizer=optimizer.Adam(parameters=model.parameters()),
                  loss=nn.CrossEntropyLoss(), metrics=Accuracy())
    train = small_mnist(128)
    model.fit(train, batch_size=64, epochs=1, verbose=0)
    path = str(tmp_path / "ckpt" / "model")
    model.save(path)

    model2 = Model(LeNet())
    model2.prepare(optimizer=optimizer.Adam(parameters=model2.parameters()),
                   loss=nn.CrossEntropyLoss(), metrics=Accuracy())
    model2.load(path)
    x = paddle.randn([4, 1, 28, 28])
    np.testing.assert_allclose(model.predict_batch([x])[0],
                               model2.predict_batch([x])[0], rtol=1e-5,
                               atol=1e-6)
    assert model2._optimizer._step_count == model._optimizer._step_count


def test_model_predict_stack():
    model = Model(LeNet())
    model.prepare(loss=None)
    ds = small_mnist(32, "test")
    outs = model.predict(ds, batch_size=16, stack_outputs=True)
    assert outs[0].shape == (32, 10)


def test_early_stopping_stops():
    paddle.seed(3)
    model = Model(nn.Sequential(nn.Flatten(), nn.Linear(784, 10)))
    model.prepare(optimizer=optimizer.SGD(learning_rate=0.0,
                                          parameters=model.parameters()),
                  loss=nn.CrossEntropyLoss())
    es = EarlyStopping(monitor="loss", patience=1, verbose=0)
    model.fit(small_mnist(64), batch_size=32, epochs=10, verbose=0,
              callbacks=[es])
    assert model.stop_training  # lr=0 -> no improvement -> stops early


def test_dataloader_shapes_and_order():
    X = np.arange(20, dtype="float32").reshape(10, 2)
    y = np.arange(10, dtype="int64")
    ds = TensorDataset([X, y])
    dl = DataLoader(ds, batch_size=4, shuffle=False)
    batches = list(dl)
    assert len(batches) == 3
    xb, yb = batches[0]
    assert xb.shape == (4, 2)
    np.testing.assert_array_equal(yb, [0, 1, 2, 3])
    dl = DataLoader(ds, batch_size=4, drop_last=True)
    assert len(list(dl)) == 2


def test_dataloader_num_workers():
    X = np.random.rand(64, 3).astype("float32")
    ds = TensorDataset([X])
    dl = DataLoader(ds, batch_size=8, num_workers=2, shuffle=False)
    got = np.concatenate([b[0] for b in dl])
    np.testing.assert_allclose(got, X)


def test_metrics_accuracy():
    from paddle_tpu.metric import Accuracy
    m = Accuracy(topk=(1, 2))
    pred = paddle.to_tensor(np.array([[0.9, 0.05, 0.05],
                                      [0.1, 0.8, 0.1],
                                      [0.3, 0.4, 0.3]], dtype="float32"))
    label = paddle.to_tensor(np.array([[0], [0], [2]]))
    correct = m.compute(pred, label)
    m.update(correct)
    top1, top2 = m.accumulate()
    assert abs(top1 - 1 / 3) < 1e-6
    assert abs(top2 - 2 / 3) < 1e-6


def test_model_summary(capsys):
    model = Model(LeNet())
    info = model.summary()
    assert info["total_params"] == 61610


def test_summary_and_flops():
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    info = paddle.summary(net, (1, 8))
    assert info["total_params"] == 8 * 16 + 16 + 16 * 4 + 4
    assert info["trainable_params"] == info["total_params"]
    f = paddle.flops(net, (1, 8))
    # two matmuls dominate: 2*(8*16) + 2*(16*4) flops per sample
    assert f >= 2 * 8 * 16 + 2 * 16 * 4
    assert f < 10000


def test_flops_leaves_net_usable_and_modes_intact():
    """Regression: flops() traces through the layer — afterwards the real
    params must be reseated (no leaked tracers) and per-sublayer
    train/eval flags preserved."""
    paddle.seed(1)
    net = nn.Sequential(nn.Linear(4, 8), nn.BatchNorm1D(8), nn.Linear(8, 2))
    net.train()
    net[1].eval()  # deliberately frozen BN
    paddle.flops(net, (2, 4))
    assert net.training and not net[1].training  # modes preserved
    out = net(paddle.to_tensor(np.ones((2, 4), "float32")))  # no tracers
    assert np.isfinite(np.asarray(out._value)).all()
    # multi-input and InputSpec forms
    from paddle_tpu.hapi.model import InputSpec
    info = paddle.summary(net, InputSpec([None, 4], "float32"))
    assert info["total_params"] > 0
    m = Model(net)
    info2 = m.summary((2, 4))
    assert info2["total_params"] == info["total_params"]


def test_model_engine_mode_independent():
    """The one-engine design delta (reference dual adapters): Model works
    identically with enable_static() flipped on around the training loop
    (fit/evaluate included — the guard lives in the engine), records NO
    ops into the default Program, and a net BUILT under static mode gets
    a clear error."""
    import paddle_tpu as paddle
    from paddle_tpu import io, nn, optimizer, static
    from paddle_tpu.distributed import mesh as mesh_mod

    prev_mesh = mesh_mod.get_mesh()
    mesh_mod.reset_mesh()  # isolate from suites that leave a dp mesh
    net = nn.Linear(4, 2)
    m = paddle.Model(net)
    m.prepare(optimizer.SGD(learning_rate=0.1,
                            parameters=net.parameters()),
              nn.CrossEntropyLoss())
    x = np.random.RandomState(0).rand(8, 4).astype("float32")
    y = np.random.RandomState(1).randint(0, 2, (8,)).astype("int64")
    base = m.train_batch([x], [y])
    assert np.isfinite(base[0])
    paddle.enable_static()
    try:
        n_ops_before = len(static.default_main_program().ops)
        again = m.train_batch([x], [y])
        assert np.isfinite(again[0])

        class _DS(io.Dataset):
            def __getitem__(self, i):
                return x[i % 8], y[i % 8]

            def __len__(self):
                return 8

        m.fit(_DS(), batch_size=4, epochs=1, verbose=0)   # engine path
        m.evaluate(_DS(), batch_size=4, verbose=0)
        # the engine must not have appended ops to the static Program
        assert len(static.default_main_program().ops) == n_ops_before
        with pytest.raises(TypeError, match="enable_static"):
            paddle.Model(nn.Linear(4, 2))
    finally:
        paddle.disable_static()
        if prev_mesh is not None:
            mesh_mod.set_mesh(prev_mesh)


# --------------------------------------------------------------------------
# fit's host phases as spans (core/trace.py): fit/step and its children
# --------------------------------------------------------------------------

def _traced_fit(n_batches=3, **fit_kw):
    from paddle_tpu.core import trace
    paddle.seed(0)
    rng = np.random.RandomState(0)
    X = rng.rand(16 * n_batches, 4).astype("float32")
    Y = X @ rng.rand(4, 1).astype("float32")
    net = nn.Linear(4, 1)
    model = Model(net)
    model.prepare(optimizer=optimizer.SGD(learning_rate=0.1,
                                          parameters=net.parameters()),
                  loss=nn.MSELoss())
    trace.reset()
    model.fit(TensorDataset([X, Y]), batch_size=16, epochs=1, verbose=0,
              shuffle=False, **fit_kw)
    return trace.recent()


def test_fit_three_steps_leave_step_spans_with_their_phases():
    spans = _traced_fit(3)
    steps = [sp for sp in spans if sp.name == "fit/step"]
    assert [sp.attrs["step"] for sp in steps] == [0, 1, 2]
    selfs = self_times(spans)
    for step in steps:
        kids = [sp.name for sp in spans if sp.parent_id == step.span_id]
        assert kids[:3] == ["fit/next_batch", "fit/callbacks",
                            "fit/dispatch"]
        assert kids[-1] == "fit/callbacks" and kids.count(
            "fit/callbacks") == 2
        assert set(kids[3:-1]) <= {"fit/drain"}
        inside = [sp for sp in spans
                  if step.t0 <= sp.t0 and sp.t1 <= step.t1]
        assert sum(selfs[sp.span_id] for sp in inside) == pytest.approx(
            step.t1 - step.t0, rel=0.01)
    # the loader's own spans sit under the pull, the (one) build of the
    # train step under the first dispatch
    by_id = {sp.span_id: sp for sp in spans}
    produce = [sp for sp in spans if sp.name == "io/produce_batch"]
    assert produce and all(
        by_id[sp.parent_id].name == "fit/next_batch" for sp in produce)
    (build,) = [sp for sp in spans if sp.name == "hapi/build_train_fn"]
    first_dispatch = next(sp for sp in spans if sp.name == "fit/dispatch")
    assert first_dispatch.t0 <= build.t0 and build.t1 <= first_dispatch.t1
    # every step's loss is drained exactly once, in order; with the
    # default in-flight window of two the tail drains at the epoch's end
    drains = [sp for sp in spans if sp.name == "fit/drain"]
    assert [sp.attrs["step"] for sp in drains] == [0, 1, 2]
    assert drains[-1].parent_id is None
    # the iteration that found the loader empty is not a step
    assert len([sp for sp in spans if sp.name == "fit/next_batch"]) == 4


def test_fit_synchronous_loop_drains_inside_every_step():
    """Gradient accumulation turns the in-flight window off: the loop
    reads each loss at once, and that blocking read is the step's own
    fit/drain."""
    spans = _traced_fit(2, accumulate_grad_batches=2)
    steps = [sp for sp in spans if sp.name == "fit/step"]
    assert len(steps) == 2
    for step in steps:
        kids = [sp for sp in spans if sp.parent_id == step.span_id]
        drains = [sp for sp in kids if sp.name == "fit/drain"]
        assert len(drains) == 1
        assert drains[0].attrs["step"] == step.attrs["step"]


def test_fit_step_span_records_a_callbacks_error_and_closes():
    from paddle_tpu.core import trace
    from paddle_tpu.hapi.callbacks import Callback

    class Boom(Callback):
        def on_train_batch_end(self, step, logs=None):
            if step == 1:
                raise KeyError("boom")

    with pytest.raises(KeyError):
        _traced_fit(3, callbacks=[Boom()])
    assert trace.open_spans() == [] and trace.current() is None
    steps = [sp for sp in trace.recent() if sp.name == "fit/step"]
    assert [sp.attrs.get("error") for sp in steps] == [None, "KeyError"]
