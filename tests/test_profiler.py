"""Profiler subsystem (analog of reference platform/profiler.h +
fluid/profiler.py tests)."""
import os

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import profiler as prof


def test_record_event_and_summary():
    prof.reset_profiler()
    prof.start_profiler()
    try:
        with prof.RecordEvent("phase_a"):
            _ = sum(range(1000))
        with prof.RecordEvent("phase_a"):
            pass
        with prof.RecordEvent("phase_b"):
            pass
    finally:
        prof.stop_profiler()
    evs = prof.events()
    assert len(evs) == 3
    table = prof.summary(sorted_key="calls")
    assert "phase_a" in table and "phase_b" in table
    # disabled: RecordEvent must be a no-op
    with prof.RecordEvent("after_stop"):
        pass
    assert len(prof.events()) == 3


def test_profiler_context_captures_op_events(capsys):
    x = paddle.to_tensor(np.ones((8, 8), "float32"))
    with prof.profiler(sorted_key="total"):
        y = x @ x
        _ = y.sum()
    out = capsys.readouterr().out
    assert "op/" in out  # per-op host annotations made it into the table


def test_chrome_trace_export(tmp_path):
    prof.reset_profiler()
    prof.start_profiler()
    with prof.RecordEvent("traced"):
        pass
    prof.stop_profiler()
    path = os.path.join(tmp_path, "trace.json")
    prof.export_chrome_trace(path)
    import json
    with open(path) as f:
        data = json.load(f)
    assert data["traceEvents"] and data["traceEvents"][0]["name"] == "traced"


def test_cost_analysis_reports_flops():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(a, b):
        return a @ b

    a = jnp.ones((128, 128), jnp.float32)
    ca = prof.cost_analysis(f, a, a)
    # 2*M*N*K flops for a 128^3 matmul
    assert float(ca.get("flops", 0)) >= 2 * 128 ** 3 * 0.9


def test_profiler_callback_in_fit(capsys):
    from paddle_tpu import nn, optimizer
    from paddle_tpu.hapi.callbacks import ProfilerCallback
    from paddle_tpu.io import TensorDataset

    paddle.seed(0)
    X = np.random.rand(32, 4).astype("float32")
    Y = (X @ np.random.rand(4, 1).astype("float32"))
    net = nn.Linear(4, 1)
    model = paddle.Model(net)
    model.prepare(optimizer=optimizer.SGD(learning_rate=0.1,
                                          parameters=net.parameters()),
                  loss=nn.MSELoss())
    cb = ProfilerCallback(start_step=1, stop_step=2)
    model.fit(TensorDataset([X, Y]), batch_size=16, epochs=1, verbose=0,
              callbacks=[cb])
    out = capsys.readouterr().out
    # fit's phases are trace spans, mirrored into the profiler's table
    assert "fit/dispatch" in out and "fit/step" in out
