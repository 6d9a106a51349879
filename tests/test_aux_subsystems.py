"""Aux subsystems: enforce errors (N25), Program passes + DOT dumps (N10),
LogWriter/VisualDL (5.5), SIGTERM preemption guard (5.3)."""
import os
import signal
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, ops, optimizer
from paddle_tpu.core import enforce


def test_enforce_taxonomy():
    with pytest.raises(enforce.InvalidArgumentError):
        enforce.enforce(False, "nope")
    with pytest.raises(ValueError):  # typed errors are also builtins
        enforce.enforce_eq(1, 2)
    with pytest.raises(enforce.EnforceNotMet):
        enforce.check_type(3, "x", str)
    enforce.check_shape([2, -1, 3])
    with pytest.raises(enforce.InvalidArgumentError):
        enforce.check_shape([0, 2])
    enforce.enforce_ge(2, 2)


def test_program_passes_and_dot(tmp_path):
    import paddle_tpu.static as static
    from paddle_tpu.static.passes import apply_pass, graph_viz
    paddle.enable_static()
    try:
        main = static.Program()
        with static.program_guard(main):
            x = static.data("x", [4], "float32")
            y = ops.sum(x * 2.0)
            _dead = ops.exp(x) + 5.0  # feeds nothing
            main._jit_fetch_vars = [y]
        n_before = len(main.ops)
        pruned = apply_pass(main, "eliminate_dead_ops")
        assert len(pruned.ops) < n_before
        exe = static.Executor()
        out = exe.run(pruned, feed={"x": np.ones(4, "float32")},
                      fetch_list=[y])[0]
        assert float(out) == 8.0

        dot = graph_viz(main, path=os.path.join(tmp_path, "g.dot"))
        assert dot.startswith("digraph") and "sum" in dot
        assert os.path.exists(os.path.join(tmp_path, "g.dot"))
    finally:
        paddle.disable_static()


def test_log_writer_and_visualdl_callback(tmp_path):
    from paddle_tpu.hapi.callbacks import VisualDL
    from paddle_tpu.io import TensorDataset
    from paddle_tpu.utils import read_scalars

    paddle.seed(0)
    X = np.random.rand(32, 4).astype("float32")
    Y = X @ np.random.rand(4, 1).astype("float32")
    net = nn.Linear(4, 1)
    model = paddle.Model(net)
    model.prepare(optimizer=optimizer.SGD(learning_rate=0.1,
                                          parameters=net.parameters()),
                  loss=nn.MSELoss())
    logdir = os.path.join(tmp_path, "vdl")
    model.fit(TensorDataset([X, Y]), batch_size=8, epochs=2, verbose=0,
              callbacks=[VisualDL(logdir)])
    recs = read_scalars(logdir, tag="train/loss")
    assert len(recs) == 8
    assert recs[-1]["value"] < recs[0]["value"]
    assert read_scalars(logdir, tag="epoch/loss")


CHILD = textwrap.dedent("""
    import os, signal, threading, time
    import jax; jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.io import TensorDataset
    from paddle_tpu.hapi.callbacks import Callback

    class SigtermAt(Callback):
        def __init__(self): self.n = 0
        def on_train_batch_end(self, step, logs=None):
            self.n += 1
            if self.n == 3:   # mid-epoch, NOT on a save interval
                os.kill(os.getpid(), signal.SIGTERM)

    paddle.seed(5)
    X = np.random.rand(32, 4).astype("float32")
    Y = (X @ np.random.rand(4, 1).astype("float32"))
    net = nn.Linear(4, 1)
    model = paddle.Model(net)
    model.prepare(optimizer=optimizer.SGD(learning_rate=0.1,
                                          parameters=net.parameters()),
                  loss=nn.MSELoss())
    model.fit(TensorDataset([X, Y]), batch_size=8, epochs=4, verbose=0,
              shuffle=False, callbacks=[SigtermAt()],
              auto_checkpoint_dir={ckpt_dir!r},
              auto_checkpoint_freq=100)   # periodic saves never fire
""")


def test_sigterm_grace_checkpoint(tmp_path):
    """SIGTERM mid-epoch forces one synchronous checkpoint at the exact
    step, even though the periodic interval never fired."""
    ckpt_dir = os.path.join(str(tmp_path), "ckpt")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", CHILD.format(ckpt_dir=ckpt_dir)],
        env=env, cwd="/root/repo", capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == -signal.SIGTERM, (proc.returncode,
                                                proc.stderr[-2000:])
    from paddle_tpu.incubate.checkpoint import TrainingCheckpoint
    ck = TrainingCheckpoint(ckpt_dir)
    assert ck.latest_step() == 3
    st = ck.restore()
    assert st["counters"]["global_step"] == 3


# ------------------------- elastic / heartbeats ----------------------------

def test_heartbeat_update_and_check(tmp_path):
    from paddle_tpu.distributed.elastic import Heartbeat
    hb = Heartbeat(str(tmp_path), rank=0, interval_s=0.05).start()
    hb.update(step=7)
    import json
    with open(hb.path) as f:
        rec = json.load(f)
    assert rec["rank"] == 0 and rec["step"] == 7
    assert Heartbeat.check(str(tmp_path), timeout_s=60) == []
    hb.stop()
    import time
    time.sleep(0.15)
    assert Heartbeat.check(str(tmp_path), timeout_s=0.05) == [0]


def test_stall_monitor_fires():
    import time
    from paddle_tpu.distributed.elastic import StallMonitor
    fired = []
    with StallMonitor(timeout_s=0.2, on_stall=fired.append) as m:
        m.step_done()
        time.sleep(0.5)
    assert fired and fired[0] >= 0.2
    assert m.stalled


def test_launch_elastic_restart(tmp_path):
    """A trainer that crashes on its first attempt and succeeds after a
    restart (state via a marker file, standing in for auto-checkpoint
    resume)."""
    import textwrap
    from paddle_tpu.distributed.launch import launch
    script = os.path.join(str(tmp_path), "train.py")
    marker = os.path.join(str(tmp_path), "attempted")
    with open(script, "w") as f:
        f.write(textwrap.dedent(f"""
            import os, sys
            if not os.path.exists({marker!r}):
                open({marker!r}, "w").close()
                sys.exit(1)       # first attempt: crash
            sys.exit(0)           # resumed attempt: success
        """))
    assert launch(script, nproc_per_node=1, elastic_retries=2) == 0
    with pytest.raises(SystemExit):
        os.remove(marker)
        launch(script, nproc_per_node=1, elastic_retries=0)
