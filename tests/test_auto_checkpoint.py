"""Preemption-safe checkpointing (VERDICT r02 item 7; reference
fluid/incubate/checkpoint/auto_checkpoint.py:71).

The contract under test: SIGKILL mid-training, resume from the latest
committed checkpoint, and the continued loss trajectory is bit-identical
to an uninterrupted run — params, optimizer slots, LR state, rng chain and
data position all restored.
"""
import os
import signal
import subprocess
import sys
import textwrap

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.hapi.callbacks import Callback

STEPS_PER_EPOCH = 4
EPOCHS = 3


class LossTrace(Callback):
    def __init__(self):
        self.losses = []

    def on_train_batch_end(self, step, logs=None):
        self.losses.append(float(logs["loss"]))


def _build():
    paddle.seed(123)
    np.random.seed(123)
    X = np.random.rand(32, 8).astype("float32")
    Y = (X @ np.random.rand(8, 1).astype("float32"))
    net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 1))
    model = paddle.Model(net)
    model.prepare(optimizer=optimizer.Adam(learning_rate=0.05,
                                           parameters=net.parameters()),
                  loss=nn.MSELoss())
    from paddle_tpu.io import TensorDataset
    return model, TensorDataset([X, Y])


def _fit(model, ds, ckpt_dir, callbacks, epochs=EPOCHS):
    model.fit(ds, batch_size=8, epochs=epochs, verbose=0, shuffle=False,
              callbacks=callbacks, auto_checkpoint_dir=ckpt_dir,
              auto_checkpoint_freq=2, keep_checkpoint_max=2)


CHILD = textwrap.dedent("""
    import os, signal
    import jax; jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import test_auto_checkpoint as T
    import paddle_tpu as paddle

    class Killer(T.LossTrace):
        def on_train_batch_end(self, step, logs=None):
            super().on_train_batch_end(step, logs)
            if len(self.losses) == 6:      # mid-epoch-2 (global step 6)
                os.kill(os.getpid(), signal.SIGKILL)

    model, ds = T._build()
    T._fit(model, ds, {ckpt_dir!r}, [Killer()])
    raise SystemExit("unreachable: child must have been SIGKILLed")
""")


def test_kill_and_resume_bit_identical(tmp_path):
    ckpt_dir = os.path.join(str(tmp_path), "ckpt")

    # uninterrupted reference trajectory (no checkpointing side effects)
    model, ds = _build()
    ref = LossTrace()
    model.fit(ds, batch_size=8, epochs=EPOCHS, verbose=0, shuffle=False,
              callbacks=[ref])
    assert len(ref.losses) == STEPS_PER_EPOCH * EPOCHS

    # child trains with auto-checkpoint and SIGKILLs itself mid-epoch 2
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH="/root/repo/tests:/root/repo")
    proc = subprocess.run(
        [sys.executable, "-c", CHILD.format(ckpt_dir=ckpt_dir)],
        env=env, cwd="/root/repo", capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == -signal.SIGKILL, (proc.returncode,
                                                proc.stderr[-2000:])

    # a committed checkpoint exists despite the hard kill
    from paddle_tpu.incubate.checkpoint import TrainingCheckpoint
    latest = TrainingCheckpoint(ckpt_dir).latest_step()
    assert latest is not None and 1 <= latest <= 6

    # resume: must continue the reference trajectory exactly
    model2, ds2 = _build()
    tr = LossTrace()
    _fit(model2, ds2, ckpt_dir, [tr])
    want = ref.losses[latest:]
    assert len(tr.losses) == len(want), (latest, len(tr.losses), len(want))
    np.testing.assert_allclose(tr.losses, want, rtol=1e-6)


def test_training_checkpoint_roundtrip(tmp_path):
    from paddle_tpu.incubate.checkpoint import TrainingCheckpoint
    ck = TrainingCheckpoint(os.path.join(str(tmp_path), "c"), keep=2,
                            async_save=False)
    for s in (1, 2, 3):
        ck.save(s, {"w": np.full((4,), s, "float32"), "step": s})
    ck.wait()
    assert ck.latest_step() == 3
    st = ck.restore()
    assert int(st["step"]) == 3
    np.testing.assert_array_equal(st["w"], np.full((4,), 3, "float32"))
    assert ck.restore(1) is None  # GC'd by keep-latest-k


def test_train_epoch_range_resumes(tmp_path):
    from paddle_tpu.incubate.checkpoint import train_epoch_range
    d = os.path.join(str(tmp_path), "er")
    seen = []
    for e in train_epoch_range(5, directory=d):
        seen.append(e)
        if e == 2:
            break  # crash DURING epoch 2: it never commits, so it re-runs
    seen2 = list(train_epoch_range(5, directory=d))
    assert seen == [0, 1, 2] and seen2 == [2, 3, 4]
