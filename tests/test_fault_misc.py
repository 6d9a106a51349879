"""Fault-tolerance satellites around the PS chaos suite (ISSUE 2):
supervisor-side heartbeat robustness, checkpoint-manager lifecycle, and
the SIGTERM PreemptionGuard grace-save contract."""
import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------- heartbeat

def _write(path, payload):
    with open(path, "w") as f:
        f.write(payload)


def test_heartbeat_check_tolerates_corruption(tmp_path):
    """The supervisor must outlive everything else: corrupt, partial,
    schema-less, or mid-delete beat files mark that rank STALE instead of
    raising out of the watch loop."""
    from paddle_tpu.distributed.elastic import Heartbeat
    d = str(tmp_path)
    now = time.time()
    _write(os.path.join(d, "heartbeat_0.json"),
           json.dumps({"rank": 0, "step": 5, "time": now}))        # fresh
    _write(os.path.join(d, "heartbeat_1.json"),
           json.dumps({"rank": 1, "step": 5, "time": now - 999}))  # stale
    _write(os.path.join(d, "heartbeat_2.json"), "{corrupt json!!")  # bad
    _write(os.path.join(d, "heartbeat_3.json.tmp"), "{partial")    # tmp
    _write(os.path.join(d, "heartbeat_4.json"),
           json.dumps({"rank": 4, "step": 5}))               # no "time"
    _write(os.path.join(d, "heartbeat_5.json"),
           json.dumps({"rank": 5, "time": "not-a-number"}))  # bad type
    stale = Heartbeat.check(d, timeout_s=60.0)
    # 0 alive; 3 is an uncommitted atomic-write twin, not a rank
    assert stale == [1, 2, 4, 5]


def test_heartbeat_check_survives_missing_directory(tmp_path):
    from paddle_tpu.distributed.elastic import Heartbeat
    assert Heartbeat.check(str(tmp_path / "never_made")) == []


def test_heartbeat_update_then_check_roundtrip(tmp_path):
    from paddle_tpu.distributed.elastic import Heartbeat
    hb = Heartbeat(str(tmp_path), rank=7, interval_s=60.0)
    hb.update(step=3)
    assert Heartbeat.check(str(tmp_path), timeout_s=60.0) == []


# ------------------------------------------- checkpoint manager leak

def test_train_epoch_range_closes_manager(tmp_path, monkeypatch):
    from paddle_tpu.incubate import checkpoint as ck
    closed = []
    orig_close = ck.TrainingCheckpoint.close
    monkeypatch.setattr(
        ck.TrainingCheckpoint, "close",
        lambda self: (closed.append(1), orig_close(self))[1])

    d1 = str(tmp_path / "full")
    assert list(ck.train_epoch_range(2, directory=d1)) == [0, 1]
    assert len(closed) == 1, "exhausted generator must close its manager"

    # abandoned mid-loop (break → GeneratorExit) closes too
    gen = ck.train_epoch_range(5, directory=str(tmp_path / "part"))
    next(gen)
    gen.close()
    assert len(closed) == 2, "abandoned generator must close its manager"


# -------------------------------------------------- preemption guard

GUARD_CHILD = textwrap.dedent("""
    import os, sys, time
    import jax; jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from paddle_tpu.incubate.checkpoint import (TrainingCheckpoint,
                                                PreemptionGuard)
    d = sys.argv[1]
    # save_interval huge: the ONLY way a checkpoint lands is the guard's
    # grace save at SIGTERM time
    ck = TrainingCheckpoint(d, keep=2, save_interval_steps=10**9,
                            async_save=False)
    state = {"step": 0}

    def capture():
        s = state["step"]
        return s, {"w": np.full((4,), s, np.float32),
                   "counters": {"epoch": 0, "step": s, "global_step": s}}

    with PreemptionGuard(ck, capture):
        print("ready", flush=True)
        for step in range(1, 10 ** 6):
            state["step"] = step
            time.sleep(0.02)
    raise SystemExit("unreachable: child must die by SIGTERM")
""")


def test_preemption_guard_grace_checkpoint(tmp_path):
    """SIGTERM a training loop: the grace checkpoint lands, the process
    dies BY SIGTERM as its wait status (so launchers see the truth), and
    a restore resumes from the exact captured step."""
    d = os.path.join(str(tmp_path), "guard_ckpt")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO)
    proc = subprocess.Popen(
        [sys.executable, "-c", GUARD_CHILD, d], env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "ready"
        time.sleep(0.5)                       # let a few steps tick
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    # died BY SIGTERM (grace handler re-raises the default disposition)
    assert proc.returncode == -signal.SIGTERM, (
        proc.returncode, proc.stderr.read()[-2000:])

    from paddle_tpu.incubate.checkpoint import TrainingCheckpoint
    ck = TrainingCheckpoint(d, save_interval_steps=10 ** 9,
                            async_save=False)
    try:
        latest = ck.latest_step()
        assert latest is not None and latest >= 1, \
            "grace checkpoint never landed"
        st = ck.restore()
        # checkpoint is internally consistent with ITS step label — the
        # exact step the signal interrupted, not a stale periodic save
        assert int(st["counters"]["global_step"]) == latest
        np.testing.assert_array_equal(
            st["w"], np.full((4,), latest, np.float32))
    finally:
        ck.close()


def test_preemption_guard_restore_into_resumes_exact_step(tmp_path):
    """restore_into() on a model picks the training loop back up at the
    grace-saved step (counters round-trip through capture/restore)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.incubate.checkpoint import (PreemptionGuard,
                                                TrainingCheckpoint)

    def build():
        paddle.seed(7)
        net = nn.Sequential(nn.Linear(4, 1))
        model = paddle.Model(net)
        model.prepare(
            optimizer=optimizer.SGD(learning_rate=0.1,
                                    parameters=net.parameters()),
            loss=nn.MSELoss())
        return model

    d = os.path.join(str(tmp_path), "resume_ckpt")
    model = build()
    ck = TrainingCheckpoint(d, async_save=False)
    step_at_signal = 17

    def capture():
        return step_at_signal, ck.capture(model, epoch=2,
                                          step=step_at_signal,
                                          global_step=step_at_signal)

    # in-process SIGTERM with a chained no-op handler: the guard must
    # grace-save, then defer to the previous (callable) handler instead
    # of killing the test process
    fired = []
    prev = signal.signal(signal.SIGTERM, lambda s, f: fired.append(s))
    try:
        with PreemptionGuard(ck, capture) as guard:
            os.kill(os.getpid(), signal.SIGTERM)
        assert guard.fired and fired == [signal.SIGTERM]
    finally:
        signal.signal(signal.SIGTERM, prev)

    model2 = build()
    counters = ck.restore_into(model2)
    assert {k: int(v) for k, v in counters.items()} == {
        "epoch": 2, "step": step_at_signal,
        "global_step": step_at_signal}
    ck.close()
