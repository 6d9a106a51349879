"""Preemption-proof elastic training (ISSUE 8): trainer death is a
non-event, proven bitwise.

The contract under test (incubate/checkpoint.py integrity tier +
io data-resume + distributed/elastic.py Supervisor + PSClient replay
persistence):

- THE proof: a PS-backed, pipelined (static PipelineRunner) training
  subprocess SIGKILLed — no grace, not SIGTERM — at a seeded mid-epoch
  step and restarted by the supervisor ends with final params AND every
  server's `table.applied` counters bitwise-equal to the uninterrupted
  run (re-sent in-doubt pushes dedupe under the checkpoint-persisted
  replay identity);
- SIGKILL racing an async checkpoint save leaves a restorable directory;
- a truncated/corrupted newest checkpoint is caught by manifest
  verification, quarantined, and restore lands on the previous verified
  step;
- `restore_into` on a model whose parameter shapes changed raises a
  clear per-param error, not a broadcast crash;
- `train_epoch_range` killed between the yield and its post-epoch save
  REDOES the interrupted epoch;
- `DataLoader.state_dict()` resumes mid-epoch at the exact batch with
  the exact shuffle;
- the Supervisor kills and restarts a trainer whose heartbeat beats but
  whose step counter stalls, and `_reap` escalates TERM -> KILL for a
  child that ignores SIGTERM.
"""
import glob
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
sys.path.insert(0, os.path.join(REPO, "tools"))

pytestmark = pytest.mark.chaos

CHILD_ENV = dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=f"{os.path.join(REPO, 'tools')}:{REPO}")


# ------------------------------------------------- THE acceptance proof

def test_sigkill_midepoch_supervised_restart_bitwise_equal(tmp_path):
    """SIGKILL a PS-backed pipelined trainer at the seeded mid-epoch
    step; the supervisor restarts it; the resumed run must be
    indistinguishable — params bitwise, per-server applied counters
    exact (zero lost, zero double-applied), >=1 server-side replay
    actually exercised."""
    import elastic_drill as drill
    from paddle_tpu.core import monitor

    ref = drill.run_supervised(str(tmp_path), kill=False)
    # fault-free supervisor saw zero restarts
    assert ref[4] == []

    replays0 = monitor.stat_get("ps.rpc.replays")
    chaos = drill.run_supervised(str(tmp_path), kill=True)

    # the kill actually happened (SIGKILL, supervised restart)
    assert any("rc=-9" in e[2] for e in chaos[4]), chaos[4]
    kill_marker = os.path.join(str(tmp_path), "killed_chaos")
    assert os.path.exists(kill_marker)
    kill_step = int(open(kill_marker).read())
    assert kill_step == drill.kill_step_for(drill.DRILL_SEED)
    assert 0 < kill_step < drill.DRILL_STEPS  # mid-epoch, seeded

    # ...and left in-doubt pushes that were REPLAYED, not re-applied
    assert monitor.stat_get("ps.rpc.replays") - replays0 >= 1

    # bitwise: dense-model params (through the pipelined executor +
    # checkpoint restore)...
    assert set(ref[0]) == set(chaos[0])
    for k in ref[0]:
        np.testing.assert_array_equal(ref[0][k], chaos[0][k],
                                      err_msg=f"param {k}")
    # ...the PS tables themselves...
    np.testing.assert_array_equal(ref[1], chaos[1])
    np.testing.assert_array_equal(ref[2], chaos[2])
    # ...and the exactly-once observable: per-server applied counters.
    # dense0 is owned by one shard: its owner applied EXACTLY one push
    # per step — a single lost or double-applied in-doubt push breaks it
    assert ref[3] == chaos[3]
    assert max(s["dense0"] for s in chaos[3].values()) \
        == drill.DRILL_STEPS


# ---------------------------------------- checkpoint integrity tier

def _save_steps(directory, steps, async_save=False):
    from paddle_tpu.incubate.checkpoint import TrainingCheckpoint
    ck = TrainingCheckpoint(directory, keep=4, async_save=async_save)
    for s in steps:
        ck.save(s, {"w": np.arange(64, dtype="float32") * s,
                    "step": s})
    ck.wait()
    return ck


def test_truncated_newest_checkpoint_falls_back_to_verified(tmp_path):
    from paddle_tpu.core import monitor
    from paddle_tpu.incubate.checkpoint import (CheckpointCorruptError,
                                                TrainingCheckpoint)
    d = str(tmp_path / "ck")
    _save_steps(d, (1, 2)).close()

    # truncate/garble the newest step's payload blobs on disk
    blobs = glob.glob(os.path.join(d, "2", "default", "**", "d", "*"),
                      recursive=True)
    assert blobs, "no ocdbt data blobs found — layout changed?"
    for fp in blobs:
        with open(fp, "r+b") as f:
            sz = os.path.getsize(fp)
            f.truncate(max(sz // 2, 1))

    ck = TrainingCheckpoint(d, keep=4, async_save=False)
    # explicit-step restore: structured error, not garbage
    with pytest.raises(CheckpointCorruptError) as ei:
        ck.restore(2)
    assert ei.value.step == 2

    # latest-restore: quarantine + counter + walk back to verified step 1
    before = monitor.stat_get("ckpt.corrupt_skipped")
    st = ck.restore()
    assert int(st["step"]) == 1
    np.testing.assert_array_equal(st["w"],
                                  np.arange(64, dtype="float32"))
    assert monitor.stat_get("ckpt.corrupt_skipped") == before + 1
    q = os.path.join(d, ".quarantine")
    assert os.path.isdir(q) and any(n.startswith("2")
                                    for n in os.listdir(q))
    # the bad step is OUT of the walk: a fresh manager restores 1 clean
    st2 = TrainingCheckpoint(d, keep=4, async_save=False).restore()
    assert int(st2["step"]) == 1


def test_hash_mismatch_names_the_leaf(tmp_path):
    """A silent bit-flip (size-preserving, so the store layer may not
    notice) is caught by the per-leaf sha256 and NAMES the leaf."""
    from paddle_tpu.incubate.checkpoint import (CheckpointCorruptError,
                                                TrainingCheckpoint,
                                                build_manifest)
    d = str(tmp_path / "ck")
    ck = _save_steps(d, (3,))
    # forge the manifest as if leaf "w" had different bytes: simulates
    # stored-data corruption the reader cannot see structurally
    man = build_manifest(3, {"w": np.zeros(64, "float32"),
                             "step": np.asarray(3)})
    with open(os.path.join(d, "manifest_3.json"), "w") as f:
        json.dump(man, f)
    with pytest.raises(CheckpointCorruptError) as ei:
        ck.restore(3)
    assert ei.value.leaf == "w"
    assert "sha256" in ei.value.reason


def test_sigkill_during_async_save_leaves_restorable_dir(tmp_path):
    """Kill the trainer WHILE an async checkpoint is writing: the
    directory must stay restorable (the previous committed step; or the
    new one if the commit won the race) — never a crash, never garbage."""
    d = str(tmp_path / "ck")
    child = textwrap.dedent(f"""
        import os, numpy as np
        import jax; jax.config.update("jax_platforms", "cpu")
        from paddle_tpu.incubate.checkpoint import TrainingCheckpoint
        ck = TrainingCheckpoint({d!r}, keep=3, async_save=True)
        ck.save(1, {{"w": np.full((1 << 10,), 1, "float32"), "step": 1}})
        ck.wait()
        # a BIG step 2 so the async write is still in flight at kill
        ck.save(2, {{"w": np.ones((1 << 22,), "float32"), "step": 2}})
        os.kill(os.getpid(), 9)
    """)
    proc = subprocess.run([sys.executable, "-c", child], env=CHILD_ENV,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == -signal.SIGKILL, proc.stderr[-2000:]

    from paddle_tpu.incubate.checkpoint import TrainingCheckpoint
    ck = TrainingCheckpoint(d, keep=3, async_save=False)
    st = ck.restore()
    assert st is not None, "SIGKILL during async save lost ALL state"
    step = int(st["step"])
    assert step in (1, 2)
    np.testing.assert_array_equal(
        np.asarray(st["w"])[:4], np.full((4,), step, "float32"))


def test_restore_into_shape_mismatch_names_param(tmp_path):
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.incubate.checkpoint import TrainingCheckpoint

    def build(in_dim):
        net = nn.Sequential(nn.Linear(in_dim, 3), nn.Linear(3, 1))
        model = paddle.Model(net)
        model.prepare(optimizer=optimizer.Adam(
            learning_rate=0.01, parameters=net.parameters()),
            loss=nn.MSELoss())
        return model

    d = str(tmp_path / "ck")
    ck = TrainingCheckpoint(d, keep=2, async_save=False)
    ck.save(5, ck.capture(build(4), 0, 4, 5))
    ck.wait()

    with pytest.raises(ValueError, match="shape mismatch") as ei:
        ck.restore_into(build(6))   # first Linear grew: [4,3] -> [6,3]
    msg = str(ei.value)
    assert "[4, 3]" in msg and "[6, 3]" in msg
    # the offending parameter is NAMED
    assert ".w_" in msg or "weight" in msg, msg


def test_train_epoch_range_killed_before_commit_redoes_epoch(tmp_path):
    """Killed between the yield (body done) and the post-epoch save:
    the interrupted epoch must be REDONE on restart, never skipped."""
    from paddle_tpu.incubate.checkpoint import train_epoch_range
    d = str(tmp_path / "er")
    gen = train_epoch_range(4, directory=d)
    assert next(gen) == 0
    assert next(gen) == 1    # resuming the iterator commits epoch 0...
    gen.close()              # ...then death lands before epoch 1 commits
    assert list(train_epoch_range(4, directory=d)) == [1, 2, 3]


# -------------------------------------------------- exact data resume

class _IdxDataset:
    def __init__(self, n):
        self.n = n

    def __getitem__(self, i):
        return np.asarray([i], np.int64)

    def __len__(self):
        return self.n


def _batch_ids(batches):
    return [tuple(int(v) for v in np.asarray(b).ravel()) for b in batches]


def test_dataloader_exact_midepoch_resume_with_shuffle():
    from paddle_tpu.io import DataLoader

    ref = DataLoader(_IdxDataset(12), batch_size=3, shuffle=True,
                     shuffle_seed=42)
    sched = [_batch_ids(ref) for _ in range(3)]   # 3 uninterrupted epochs
    assert sched[0] != sched[1]                   # reshuffles per epoch

    run = DataLoader(_IdxDataset(12), batch_size=3, shuffle=True,
                     shuffle_seed=42)
    _batch_ids(run)                               # epoch 0
    it = iter(run)
    consumed = [next(it), next(it)]               # 2 batches of epoch 1
    assert _batch_ids(consumed) == sched[1][:2]
    sd = run.state_dict()
    assert sd["epoch"] == 1 and sd["batch"] == 2

    # a FRESH loader (new process, different default seed) + state
    res = DataLoader(_IdxDataset(12), batch_size=3, shuffle=True,
                     shuffle_seed=7)
    res.load_state_dict(sd)
    assert _batch_ids(res) == sched[1][2:]        # exact mid-epoch tail
    assert _batch_ids(res) == sched[2]            # next epoch exact too


def test_dataloader_completed_epoch_state_rolls_forward():
    from paddle_tpu.io import DataLoader
    ref = DataLoader(_IdxDataset(8), batch_size=2, shuffle=True,
                     shuffle_seed=3)
    sched = [_batch_ids(ref) for _ in range(2)]

    run = DataLoader(_IdxDataset(8), batch_size=2, shuffle=True,
                     shuffle_seed=3)
    it = iter(run)
    for _ in range(4):
        next(it)                     # consume ALL of epoch 0...
    sd = run.state_dict()            # ...but the epoch never rolled
    assert sd["epoch"] == 0 and sd["batch"] == 4

    res = DataLoader(_IdxDataset(8), batch_size=2, shuffle=True,
                     shuffle_seed=99)
    res.load_state_dict(sd)
    assert _batch_ids(res) == sched[1]   # auto-rolls into epoch 1, exact


def test_checkpoint_carries_data_section_roundtrip(tmp_path):
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.incubate.checkpoint import TrainingCheckpoint
    from paddle_tpu.io import DataLoader

    net = nn.Sequential(nn.Linear(2, 1))
    model = paddle.Model(net)
    model.prepare(optimizer=optimizer.Adam(learning_rate=0.01,
                                           parameters=net.parameters()),
                  loss=nn.MSELoss())
    loader = DataLoader(_IdxDataset(10), batch_size=2, shuffle=True,
                        shuffle_seed=5)
    it = iter(loader)
    next(it), next(it), next(it)
    data_state = loader.state_dict()     # position: epoch 0, batch 3
    expect_tail = _batch_ids(it)         # rest of the epoch

    ck = TrainingCheckpoint(str(tmp_path / "ck"), keep=2,
                            async_save=False)
    ck.save(3, ck.capture(model, 0, 2, 3, data_state=data_state))
    ck.wait()

    loader2 = DataLoader(_IdxDataset(10), batch_size=2, shuffle=True,
                         shuffle_seed=5)
    counters = ck.restore_into(model, data_loader=loader2)
    assert counters["data_resumed"] is True
    assert counters == {**counters, "epoch": 0, "step": 2,
                        "global_step": 3}
    # loader2 was mid-epoch-armed: wait, loader above consumed 3 batches
    got = _batch_ids(loader2)
    assert got == expect_tail


def test_train_from_dataset_start_batch_resumes_exact(tmp_path):
    """Executor.train_from_dataset(start_batch=N) — the two halves of a
    split run produce the same final params as the whole run."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, ops, optimizer, static

    def build(tag):
        paddle.seed(0)
        prog = static.Program(f"tfd_{tag}")
        with static.program_guard(prog):
            x = static.data("x", [-1, 4], "float32")
            y = static.data("y", [-1, 1], "float32")
            loss = ops.mse_loss(nn.Linear(4, 1)(x), y)
            optimizer.SGD(learning_rate=0.1).minimize(loss)
        return prog, loss

    class _Feeds:
        def __init__(self, n):
            self.n = n

        def batches(self, start_batch=0):
            rng = np.random.RandomState(5)
            all_ = [{"x": rng.rand(4, 4).astype("float32"),
                     "y": rng.rand(4, 1).astype("float32")}
                    for _ in range(self.n)]
            yield from all_[int(start_batch):]

    paddle.enable_static()
    try:
        exe = static.Executor()
        prog, _ = build("whole")
        exe.train_from_dataset(prog, _Feeds(6))
        want = [np.asarray(static.global_scope().get(n))
                for n in prog.persist_ids]

        prog2, _ = build("split")
        exe.train_from_dataset(prog2, _Feeds(3))     # first 3 batches
        exe.train_from_dataset(prog2, _Feeds(6), start_batch=3)
        got = [np.asarray(static.global_scope().get(n))
               for n in prog2.persist_ids]
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
    finally:
        paddle.disable_static()


def test_fit_resume_at_epoch_boundary_stays_bitwise(tmp_path):
    """A checkpoint saved exactly at an epoch boundary (freq divides the
    epoch length, steps=None) must resume into the NEXT epoch — not
    re-train one extra loader epoch under a stale epoch label."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.io import DataLoader

    class DS:
        def __getitem__(self, i):
            r = np.random.RandomState(i)
            return (r.rand(4).astype("float32"),
                    r.rand(1).astype("float32"))

        def __len__(self):
            return 12

    def build():
        paddle.seed(9)
        net = nn.Sequential(nn.Linear(4, 3), nn.Linear(3, 1))
        model = paddle.Model(net)
        model.prepare(optimizer=optimizer.Adam(
            learning_rate=0.01, parameters=net.parameters()),
            loss=nn.MSELoss())
        return model, net

    def loader():
        return DataLoader(DS(), batch_size=2, shuffle=True,
                          shuffle_seed=13)

    def params(net):
        return {k: np.asarray(v._value if hasattr(v, "_value") else v)
                for k, v in net.state_dict().items()}

    ref_model, ref_net = build()
    ref_model.fit(train_data=loader(), epochs=3, verbose=0)
    want = params(ref_net)

    # epoch length 6, freq 6: the save lands exactly at epoch 0's end
    # with data cursor batch == len(loader); fit(epochs=1) then ends —
    # the same on-disk state a kill right after that save leaves
    d = str(tmp_path / "ck")
    m1, _ = build()
    m1.fit(train_data=loader(), epochs=1, verbose=0,
           auto_checkpoint_dir=d, auto_checkpoint_freq=6)

    m2, net2 = build()
    m2.fit(train_data=loader(), epochs=3, verbose=0,
           auto_checkpoint_dir=d, auto_checkpoint_freq=6)
    got = params(net2)
    assert set(want) == set(got)
    for k in want:
        np.testing.assert_array_equal(want[k], got[k], err_msg=k)


# ------------------------------------------------- supervisor behavior

_STALL_SCRIPT = textwrap.dedent("""
    import json, os, sys, time
    hb, cnt = sys.argv[1], sys.argv[2]
    n = int(open(cnt).read()) if os.path.exists(cnt) else 0
    with open(cnt, "w") as f:
        f.write(str(n + 1))
    if n >= 1:
        sys.exit(0)          # restarted attempt: healthy, done
    os.makedirs(hb, exist_ok=True)
    t0 = time.time()
    while time.time() - t0 < 60:
        tmp = os.path.join(hb, "heartbeat_0.json.tmp")
        with open(tmp, "w") as f:       # beats keep coming...
            json.dump({"rank": 0, "step": 5,    # ...step NEVER advances
                       "time": time.time()}, f)
        os.replace(tmp, os.path.join(hb, "heartbeat_0.json"))
        time.sleep(0.05)
""")


def test_supervisor_restarts_stalled_trainer(tmp_path):
    from paddle_tpu.core import monitor
    from paddle_tpu.distributed.elastic import Supervisor
    script = tmp_path / "stall.py"
    script.write_text(_STALL_SCRIPT)
    hb = str(tmp_path / "hb")
    cnt = str(tmp_path / "attempts")

    def start(rank):
        return subprocess.Popen([sys.executable, str(script), hb, cnt],
                                env=dict(os.environ))

    stalls0 = monitor.stat_get("elastic.stalls")
    sup = Supervisor(start, nranks=1, heartbeat_dir=hb, max_restarts=2,
                     backoff_s=0.05, heartbeat_timeout_s=30.0,
                     stall_timeout_s=1.0, poll_s=0.1)
    assert sup.run() == 0
    assert any("stalled" in e[2] for e in sup.events), sup.events
    assert monitor.stat_get("elastic.stalls") > stalls0
    assert int(open(cnt).read()) == 2    # original + one restart


def test_supervisor_exhausted_budget_raises(tmp_path):
    from paddle_tpu.distributed.elastic import Supervisor

    def start(rank):
        return subprocess.Popen([sys.executable, "-c",
                                 "import sys; sys.exit(3)"])

    sup = Supervisor(start, nranks=1, max_restarts=1, backoff_s=0.01,
                     poll_s=0.05)
    with pytest.raises(SystemExit) as ei:
        sup.run()
    assert ei.value.code == 3
    assert sup.restarts[0] == 2          # budget burned, then gave up


def test_reap_escalates_term_to_kill():
    """Satellite: a child that ignores SIGTERM must not hang or leak
    through the launcher teardown — bounded wait, then KILL."""
    from paddle_tpu.distributed.elastic import _reap
    p = subprocess.Popen([sys.executable, "-c", textwrap.dedent("""
        import signal, time
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        print("armed", flush=True)
        time.sleep(120)
    """)], stdout=subprocess.PIPE, text=True)
    assert p.stdout.readline().strip() == "armed"
    t0 = time.monotonic()
    _reap([p], grace_s=1.0)
    assert time.monotonic() - t0 < 30
    assert p.poll() == -signal.SIGKILL


def test_supervisor_ignores_previous_incarnation_beats(tmp_path):
    """A stale beat file left by a killed incarnation (or a previous
    job in the same dir) must not storm the restart budget: the
    supervisor grants the restarted child its startup window instead of
    re-declaring staleness every poll."""
    from paddle_tpu.distributed.elastic import Supervisor
    hb = str(tmp_path / "hb")
    os.makedirs(hb)
    with open(os.path.join(hb, "heartbeat_0.json"), "w") as f:
        json.dump({"rank": 0, "step": 3, "time": time.time() - 1000}, f)

    script = textwrap.dedent("""
        import json, os, sys, time
        hb = sys.argv[1]
        time.sleep(0.5)     # several poll cycles with only the stale beat
        tmp = os.path.join(hb, "heartbeat_0.json.tmp")
        with open(tmp, "w") as f:
            json.dump({"rank": 0, "step": 1, "time": time.time()}, f)
        os.replace(tmp, os.path.join(hb, "heartbeat_0.json"))
    """)

    def start(rank):
        return subprocess.Popen([sys.executable, "-c", script, hb],
                                env=dict(os.environ))

    sup = Supervisor(start, nranks=1, heartbeat_dir=hb, max_restarts=3,
                     backoff_s=0.05, heartbeat_timeout_s=2.0,
                     stall_timeout_s=300.0, poll_s=0.05)
    assert sup.run() == 0
    assert sup.events == [], sup.events   # zero restarts burned


def test_armed_loader_state_dict_returns_restored_position():
    """A grace save taken BEFORE the first resumed batch must re-save
    the restored cursor, not the loader's stale local counters."""
    from paddle_tpu.io import DataLoader
    run = DataLoader(_IdxDataset(12), batch_size=3, shuffle=True,
                     shuffle_seed=42)
    it = iter(run)
    next(it), next(it)
    sd = run.state_dict()

    res = DataLoader(_IdxDataset(12), batch_size=3, shuffle=True,
                     shuffle_seed=7)
    res.load_state_dict(sd)
    armed = res.state_dict()             # before ANY resumed iteration
    assert armed["epoch"] == sd["epoch"]
    assert armed["batch"] == sd["batch"]
    np.testing.assert_array_equal(
        armed["sampler"]["sampler"]["rng"]["key"],
        sd["sampler"]["sampler"]["rng"]["key"])


def test_roll_resumed_epoch_starts_next_epoch_fresh():
    """fit(steps=N) truncates epochs at a batch count the loader can't
    see; rolling the armed resume must advance the shuffle stream past
    the truncated epoch and start the next one fresh — not replay the
    truncated epoch's tail."""
    from paddle_tpu.io import DataLoader
    ref = DataLoader(_IdxDataset(12), batch_size=3, shuffle=True,
                     shuffle_seed=21)
    sched = [_batch_ids(ref) for _ in range(2)]

    run = DataLoader(_IdxDataset(12), batch_size=3, shuffle=True,
                     shuffle_seed=21)
    it = iter(run)
    next(it), next(it)                   # steps=2 cap: epoch truncated
    sd = run.state_dict()

    res = DataLoader(_IdxDataset(12), batch_size=3, shuffle=True,
                     shuffle_seed=99)
    res.load_state_dict(sd)
    res.roll_resumed_epoch()
    assert _batch_ids(res) == sched[1]   # fresh epoch-1 permutation


def test_heartbeat_beat_thread_writes_live_step(tmp_path):
    """Satellite: the beat thread must carry the LIVE step (step_fn /
    notify_step), not the last update(step=...) snapshot."""
    from paddle_tpu.core import monitor
    from paddle_tpu.distributed import elastic
    step = {"n": 0}
    hb = elastic.Heartbeat(str(tmp_path), rank=0, interval_s=0.05,
                           step_fn=lambda: step["n"]).start()
    try:
        step["n"] = 41
        deadline = time.monotonic() + 5
        path = os.path.join(str(tmp_path), "heartbeat_0.json")
        got = None
        while time.monotonic() < deadline:
            with open(path) as f:
                got = json.load(f)["step"]
            if got == 41:
                break
            time.sleep(0.02)
        assert got == 41, "beat thread kept re-writing a stale step"
        # the supervisor-side age gauge publishes on check()
        assert elastic.Heartbeat.check(str(tmp_path), timeout_s=60) == []
        assert monitor.stat_get("elastic.heartbeat_age_s") >= 0
    finally:
        hb.stop()


def test_notify_step_reaches_registered_listeners(tmp_path):
    from paddle_tpu.distributed import elastic
    mon = elastic.StallMonitor(timeout_s=300.0).start()
    hb = elastic.Heartbeat(str(tmp_path), rank=0,
                           interval_s=60.0).start()
    try:
        before = mon._last
        time.sleep(0.01)
        elastic.notify_step(17)
        assert mon._last > before
        assert hb._step == 17
    finally:
        mon.stop()
        hb.stop()


def test_stall_monitor_default_flight_records(tmp_path, monkeypatch):
    """Satellite: the default on_stall counts elastic.stalls and writes
    a flight-recorder dump (reason=stall)."""
    from paddle_tpu.core import monitor
    from paddle_tpu.distributed.elastic import StallMonitor
    monkeypatch.setenv("PADDLE_TPU_DUMP_DIR", str(tmp_path))
    before = monitor.stat_get("elastic.stalls")
    m = StallMonitor(timeout_s=300.0)
    m.on_stall(12.5)
    assert monitor.stat_get("elastic.stalls") == before + 1
    assert glob.glob(os.path.join(str(tmp_path), "obsdump_stall_*"))
