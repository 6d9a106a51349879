"""Multi-host bootstrap: 2 local processes x 4 CPU devices form ONE
8-device mesh via the PADDLE_* env contract -> jax.distributed
(VERDICT r02 item 6; reference gen_nccl_id_op_helper.cc TCP rendezvous and
test strategy test_dist_base.py:642 — multi-node jobs tested as local
processes)."""
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np

WORKER = textwrap.dedent("""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import distributed as dist

    dist.init_parallel_env({"dp": 8})   # joins the coordination service
    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = dist.get_mesh()
    repl = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P("dp"))

    # dp-sharded least-squares descent: every host must end with the same w
    rng = np.random.RandomState(0)
    X = rng.randn(16, 4).astype(np.float32)
    y = (X @ np.array([1.0, 2.0, 3.0, 4.0], np.float32))
    rank = dist.get_rank()
    Xl, yl = X[rank * 8:(rank + 1) * 8], y[rank * 8:(rank + 1) * 8]
    Xg = jax.make_array_from_process_local_data(row, Xl)
    yg = jax.make_array_from_process_local_data(row, yl)
    w = jax.device_put(jnp.zeros(4, jnp.float32), repl)

    def loss(w, X, y):
        return ((X @ w - y) ** 2).mean()

    step = jax.jit(lambda w, X, y: w - 0.1 * jax.grad(loss)(w, X, y),
                   in_shardings=(repl, row, row), out_shardings=repl)
    for _ in range(20):
        w = step(w, Xg, yg)
    out = np.asarray(w)
    np.save(OUT_PATH, out)
    print("worker", rank, "w=", out)
""")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_eight_device_mesh(tmp_path):
    ports = [_free_port(), _free_port()]
    endpoints = ",".join(f"127.0.0.1:{p}" for p in ports)
    procs, outs = [], []
    for rank in range(2):
        out_path = os.path.join(str(tmp_path), f"w{rank}.npy")
        outs.append(out_path)
        code = f"OUT_PATH = {out_path!r}\n" + WORKER
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            PADDLE_TRAINER_ID=str(rank),
            PADDLE_TRAINERS_NUM="2",
            PADDLE_TRAINER_ENDPOINTS=endpoints,
            PADDLE_CURRENT_ENDPOINT=f"127.0.0.1:{ports[rank]}",
        )
        procs.append(subprocess.Popen(
            [sys.executable, "-c", code], env=env, cwd="/root/repo",
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    logs = []
    for p in procs:
        out, _ = p.communicate(timeout=480)
        logs.append(out)
    for p, log in zip(procs, logs):
        assert p.returncode == 0, log[-3000:]

    w0, w1 = np.load(outs[0]), np.load(outs[1])
    np.testing.assert_array_equal(w0, w1)  # identical params on both hosts

    # and both match the single-process reference descent
    rng = np.random.RandomState(0)
    X = rng.randn(16, 4).astype(np.float32)
    y = X @ np.array([1.0, 2.0, 3.0, 4.0], np.float32)
    w = np.zeros(4, np.float32)
    for _ in range(20):
        w = w - 0.1 * (2.0 / 16) * X.T @ (X @ w - y)
    np.testing.assert_allclose(w0, w, rtol=1e-4)


HYBRID_WORKER = textwrap.dedent("""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import distributed as dist
    from jax.sharding import NamedSharding, PartitionSpec as P

    dist.init_parallel_env()           # join the coordination service
    assert jax.process_count() == 2
    # hybrid: dp across processes (DCN analog), tp within (ICI analog)
    mesh = dist.init_hybrid_mesh({"tp": 4}, {"dp": 2})
    assert mesh.shape == {"dp": 2, "tp": 4}, mesh.shape
    # every dp group must hold devices of ONE process (DCN axis outermost)
    devs = np.asarray(mesh.devices)
    for slice_row in devs:
        assert len({d.process_index for d in slice_row.ravel()}) == 1

    repl = NamedSharding(mesh, P())
    batch = NamedSharding(mesh, P("dp"))
    col = NamedSharding(mesh, P(None, "tp"))   # W1 column-parallel
    row_ = NamedSharding(mesh, P("tp", None))  # W2 row-parallel

    rng = np.random.RandomState(0)
    X = rng.randn(16, 8).astype(np.float32)
    Y = rng.randn(16, 2).astype(np.float32)
    W1 = (rng.randn(8, 8) * 0.3).astype(np.float32)
    W2 = (rng.randn(8, 2) * 0.3).astype(np.float32)

    rank = dist.get_rank()
    Xl, Yl = X[rank * 8:(rank + 1) * 8], Y[rank * 8:(rank + 1) * 8]
    Xg = jax.make_array_from_process_local_data(batch, Xl)
    Yg = jax.make_array_from_process_local_data(batch, Yl)
    w1 = jax.device_put(jnp.asarray(W1), col)
    w2 = jax.device_put(jnp.asarray(W2), row_)

    def loss_fn(w1, w2, X, Y):
        h = jax.nn.relu(X @ w1)
        return ((h @ w2 - Y) ** 2).mean()

    @jax.jit
    def step(w1, w2, X, Y):
        l, g = jax.value_and_grad(loss_fn, argnums=(0, 1))(w1, w2, X, Y)
        return l, w1 - 0.05 * g[0], w2 - 0.05 * g[1]

    losses = []
    for _ in range(10):
        l, w1, w2 = step(w1, w2, Xg, Yg)
        losses.append(float(l))
    np.save(OUT_PATH, np.asarray(losses, np.float64))
    print("hybrid worker", rank, "loss", losses[0], "->", losses[-1])
""")


def test_hybrid_dcn_ici_train_step_matches_single_process(tmp_path):
    """VERDICT r04 item 6: dp-across-processes x tp-within-process train
    step; both processes see the same loss curve as a single-process
    reference."""
    ports = [_free_port(), _free_port()]
    endpoints = ",".join(f"127.0.0.1:{p}" for p in ports)
    procs, outs = [], []
    for rank in range(2):
        out_path = os.path.join(str(tmp_path), f"l{rank}.npy")
        outs.append(out_path)
        code = f"OUT_PATH = {out_path!r}\n" + HYBRID_WORKER
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            PADDLE_TRAINER_ID=str(rank),
            PADDLE_TRAINERS_NUM="2",
            PADDLE_TRAINER_ENDPOINTS=endpoints,
            PADDLE_CURRENT_ENDPOINT=f"127.0.0.1:{ports[rank]}",
        )
        procs.append(subprocess.Popen(
            [sys.executable, "-c", code], env=env, cwd="/root/repo",
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    logs = []
    for p in procs:
        out, _ = p.communicate(timeout=480)
        logs.append(out)
    for p, log in zip(procs, logs):
        assert p.returncode == 0, log[-3000:]

    l0, l1 = np.load(outs[0]), np.load(outs[1])
    np.testing.assert_array_equal(l0, l1)

    # single-process reference: identical math in plain numpy
    rng = np.random.RandomState(0)
    X = rng.randn(16, 8).astype(np.float32)
    Y = rng.randn(16, 2).astype(np.float32)
    W1 = (rng.randn(8, 8) * 0.3).astype(np.float32)
    W2 = (rng.randn(8, 2) * 0.3).astype(np.float32)
    ref = []
    for _ in range(10):
        H = np.maximum(X @ W1, 0)
        P_ = H @ W2
        ref.append(float(((P_ - Y) ** 2).mean()))
        dP = 2.0 / P_.size * (P_ - Y)
        dW2 = H.T @ dP
        dH = dP @ W2.T
        dH[H <= 0] = 0
        dW1 = X.T @ dH
        W1 -= 0.05 * dW1
        W2 -= 0.05 * dW2
    np.testing.assert_allclose(l0, ref, rtol=1e-4)


def test_init_hybrid_mesh_single_process_grouping():
    """Single-process form: 8 CPU devices = 1 slice; a pure-ICI hybrid
    mesh still works and validation catches bad shapes."""
    import jax
    import pytest
    from paddle_tpu.distributed import mesh as mesh_mod
    try:
        m = mesh_mod.init_hybrid_mesh({"tp": 4, "sp": 2}, {"dp": 1})
        assert m.shape == {"dp": 1, "tp": 4, "sp": 2}
        with pytest.raises(ValueError, match="needs 2 slices"):
            mesh_mod.init_hybrid_mesh({"tp": 4}, {"dp": 2})
        with pytest.raises(ValueError, match="appear in both"):
            mesh_mod.init_hybrid_mesh({"dp": 8}, {"dp": 1})
    finally:
        mesh_mod.reset_mesh()
