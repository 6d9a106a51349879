"""launch.py-driven PS job with a worker dying mid-epoch (VERDICT r03
item 6 'Done' clause): elastic whole-job restart recovers with table
state intact via the snapshot file."""
import os
import subprocess
import sys
import textwrap

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = textwrap.dedent("""
    import os, sys
    import numpy as np

    role = os.environ["TRAINING_ROLE"]
    attempt = int(os.environ.get("PADDLE_LAUNCH_ATTEMPT", "0"))
    workdir = sys.argv[1]
    snap = os.path.join(workdir, "snap")

    if role == "PSERVER":
        from paddle_tpu.distributed.ps import PSServer
        port = os.environ["PADDLE_PORT"]
        srv = PSServer(endpoint=f"127.0.0.1:{port}", tables={
            "emb": {"type": "sparse", "dim": 4, "optimizer": "sgd",
                    "lr": 1.0, "init": "zeros"}})
        srv.start()
        srv.run()                       # until stop_servers
        sys.exit(0)

    # ---- worker --------------------------------------------------------
    from paddle_tpu.distributed.ps import PSClient
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    eps = os.environ["PADDLE_PSERVERS_IP_PORT_LIST"].split(",")
    client = PSClient(eps)
    ids = np.arange(4, dtype=np.int64)

    if attempt > 0 and rank == 0 and os.path.exists(snap + ".s0"):
        # restart path: restore table state before continuing
        client.load_snapshot(snap)

    client.pull_sparse("emb", ids)
    client.push_sparse_grad("emb", ids, np.ones((4, 4), np.float32))
    if rank == 0:
        client.save_snapshot(snap)

    if attempt == 0 and rank == 0:
        # die mid-epoch on the first attempt (the "kill")
        os._exit(7)

    client.push_sparse_grad("emb", ids, np.ones((4, 4), np.float32))
    rows = client.pull_sparse("emb", ids)
    if rank == 0 and attempt > 0:
        # restored snapshot (-1s and lower from attempt 0) + this run's
        # two pushes: monotone descent proves state carried over rather
        # than restarting from zeros
        assert (np.asarray(rows) <= -2.999).all(), np.asarray(rows)
    with open(os.path.join(workdir, f"ok_{rank}_{attempt}"), "w") as f:
        f.write("done")
    if rank == 0:
        # wait for the peer before shutting servers down — stopping while
        # rank 1 is mid-push would fail its RPC and flap the job
        import time
        peer = os.path.join(workdir, f"ok_1_{attempt}")
        deadline = time.time() + 60
        while not os.path.exists(peer) and time.time() < deadline:
            time.sleep(0.1)
        client.stop_servers()
    client.close()
    sys.exit(0)
""")


def test_launch_ps_kill_worker_recovers(tmp_path):
    script = tmp_path / "ps_job.py"
    script.write_text(_SCRIPT)
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    code = (
        "from paddle_tpu.distributed.launch import launch_ps; "
        f"launch_ps({str(script)!r}, ({str(tmp_path)!r},), server_num=1, "
        f"worker_num=2, start_port={port}, elastic_retries=2)")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": f"{os.environ.get('PYTHONPATH', '')}:{REPO}"}
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"launch failed:\n{r.stdout}\n{r.stderr}"
    assert "elastic restart 1/2" in r.stdout
    import glob
    oks = sorted(os.path.basename(f)
                 for f in glob.glob(str(tmp_path / "ok_*")))
    # rank 0 must have completed on a RESTARTED attempt (it dies on #0)
    assert any(f.startswith("ok_0_") and not f.endswith("_0")
               for f in oks), oks
    assert any(f.startswith("ok_1_") for f in oks), oks
