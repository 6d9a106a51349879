"""The cluster telemetry plane's tier-1 proof: tools/cluster_obs_drill.py
runs a 3-shard-server (+1 backup each) PS fleet, a serve+online-train
client, and a TelemetryHub under seeded RESET/DROP chaos plus a scripted
decode-beat STALL, then permanently kills a shard primary mid-run.

The drill itself asserts the hard invariants (one coalesced incident,
>=3 processes in the merged dump, a trace id crossing client->primary->
backup, hub counter totals bitwise-equal to per-process sums, exactly
the scripted SLO breach); this test runs it end-to-end the way CI does
and cross-checks the printed report.
"""
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.chaos

DRILL = os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                     "cluster_obs_drill.py")


def test_cluster_obs_drill_end_to_end(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               CLUSTER_OBS_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, DRILL], env=env, cwd="/root/repo",
        capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, (proc.returncode, proc.stdout[-2000:],
                                  proc.stderr[-4000:])
    report = json.loads(proc.stdout)
    assert report["violations"] == 0
    assert report["incidents"] == 1
    assert set(report["alerts"]) == {"serve_ttft"}   # scripted breach ONLY
    assert report["stall_fired"] >= 1
    assert len(report["incident_members"]) >= 4      # client + 3 servers
    assert report["cross_process_chains"] >= 1
    # the merged incident dump landed where we pointed it
    assert any(f.startswith("incident_") and f.endswith(".json")
               for f in os.listdir(str(tmp_path)))


def test_cluster_obs_drill_self_check():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, DRILL, "--self-check"], env=env,
        cwd="/root/repo", capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, (proc.stdout[-2000:],
                                  proc.stderr[-2000:])
    assert "clean" in proc.stdout
