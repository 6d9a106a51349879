"""Test config: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's strategy of running distributed tests as multiple
local processes on one host (reference:
python/paddle/fluid/tests/unittests/test_dist_base.py:642) — here XLA's
host-platform device-count spoofing gives us 8 "chips" in-process instead.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# pass-safety harness (static/passes.py): every Program pass runs
# verify-before/verify-after in tests, so a pass bug fails at the rewrite
os.environ.setdefault("PADDLE_TPU_VERIFY_PASSES", "1")

import jax

jax.config.update("jax_platforms", "cpu")
# this process takes the config option; the XLA_FLAGS form above is what
# child processes started by tests inherit
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from tier-1 "
        "(-m 'not slow')")
    config.addinivalue_line(
        "markers", "chaos: seeded deterministic fault-injection suite "
        "(paddle_tpu.testing.faults); fast enough to stay in tier-1")


@pytest.fixture(autouse=True)
def _fresh_seed():
    import numpy as np
    import paddle_tpu
    paddle_tpu.seed(1234)
    np.random.seed(1234)  # tests draw synthetic data from the global RNG;
    yield                 # per-test seeding keeps them order-independent
