"""The benchmark's own tests run on the CPU at toy size (four virtual
devices for the dp4 driver): `python -m pytest benchmark/tests -q` from the
repo root. Tier-1 collects tests/ only."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)
