"""Delta of the server's `serve.tokens_generated` counter over the window,
per second of window (the counter moves when the host has read a token
back, so after the device)."""
from benchmark.lib.stats import rate

UNIT, SOURCE = "tokens/s", "host_clock"


def read(obs):
    if "counters" not in obs:
        return None
    return rate(obs["counters"]["serve.tokens_generated"], obs["window_s"])
