"""Input tokens of the optimizer steps completed in the window, per second
of window, per chip. The window opens and closes on a drained step."""
from benchmark.lib.stats import rate

UNIT, SOURCE = "tokens/s/chip", "host_clock"


def read(obs):
    if "tokens" not in obs:
        return None
    return rate(obs["tokens"], obs["window_s"]) / obs["chips"]
