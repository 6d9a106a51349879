"""Process start to window open: import, weights, pool, warm-up and, in a
checkout's first run, compilation."""
UNIT, SOURCE = "s", "host_clock"


def read(obs):
    return obs["setup_s"]
