"""Streams evicted for want of a pool block (`serve.preempted`), per 100
requests due in the window."""
LAYER, UNIT, SOURCE, MOVES = ("KV pool", "count", "program_counter",
                              "ttft_p95_ms")


def read(obs):
    if "counters" not in obs or not obs["attempted"]:
        return None
    return 100.0 * obs["counters"]["serve.preempted"] / obs["attempted"]
