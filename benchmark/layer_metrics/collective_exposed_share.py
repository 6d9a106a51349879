"""Share of the traced window in which a collective ran on a device and no
other operation did, mean over the devices: what the gradient all-reduce
costs that the step could not hide. None on one chip (no collective)."""
from benchmark.lib.trace_reduce import exposed_collective_s, span_s

LAYER, UNIT, SOURCE, MOVES = ("sharding", "%", "device_trace",
                              "train_tokens_per_s_chip")


def read(obs):
    shares = []
    for ops in obs.get("trace_ops", {}).values():
        exposed = exposed_collective_s(ops)
        if exposed is not None:
            shares.append(exposed / span_s(ops))
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
