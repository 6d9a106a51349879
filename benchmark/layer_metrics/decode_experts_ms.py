"""Device ms a decode step spends in its expert layers: the scopes
`router` (scores, top-k, weights, the counts the serving counters are made
of) and `experts` (the held experts' products with their gather and sum,
the grouped Pallas kernel or the `while` over row blocks, and the
zero-compute experts' identity term); from the decode program's top-level
operations in the trace and the program's map of instruction to scope
(benchmark/lib/scope_reduce.py). The shared expert is under `ffn`."""
from benchmark.lib import scope_reduce

LAYER, UNIT, SOURCE, MOVES = ("expert layer", "ms", "device_trace",
                              "serve_tokens_per_s")


def read(obs):
    return scope_reduce.decode_ms(obs, "router", "experts")
