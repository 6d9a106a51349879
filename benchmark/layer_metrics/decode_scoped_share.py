"""Share of the decode step's device time that lies under the program's
own names: over the top-level operations of the decode program's events in
the trace (a `while`'s body left to its `while`), the time of those whose
instruction the program's map (`paddle_tpu/core/program_map.py`, label
`serve/decode`) places under a vocabulary word, over all of it. What is
left is the compiler's own: the weight prefetches (`slice-done`,
`copy-done`) carry no metadata. Under 50 % the executable came from a
compile cache that a tree without scopes filled, and the `decode_*_ms`
readers report nothing. The first of them to run prints the step by word
(benchmark/lib/scope_reduce.py). Nothing is reported without a trace or
from a program that keeps no map."""
from benchmark.lib import scope_reduce

LAYER, UNIT, SOURCE, MOVES = ("decode step", "%", "device_trace",
                              "serve_tokens_per_s")


def read(obs):
    return scope_reduce.scoped_share(scope_reduce.decode_scopes(obs))
