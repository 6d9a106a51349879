"""Device ms a decode step spends under the scope `ffn`: the dense MLP or
SwiGLU of every layer with its norm and residual add, and an expert
layer's shared expert; from the decode program's top-level operations in
the trace and the program's map of instruction to scope
(benchmark/lib/scope_reduce.py). In a decode step this is a weight stream:
compare it with the weights' bytes over the HBM bandwidth."""
from benchmark.lib import scope_reduce

LAYER, UNIT, SOURCE, MOVES = ("decode step", "ms", "device_trace",
                              "serve_tokens_per_s")


def read(obs):
    return scope_reduce.decode_ms(obs, "ffn")
