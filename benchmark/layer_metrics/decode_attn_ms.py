"""Device ms a decode step spends under the scope `attn`: the norm before
the attention, the projections, the rotary embedding, the cache write (the
Pallas token writer), the attention itself or its kernel, the output
projection and the residual add, summed over the layers; from the decode
program's top-level operations in the trace and the program's map of
instruction to scope (benchmark/lib/scope_reduce.py). A kernel's own time
(`paged_attn_share`'s printed seconds) is inside it."""
from benchmark.lib import scope_reduce

LAYER, UNIT, SOURCE, MOVES = ("decode step", "ms", "device_trace",
                              "serve_tokens_per_s")


def read(obs):
    return scope_reduce.decode_ms(obs, "attn")
