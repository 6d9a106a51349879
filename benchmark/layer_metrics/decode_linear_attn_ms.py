"""Device ms a decode step spends under the scope `linear_attn`: the gated
delta-rule mixer of the hybrid's linear layers, its projections, the
convolution's window, the gates, the state-update kernel (`gdn_step`,
nested) and the output norm and projection; from the decode program's
top-level operations in the trace and the program's map of instruction to
scope (benchmark/lib/scope_reduce.py). `gdn_step_roofline` reads the
kernel alone; this is everything the layer costs a step."""
from benchmark.lib import scope_reduce

LAYER, UNIT, SOURCE, MOVES = ("decode step", "ms", "device_trace",
                              "serve_tokens_per_s")


def read(obs):
    return scope_reduce.decode_ms(obs, "linear_attn")
