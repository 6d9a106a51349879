"""Device ms a decode step spends under the scope `window_attn`: the
sliding-window layers' attention, the norm before it, the projections,
the rotary embedding, the gate, the ring's write (the Pallas token
writer), the grouped-query paged kernel over the rings, the output
projection and the residual add, summed over the sliding layers; from the
decode program's top-level operations in the trace and the program's map
of instruction to scope (benchmark/lib/scope_reduce.py). `decode_attn_ms`
reads the full layers (`attn`) beside it; `gqa_paged_attn_roofline` reads
the kernel alone."""
from benchmark.lib import scope_reduce

LAYER, UNIT, SOURCE, MOVES = ("decode step", "ms", "device_trace",
                              "serve_tokens_per_s")


def read(obs):
    if not obs.get("samples") \
            or "attn_window_decode_tokens_read" not in obs["samples"][0]:
        return None      # a program without window layers
    return scope_reduce.decode_ms(obs, "window_attn")
