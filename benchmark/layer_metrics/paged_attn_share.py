"""Device time of the paged decode-attention kernel's events over device
busy time, from the trace. The configuration file says how the profiler
names the kernel (`kernel_patterns.paged_attn`); without a pattern, or
with no matching event, nothing is reported."""
from benchmark.lib.trace_reduce import share_of_busy

LAYER, UNIT, SOURCE, MOVES = ("kernels", "%", "device_trace",
                              "serve_tokens_per_s")


def read(obs):
    pattern = obs.get("kernel_patterns", {}).get("paged_attn")
    ops = obs.get("trace_ops")
    if not pattern or not ops:
        return None
    share = share_of_busy(ops[min(ops)], pattern)
    return None if share is None else 100.0 * share
