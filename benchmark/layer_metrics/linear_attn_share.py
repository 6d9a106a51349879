"""Device time of the two gated delta-rule kernels' events over device busy
time, from the trace: the chunked scan of the prefills
(`kernel_patterns.gdn_chunk`) and the decode step's state update
(`kernel_patterns.gdn_step`), both printed in seconds beside the busy
time (a kernel is named after the jitted function around it). What XLA
does around them (the convolution, the norms, the chunks' preparation) is
not in it. Without the patterns, or with no event that matches either,
nothing is reported."""
from benchmark.layer_metrics.paged_attn_share import kernel_s
from benchmark.lib.trace_reduce import busy_s

LAYER, UNIT, SOURCE, MOVES = ("kernels", "%", "device_trace",
                              "serve_tokens_per_s")


def read(obs):
    patterns = obs.get("kernel_patterns", {})
    ops = obs.get("trace_ops")
    if not ops or not patterns.get("gdn_chunk") \
            or not patterns.get("gdn_step"):
        return None
    events = ops[min(ops)]
    chunk_s = kernel_s(events, patterns["gdn_chunk"])
    step_s = kernel_s(events, patterns["gdn_step"])
    if not chunk_s + step_s:
        return None
    busy = busy_s(events)
    print(f"trace: kernel seconds: chunked scan {chunk_s:.4f}, state update "
          f"{step_s:.4f}, of {busy:.4f} busy", flush=True)
    return 100.0 * (chunk_s + step_s) / busy
