"""Device time of the paged decode-attention kernel's events over device
busy time, from the trace. The configuration file says how the profiler
names each of the program's Pallas kernels (a kernel is named after the
jitted function around it): `kernel_patterns.paged_attn` the attention
kernel, whose share this is, and `kernel_patterns.kv_write` the decode
step's KV writer, whose seconds are printed beside the attention kernel's.
A share of busy time reads the wrong way once nothing else is left on the
device: compare the printed seconds. Without an attention pattern, or with
no event that matches it, nothing is reported."""
import re

from benchmark.lib.trace_reduce import busy_s

LAYER, UNIT, SOURCE, MOVES = ("kernels", "%", "device_trace",
                              "serve_tokens_per_s")


def kernel_s(events, pattern):
    rx = re.compile(pattern)
    return busy_s([e for e in events if rx.search(e[0])])


def read(obs):
    patterns = obs.get("kernel_patterns", {})
    ops = obs.get("trace_ops")
    if not patterns.get("paged_attn") or not ops:
        return None
    events = ops[min(ops)]
    attention_s = kernel_s(events, patterns["paged_attn"])
    if not attention_s:
        return None
    busy = busy_s(events)
    said = f"trace: kernel seconds: paged attention {attention_s:.4f}"
    if patterns.get("kv_write"):
        said += (f", KV writer ({patterns['kv_write']}) "
                 f"{kernel_s(events, patterns['kv_write']):.4f}")
    print(f"{said}, of {busy:.4f} busy", flush=True)
    return 100.0 * attention_s / busy
