"""Device time of the paged decode-attention kernel's events over device
busy time, from the trace. The configuration file says how the profiler
names the program's Pallas kernels (`kernel_patterns.paged_attn`); since
PR 26 the decode step holds a second one, the KV writer, which the trace
names apart (a kernel is named after the jitted function around it): its
events are left out of the share and its seconds printed beside the
attention kernel's. A share of busy time reads the wrong way once nothing
else is left on the device: compare the printed seconds. Without a
pattern, or with no matching event, nothing is reported."""
import re

from benchmark.lib.trace_reduce import busy_s

LAYER, UNIT, SOURCE, MOVES = ("kernels", "%", "device_trace",
                              "serve_tokens_per_s")
WRITER = "_paged_write_once"


def read(obs):
    pattern = obs.get("kernel_patterns", {}).get("paged_attn")
    ops = obs.get("trace_ops")
    if not pattern or not ops:
        return None
    events = ops[min(ops)]
    rx = re.compile(pattern)
    kernels = [e for e in events if rx.search(e[0])]
    attention = [e for e in kernels if WRITER not in e[0]]
    if not attention:
        return None
    writer = [e for e in kernels if WRITER in e[0]]
    busy, attention_s = busy_s(events), busy_s(attention)
    print(f"trace: kernel seconds: paged attention {attention_s:.4f}, KV "
          f"writer ({WRITER}) {busy_s(writer):.4f}, of {busy:.4f} busy",
          flush=True)
    return 100.0 * attention_s / busy
