"""Share of device busy time spent in prefill programs, from the trace's
"XLA Modules" line (one event per executed program; the configuration's
`module_patterns.prefill` says how the prefill programs are named): every
decoding stream stands still while a prefill runs, so in a cell above its
knee this share is capacity not spent on tokens. `prefill_device_share`
reads the same for cells judged on the gap between tokens."""
import re

from benchmark.lib.trace_reduce import busy_s

LAYER, UNIT, SOURCE, MOVES = ("serve scheduler", "%", "device_trace",
                              "serve_tokens_per_s")


def read(obs):
    pattern = obs.get("module_patterns", {}).get("prefill")
    modules = obs.get("trace_modules")
    if not pattern or not modules:
        return None
    events = modules[min(modules)]
    rx = re.compile(pattern)
    hit = [e for e in events if rx.search(e[0])]
    if not events or not hit:
        return None
    return 100.0 * busy_s(hit) / busy_s(events)
