"""Share of device busy time spent in prefill programs, from the trace's
"XLA Modules" line (one event per executed program; the configuration's
`module_patterns.prefill` says how the prefill programs are named). Every
decoding stream stalls while a prefill runs."""
import re

from benchmark.lib.trace_reduce import busy_s

LAYER, UNIT, SOURCE, MOVES = ("serve scheduler", "%", "device_trace",
                              "tpot_p50_ms")


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
