"""Device microseconds a real prompt token costs in prefill: the mean
device time of the prefill programs' events on the trace's "XLA Modules"
line (`module_patterns.prefill`) over the mean `prompt_len` of the
`serve/prefill` spans in the same trace (mean over mean, as
`gdn_chunk_roofline` does, so that a prefill cut by the trace's edge moves
neither). Padding, bucket choice and the programs' own efficiency are all
in it: it is what a change to prefill is claimed against. Prints the
programs by bucket (benchmark/lib/scope_reduce.pair_prefills). Nothing is
reported without a trace, from a program that does not say its
`prefill_rows` (the parent of PR 41), or without a prefill in the trace."""
import re

from benchmark.lib import scope_reduce

LAYER, UNIT, SOURCE, MOVES = ("serve scheduler", "us", "device_trace",
                              "serve_tokens_per_s")


def read(obs, xplane=None):
    pattern = obs.get("module_patterns", {}).get("prefill")
    modules, samples = obs.get("trace_modules"), obs.get("samples")
    if not pattern or not modules or not samples \
            or "prefill_rows" not in samples[0]:
        return None
    spans = scope_reduce.prefill_spans(xplane)
    rx = re.compile(pattern)
    programs = [d for name, _s, d in modules[min(modules)]
                if rx.search(name)]
    if not spans or not programs:
        return None
    print(scope_reduce.prefill_line(scope_reduce.pair_prefills(
        spans, modules[min(modules)], pattern)), flush=True)
    tokens = sum(int(e[3]["prompt_len"]) for e in spans)
    rows = sum(int(e[3]["bucket"]) for e in spans)
    print(f"prefill: the trace's {len(spans)} serve/prefill spans: {tokens} "
          f"prompt tokens in {rows} rows, {100.0 * (1.0 - tokens / rows):.2f}"
          f" % padding; {len(programs)} prefill programs of mean "
          f"{sum(programs) * 1e-6 / len(programs):.2f} ms", flush=True)
    return sum(programs) * 1e-3 / len(programs) / (tokens / len(spans))
