#!/usr/bin/env python3
"""A trace by the work's own name: the decode step's device time under the
program's scopes and the prefill programs by their bucket, from a trace
directory that holds `program_map.json` beside the xplane file (a
`--trace 1` run of the benchmark writes it; so does an operator's
`paddle.profiler.xplane_trace(dir)`), through the functions the readers
use (benchmark/lib/scope_reduce.py; benchmark/README_scopes.md).

    python3 benchmark/inspect_scopes.py benchmark/.trace/<workload> [stems]

`stems`: how many instruction stems to name under each word (default 5).
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import scope_reduce as sr  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402


def main(trace_dir, stems=5):
    from paddle_tpu.core.program_map import FILE_NAME, scope_of
    with open(os.path.join(trace_dir, FILE_NAME)) as f:
        programs = json.load(f)["programs"]
    for label, program in programs.items():
        under = sum(scope_of(p) is not None for p in program["ops"].values())
        print(f"{label}: {program['module']}, {len(program['ops'])} "
              f"instructions with a path, {under} of them under a word")
    path = tr.find_xplane(trace_dir)
    lines = tr.device_lines(path, (tr.OPS_LINE, tr.MODULES_LINE))
    ops, modules = lines[tr.OPS_LINE], lines[tr.MODULES_LINE]
    if not ops or not modules:
        print("scope: no device plane in this trace (a CPU run)")
        print("prefill: no device plane in this trace (a CPU run)")
        return
    ops, modules = ops[min(ops)], modules[min(modules)]
    decode = programs.get(sr.DECODE_LABEL)
    table, module_ms = sr.decode_table(ops, modules, decode, scope_of) \
        if decode else ({}, None)
    print(sr.scope_line(table, module_ms, stems) if table
          else "scope: no decode step in this trace")
    buckets = sr.pair_prefills(sr.prefill_spans(path), modules, "prefill")
    print(sr.prefill_line(buckets) if buckets
          else "prefill: no prefill program in this trace")


if __name__ == "__main__":
    main(sys.argv[1], *(int(a) for a in sys.argv[2:3]))
