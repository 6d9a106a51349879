#!/usr/bin/env python3
"""Look at the program's spans in one profiler trace, beside the device's
programs: every thread line of "/host:CPU" that holds program spans with
the first spans of each, and the idle gaps between programs on device 0
with the span each lies under.

    python3 benchmark/inspect_spans.py benchmark/.trace/<workload> [n]
    python3 benchmark/inspect_spans.py benchmark/.trace/<workload> record <out.json> <first> <count>

`record` cuts `count` consecutive programs of device 0's "XLA Modules"
line, starting at program `first`, and the program spans that overlap
them, into a small JSON file (times from 0), for benchmark/tests/data/spans/ (its
own directory: test_trace_reduce.py takes every .json directly under data/
for a recording of device operations).
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import host_spans as hs  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402


def load(trace_dir):
    path = tr.find_xplane(trace_dir)
    modules = tr.device_lines(path, (tr.MODULES_LINE,))[tr.MODULES_LINE]
    programs = modules[min(modules)] if modules else []
    return path, programs, hs.host_lines(path)


def main(trace_dir, n=12):
    path, programs, lines = load(trace_dir)
    print("xplane:", path, os.path.getsize(path), "bytes")
    for name, events in lines.items():
        print(f"LINE {name!r}: {len(events)} program spans")
        for e in events[:n]:
            print(f"    {e[0]!r} start={e[1]:.0f} dur={e[2]:.0f} {e[3]}")
    host = [e for events in lines.values() for e in events]
    labelled, share = hs.attribute_gaps(programs, host, n)
    print(f"device programs: {len(programs)}, idle between them named: "
          f"{share}")
    for label, seconds in labelled:
        print(f"    gap {seconds * 1e3:.3f} ms under {label}")


def record(trace_dir, out, first, count):
    _path, programs, lines = load(trace_dir)
    programs = programs[first:first + count]
    t0 = programs[0][1]
    t1 = max(s + d for _, s, d in programs)
    cut = {name: [[e[0], e[1] - t0, e[2], e[3]] for e in events
                  if e[1] + e[2] > t0 and e[1] < t1]
           for name, events in lines.items()}
    with open(out, "w") as f:
        json.dump({"what": f"programs {first}..{first + count} of device "
                   f"0's XLA Modules line of {trace_dir}, and the program "
                   "spans of /host:CPU that overlap them; ns from the "
                   "first program's start",
                   "programs": [[n, s - t0, d] for n, s, d in programs],
                   "host": {k: v for k, v in cut.items() if v}}, f,
                  indent=0)


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[2] == "record":
        record(sys.argv[1], sys.argv[3], int(sys.argv[4]), int(sys.argv[5]))
    else:
        main(sys.argv[1], *(int(a) for a in sys.argv[2:3]))
