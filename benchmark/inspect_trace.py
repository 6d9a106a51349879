#!/usr/bin/env python3
"""Look at one profiler trace by hand before writing a reader against it:
planes, lines, event counts, the first events of each device line with
their stats, and the operations with most device time.

    python3 benchmark/inspect_trace.py benchmark/.trace/<workload> [n]
    python3 benchmark/inspect_trace.py benchmark/.trace/<workload> record <out.json> <first> <count>

`record` cuts `count` consecutive events of device 0's "XLA Ops" line,
starting at event `first`, into a small JSON file: how the recorded traces
under benchmark/tests/data/ were made.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import trace_reduce as tr  # noqa: E402


def main(trace_dir, n=12):
    from jax.profiler import ProfileData
    path = tr.find_xplane(trace_dir)
    print("xplane:", path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            if not tr.DEVICE_PLANE.match(plane.name):
                continue
            for e in events[:n]:
                stats = [(k, str(v)[:80]) for k, v in e.stats]
                print(f"      {e.name!r} start={e.start_ns:.0f} "
                      f"dur={e.duration_ns:.0f} {stats}")
    for dev, ops in tr.device_lines(path)[tr.OPS_LINE].items():
        print(f"device {dev}: busy {tr.busy_s(ops):.4f} s of "
              f"{tr.span_s(ops):.4f} s")
        for name, s in tr.top_ops(ops, 25, key=lambda x: x):
            print(f"   {s:10.6f} s  {name}")
        for name, s in tr.top_ops(ops, 15):
            print(f"   stem {s:10.6f} s  {name}")
        for name, s in tr.idle_gaps(ops, 5):
            print(f"   gap {s:10.6f} s  {name}")


def record(trace_dir, out, first, count):
    ops = tr.device_lines(tr.find_xplane(trace_dir))[tr.OPS_LINE]
    cut = ops[min(ops)][first:first + count]
    with open(out, "w") as f:
        json.dump({"ops": cut}, f)
    print(f"{len(cut)} events -> {out}")


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[2] == "record":
        record(sys.argv[1], sys.argv[3], int(sys.argv[4]), int(sys.argv[5]))
    else:
        main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 12)
