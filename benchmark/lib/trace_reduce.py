"""From the profiler's trace to numbers: device busy and idle time, the
operations that took most device time, the longest idle gaps, a kernel's
share, exposed collective time.

Two steps, so that the arithmetic can be checked without a profiler:
`device_lines(path)` reads an `.xplane.pb` with `jax.profiler.ProfileData`
into plain lists, one per device plane and line, of `[name, start_ns,
dur_ns]` (the "XLA Ops" line has one event per executed HLO operation);
every function below works on those lists. benchmark/tests/data/ keeps a small
recorded one.

What a TPU v5e trace looks like (looked at by hand, PR 23; see PERF.md):
planes "/device:TPU:<n>" with lines "Steps", "XLA Modules" (one event per
executed program), "XLA Ops" (one per executed HLO operation, in order on
the core) and "Async XLA Ops" (copy-start..copy-done and the like, which
overlap the former); host threads sit in "/host:CPU". An event of "XLA
Ops" is named by its whole HLO text, `%decode_step.48 = bf16[..]{..}
custom-call(...), custom_call_target="tpu_custom_call", ...`; `short_name`
cuts that to "custom-call[tpu_custom_call] decode_step.48": opcode, the
target of a custom call (Pallas kernels are `tpu_custom_call`, named after
the jitted function they sit in, not after the kernel), instruction name.
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# operations that move data between chips, by opcode (the -start/-done
# halves of the asynchronous forms included)
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")
_HLO = re.compile(r"^%(?P<name>\S+) = .*? (?P<opcode>[a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(hlo_text):
    """"<opcode>[<custom-call target>] <instruction name>" from the HLO
    text the profiler names an operation by; other names pass through."""
    m = _HLO.match(hlo_text)
    if not m:
        return hlo_text
    opcode = m.group("opcode")
    if opcode == "custom-call":
        t = _TARGET.search(hlo_text)
        opcode += f"[{t.group(1)}]" if t else ""
    return f"{opcode} {m.group('name')}"


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def device_lines(path, line_names=(OPS_LINE,)):
    """{line name: {device index: [[name, start_ns, dur_ns], ...]}} for the
    named lines of every device plane, events sorted by start. One pass
    over the file (tens of MB)."""
    from jax.profiler import ProfileData
    out = {name: {} for name in line_names}
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name in out:
                events = [[short_name(e.name), float(e.start_ns),
                           float(e.duration_ns)] for e in line.events]
                events.sort(key=lambda e: e[1])
                out[line.name][int(m.group(1))] = events
    return out


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_s(events, t0_ns=None, t1_ns=None):
    """Seconds in which some operation ran: the union of the events'
    intervals, clipped to [t0, t1] when given."""
    total = 0.0
    for s, e in _union((s, s + d) for _, s, d in events):
        if t0_ns is not None:
            s = max(s, t0_ns)
        if t1_ns is not None:
            e = min(e, t1_ns)
        total += max(0.0, e - s)
    return total * 1e-9


def span_s(events):
    """First start to last end, seconds."""
    if not events:
        return 0.0
    return (max(s + d for _, s, d in events)
            - min(s for _, s, _d in events)) * 1e-9


def op_stem(name):
    """"fusion fusion.123" -> "fusion fusion": 48 unrolled layers give
    every operation its own number, and a top-ten of unique names says
    nothing."""
    return re.sub(r"[.\d]+$", "", name) or name


def top_ops(events, n=10, key=op_stem):
    """[[name, seconds], ...] for the n groups with most device time.
    Nested events (a while loop and its body) are both counted under their
    own names, so the column does not sum to busy time."""
    totals = {}
    for name, _s, d in events:
        k = key(name)
        totals[k] = totals.get(k, 0.0) + d
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in ranked]


def idle_gaps(events, n=10):
    """The n longest intervals with no operation on the device, as
    [[label, seconds], ...], each named by the operations on either side
    of it. The gaps between programs, which the host causes, are named by
    the program's own spans in host_spans.py; run.py falls back on this
    where a trace has no program line."""
    merged = _union((s, s + d) for _, s, d in events)
    ends_at = {s + d: name for name, s, d in events}
    starts_at = {s: name for name, s, _d in events}
    gaps = sorted(((s1 - e0, e0, s1) for (_, e0), (s1, _)
                   in zip(merged, merged[1:])), reverse=True)
    return [[f"host span: not available (after {op_stem(ends_at[e0])}, "
             f"before {op_stem(starts_at[s1])})", length * 1e-9]
            for length, e0, s1 in gaps[:n]]


def exposed_collective_s(events):
    """Seconds in which a collective ran on this device and no other
    operation did: collective time the step could not hide. None when the
    trace has no collective."""
    coll = _union((s, s + d) for name, s, d in events
                  if COLLECTIVE.match(name))
    if not coll:
        return None
    other = _union((s, s + d) for name, s, d in events
                   if not COLLECTIVE.match(name))
    exposed, j = 0.0, 0
    for s, e in coll:
        cur = s
        while j < len(other) and other[j][1] <= cur:
            j += 1
        k = j
        while k < len(other) and other[k][0] < e:
            exposed += max(0.0, other[k][0] - cur)
            cur = max(cur, other[k][1])
            k += 1
        exposed += max(0.0, e - cur)
    return exposed * 1e-9
