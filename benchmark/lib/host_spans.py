"""The program's own spans, read back from the profiler's trace and laid
against the device's idle time.

`paddle_tpu/core/trace.py` opens a `jax.profiler.TraceAnnotation` for every
span, so while the profiler runs each span is an event of the xplane's
"/host:CPU" plane, on the line of the thread that opened it, on the same
clock as the device planes, with the span's scalar attributes as event
stats. A program without that bridge (the parent of PR 25) leaves no such
events, and every reader built on this file then reports nothing.

Two steps, as in trace_reduce.py: `host_lines(path, prefixes)` reads an
`.xplane.pb` into plain lists `[name, start_ns, dur_ns, {stat: value}]`;
the arithmetic below works on those lists (and on trace_reduce's
`[name, start_ns, dur_ns]` for device events), so it is tested without a
profiler. benchmark/tests/data/ keeps a small hand-made trace.

Span names are the contract with the program: `serve/tick` is one scheduler
beat and `fit/step` one iteration of `Model.fit`'s loop; inside them the
self time of `serve/settle_wait`, `serve/retire_wait` and `fit/drain` is the
host blocked on the device, every other span's self time is host work.
"""
from __future__ import annotations

import functools
import os
import re
import sys

from benchmark.lib import trace_reduce as tr

HOST_PLANE = "/host:CPU"
PROGRAM_PREFIXES = ("serve/", "fit/", "io/", "hapi/")
NO_SPAN = "no program span"


@functools.lru_cache(maxsize=2)
def host_lines(path, prefixes=PROGRAM_PREFIXES):
    """{"<thread line>#<its position>": [[name, start_ns, dur_ns, {stat:
    value}], ...]} for the events of the host plane whose name starts with
    one of `prefixes` (a tuple), sorted by start (a parent before its
    children). One pass over the file, remembered: several readers ask for
    the same trace."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        # Python threads are all named "python": the line's position in
        # the plane keeps two threads apart
        for k, line in enumerate(plane.lines):
            events = [[e.name, float(e.start_ns), float(e.duration_ns),
                       dict(e.stats)] for e in line.events
                      if e.name.startswith(prefixes)]
            if events:
                events.sort(key=lambda e: (e[1], -e[2]))
                out[f"{line.name}#{k}"] = events
    return out


def this_run_xplane():
    """The xplane file of the traced run this process is. run.py hands the
    readers its observations, not the trace directory, so it is found the
    way run.py names it: benchmark/.trace/<the --workload of sys.argv>.
    None when this process is no such run (tests pass a path)."""
    if "--workload" not in sys.argv[:-1]:
        return None
    workload = sys.argv[sys.argv.index("--workload") + 1]
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return tr.find_xplane(os.path.join(here, ".trace", workload))


def this_run_lines(path=None):
    """`host_lines` of the trace at `path` (default: this run's); {} when
    there is no trace or the program put no span into it."""
    path = path or this_run_xplane()
    return host_lines(path) if path else {}


def covered_ns(intervals, t0, t1):
    """Nanoseconds of [t0, t1) that the union of `intervals` covers."""
    total = 0.0
    for s, e in tr._union((max(s, t0), min(e, t1)) for s, e in intervals
                          if e > t0 and s < t1):
        total += e - s
    return total


def self_ns(events):
    """Self time of each span of ONE thread: its duration minus the part of
    it that its child spans cover. `events` are `[name, start_ns, dur_ns,
    ...]`; a child is a span that starts inside another and is directly
    below it. Returns a list aligned with `events`; the self times of a
    span and of everything beneath it add up to the span's duration."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    out = [float(e[2]) for e in events]
    stack = []                       # indices of the open ancestors
    for i in order:
        start, end = events[i][1], events[i][1] + events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            parent = stack[-1]
            p_end = events[parent][1] + events[parent][2]
            out[parent] -= max(0.0, min(end, p_end) - start)
        stack.append(i)
    return out


def phase_ms(lines, root, must_hold=None):
    """{span name: mean ms of self time per `root` span} over everything
    inside the spans named `root` (the root's own self time under its own
    name): where a beat's or a step's host time goes. Sums to the mean
    duration of the roots. {} when there is no root."""
    totals, roots = {}, 0
    for line in lines.values():
        selfs = self_ns(line)
        for r in (e for e in line if e[0] == root):
            t0, t1 = r[1], r[1] + r[2]
            inside = [(e, s) for e, s in zip(line, selfs)
                      if e[1] >= t0 and e[1] + e[2] <= t1]
            if must_hold and not any(e[0] == must_hold for e, _ in inside):
                continue
            roots += 1
            for e, s in inside:
                totals[e[0]] = totals.get(e[0], 0.0) + s
    return {k: v / roots * 1e-6 for k, v in totals.items()} if roots else {}


def work_ms(phases, waits):
    """The host's own work per root span, ms: a `phase_ms` table without
    the spans named in `waits` (the host blocked on the device). None for
    an empty table."""
    if not phases:
        return None
    return sum(v for k, v in phases.items() if k not in waits)


def print_phases(root, phases):
    """One line of a `phase_ms` table, largest first, for the run's log."""
    if phases:
        print(f"trace: ms per {root} by span (self time): " + ", ".join(
            f"{k} {v:.3f}" for k, v in sorted(phases.items(),
                                              key=lambda kv: -kv[1])),
              flush=True)


def program_gaps(program_events):
    """[(start_ns, end_ns)] of the intervals in which no program ran on the
    device, between the first program's start and the last one's end.
    `program_events` is one device's "XLA Modules" line: idle time between
    programs is what the host can cause; the microsecond gaps between the
    operations inside one program are the compiler's."""
    merged = tr._union((s, s + d) for _, s, d in program_events)
    return [(e0, s1) for (_, e0), (s1, _) in zip(merged, merged[1:])
            if s1 > e0]


def _label_gaps(program_events, host_events):
    """([(label, start_ns, end_ns), ...] in device order, ns of idle time
    under some program span) for the idle intervals between consecutive
    programs: each labelled with the innermost (shortest) program span
    that covers most of it; where none covers half of it, the span that
    covers the largest part; "no program span" where none touches it."""
    spans = sorted((s, s + d, name) for name, s, d, *_ in host_events)
    labelled, named = [], 0.0
    for g0, g1 in program_gaps(program_events):
        # (covers most of the gap, then: shorter span | larger overlap)
        best, best_key = NO_SPAN, None
        for s, e, name in spans:
            if s >= g1:
                break
            overlap = min(e, g1) - max(s, g0)
            if overlap <= 0:
                continue
            most = 2 * overlap >= g1 - g0
            key = (most, s - e if most else overlap)
            if best_key is None or key > best_key:
                best, best_key = name, key
        named += covered_ns(((s, e) for s, e, _ in spans), g0, g1)
        labelled.append((best, g0, g1))
    return labelled, named


def attribute_gaps(program_events, host_events, n=10):
    """Name each idle interval between consecutive programs on a device by
    what the host was doing in it (`_label_gaps`). Returns `([[label,
    seconds], ...], share)`: the `n` longest gaps, and the share in [0, 1]
    of all such idle time that lies under some program span (None when the
    device was never idle between programs)."""
    labelled, named = _label_gaps(program_events, host_events)
    idle = sum(g1 - g0 for _, g0, g1 in labelled)
    if idle <= 0:
        return [], None
    ranked = sorted(([label, (g1 - g0) * 1e-9] for label, g0, g1
                     in labelled), key=lambda x: -x[1])
    return ranked[:n], named / idle


def breakdown_gaps(program_events, host_events, n=10):
    """The result line's `breakdown.idle_gaps`: the `n` longest idle
    intervals between programs as `["<span> after <program> before
    <program>", seconds]`, the program span first so that it survives
    where the label is cut short."""
    # the profiler prints a program as `jit_prefill(<fingerprint>)`
    plain = lambda name: re.sub(r"\(\d+\)$", "", name)  # noqa: E731
    ends = {s + d: plain(name) for name, s, d in program_events}
    starts = {s: plain(name) for name, s, _d in program_events}
    labelled, _ = _label_gaps(program_events, host_events)
    ranked = sorted(labelled, key=lambda g: g[1] - g[2])[:n]
    return [[f"{label} after {ends[g0]} before {starts[g1]}",
             (g1 - g0) * 1e-9] for label, g0, g1 in ranked]


def idle_named_share(obs, path=None, n=10):
    """What the two `*_idle_named_share` readers report: the percentage of
    the first device's between-program idle time that lies under a program
    span, the `n` longest gaps printed with their labels. None without a
    trace, without program spans in it, or without idle time."""
    modules = obs.get("trace_modules")
    if not modules:
        return None
    host = [e for line in this_run_lines(path).values() for e in line]
    if not host:
        return None
    labelled, share = attribute_gaps(modules[min(modules)], host, n)
    if share is None:
        return None
    for label, seconds in labelled:
        print(f"trace: gap {seconds * 1e3:.3f} ms under {label}", flush=True)
    return 100.0 * share
