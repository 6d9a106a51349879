"""A device timeline by the work's own name: a decode step's device time
under the program's scopes, and the prefill programs by their bucket.

The profiler names an operation by its instruction (`fusion fusion.123`);
the program says which scope each instruction of a compiled serve program
was made under (`paddle_tpu/core/program_map.py`: `scopes(label)` =
`{"module", "ops": {instruction: op_name path}}`, `scope_of(path)` = the
innermost vocabulary word) and stamps each prefill it dispatches with its
bucket (the `serve/prefill` span). The arithmetic that lays the one over
the other is here, as pure functions over the lists
`trace_reduce.device_lines` and `host_spans.host_lines` return, so that it
is tested on hand-made lists (tests/test_scope_reduce.py):

- `program_ops(ops, modules, pattern)`: the top-level operations of the
  programs a pattern matches, and how many programs there were;
- `ms_by_scope(events, n_programs, words)`: ms a program execution by
  vocabulary word, by instruction stem inside each word;
- `pair_prefills(spans, modules, pattern)`: per bucket, the prefill
  programs' calls, mean device ms and mean real tokens.

Below them the glue the seven readers of PR 41 and `inspect_scopes.py`
share: it asks the program of THIS process for its map (a parent without
`paddle_tpu.core.program_map` has none: every reader then reports
nothing), prints each table once a run and writes `program_map.json`
beside the trace. benchmark/README_scopes.md is the guide.
"""
from __future__ import annotations

import bisect
import os
import re
import time

from benchmark.lib import host_spans
from benchmark.lib import trace_reduce as tr

UNSCOPED = "unscoped"
DECODE_LABEL = "serve/decode"
PREFILL_SPAN = "serve/prefill"
# under this share of a decode step's time under some word the executable
# came from a cache that another tree filled: no per-word metric is read
MIN_SCOPED_SHARE = 50.0


def program_ops(ops, modules, pattern):
    """(the top-level "XLA Ops" events that start inside an "XLA Modules"
    event `pattern` matches, how many such program events there were). An
    operation that lies inside another event of the line (a `while`'s
    body) is left to its parent, whose duration holds it. `ops` and
    `modules` are one device's lines, sorted by start."""
    rx = re.compile(pattern)
    programs = [(s, s + d) for name, s, d in modules if rx.search(name)]
    starts = [e[1] for e in ops]
    out = []
    for p0, p1 in programs:
        top_end = p0
        for name, s, d in ops[bisect.bisect_left(starts, p0):
                              bisect.bisect_left(starts, p1)]:
            if s < top_end:          # inside the event before it
                continue
            out.append([name, s, d])
            top_end = s + d
    return out, len(programs)


def ms_by_scope(events, n_programs, words):
    """{"total_ms": ms a program execution, "words": {word: ms},
    "stems": {word: {instruction stem: ms}}} of `program_ops`' events.
    `words` maps an instruction's name (the second word of an event's
    short name, `fusion.123`) to its vocabulary word; an instruction it
    does not hold, or holds as None, is "unscoped". {} without a
    program."""
    if not n_programs:
        return {}
    by_word, by_stem = {}, {}
    for name, _s, d in events:
        instruction = name.split(" ", 1)[-1]
        word = words.get(instruction) or UNSCOPED
        ms = d * 1e-6 / n_programs
        by_word[word] = by_word.get(word, 0.0) + ms
        stems = by_stem.setdefault(word, {})
        stem = tr.op_stem(instruction)
        stems[stem] = stems.get(stem, 0.0) + ms
    return {"total_ms": sum(by_word.values()), "words": by_word,
            "stems": by_stem}


def scoped_share(table):
    """Percent of a `ms_by_scope` table's time under some word."""
    if not table or not table["total_ms"]:
        return None
    return 100.0 * (1.0 - table["words"].get(UNSCOPED, 0.0)
                    / table["total_ms"])


def pair_prefills(spans, modules, pattern):
    """{bucket: {"calls", "ms", "tokens"}}: the prefill programs of a
    trace by the bucket the scheduler dispatched them for. `spans` are
    the trace's `serve/prefill` spans (`[name, start_ns, dur_ns, {"bucket",
    "prompt_len", ...}]`), `modules` one device's "XLA Modules" line,
    `pattern` what names a prefill program there. The k-th span goes with
    the k-th program that starts after the first span starts (a program
    before it was dispatched before the trace began); should the host run
    further ahead of the device than that, the shift by one or two
    programs is taken under which the programs' fingerprints and the
    buckets agree best. Each fingerprint (`jit_prefill(<number>)`: one
    compiled program) takes the bucket most of its pairs name and ALL of
    its events, paired or cut off by the trace's edge, count as that
    bucket's calls: the calls add up to the trace's prefill programs.
    "ms": mean device time of a call; "tokens": mean `prompt_len` of the
    bucket's paired spans. A fingerprint no span was paired with is
    listed under bucket None."""
    rx = re.compile(pattern)
    programs = [e for e in modules if rx.search(e[0])]
    spans = sorted((e for e in spans if "bucket" in e[3]),
                   key=lambda e: e[1])
    votes, best = {}, 0.0
    first = sum(p[1] < spans[0][1] for p in programs) if spans else 0
    for offset in range(first, first + 3):
        pairs = list(zip(spans, programs[offset:]))
        if not pairs or any(p[1] < s[1] for s, p in pairs):
            continue                 # a program before its own dispatch
        tally = {}                   # fingerprint -> bucket -> prompt_lens
        for s, p in pairs:
            tally.setdefault(p[0], {}).setdefault(
                int(s[3]["bucket"]), []).append(int(s[3]["prompt_len"]))
        agree = sum(max(len(v) for v in by.values())
                    for by in tally.values()) / len(pairs)
        if agree > best:
            best, votes = agree, tally
    out = {}
    for name in dict.fromkeys(p[0] for p in programs):
        by = votes.get(name)
        bucket = max(by, key=lambda b: len(by[b])) if by else None
        row = out.setdefault(bucket, {"calls": 0, "ms": 0.0, "tokens": []})
        mine = [p for p in programs if p[0] == name]
        row["calls"] += len(mine)
        row["ms"] += sum(p[2] for p in mine) * 1e-6
        row["tokens"] += by[bucket] if by else []
    for row in out.values():
        row["ms"] /= row["calls"]
        row["tokens"] = (sum(row["tokens"]) / len(row["tokens"])
                         if row["tokens"] else None)
    return dict(sorted(out.items(), key=lambda kv: (kv[0] is None, kv[0])))


def scope_line(table, module_ms=None, top=3):
    """`scope: decode step 13.43 ms = attn 4.90 (fusion 2.20, ...) | ...`:
    a `ms_by_scope` table as one line of a run's log, words by time, the
    `top` stems of each; `module_ms` (the program event's own mean) is
    said beside the sum it should equal."""
    def stems(word):
        ranked = sorted(table["stems"][word].items(), key=lambda kv: -kv[1])
        return ", ".join(f"{k} {v:.2f}" for k, v in ranked[:top])
    words = sorted(table["words"].items(),
                   key=lambda kv: (kv[0] == UNSCOPED, -kv[1]))
    whole = "" if module_ms is None else \
        f" (the program's own events: {module_ms:.2f} ms)"
    return (f"scope: decode step {table['total_ms']:.2f} ms{whole} = "
            + " | ".join(f"{w} {ms:.2f} ({stems(w)})" for w, ms in words))


def prefill_line(buckets):
    """`prefill: bucket 1024: 11 calls x 50.7 ms, 842 real tokens a call;
    ...` of a `pair_prefills` table."""
    def one(bucket, row):
        real = "no span paired" if row["tokens"] is None else \
            f"{row['tokens']:.0f} real tokens a call"
        return (f"bucket {bucket}: {row['calls']} calls x {row['ms']:.1f} "
                f"ms, {real}")
    return "prefill: " + "; ".join(one(b, r) for b, r in buckets.items())


# -- what the readers share -------------------------------------------------

def program_map():
    """The program's `core/program_map` module, None where the program has
    none (the parent of PR 41)."""
    try:
        from paddle_tpu.core import program_map as pm
    except ImportError:
        return None
    return pm


def this_run_dir():
    """The directory run.py put this run's trace into (the xplane file
    `host_spans.this_run_xplane` finds lies under its `plugins/`). None
    when this process is no such run."""
    path = host_spans.this_run_xplane()
    return path and path.split(os.sep + "plugins" + os.sep)[0]


def decode_table(ops, modules, program, scope_of):
    """(`ms_by_scope` table of the decode step, mean ms of its program
    events) from one device's lines, the program's map of its decode step
    and its `scope_of`. ({}, None) when the trace holds no such program."""
    pattern = "^" + re.escape(program["module"]) + r"(\(|$)"
    events, n = program_ops(ops, modules, pattern)
    if not n:
        return {}, None
    words = {k: scope_of(v) for k, v in program["ops"].items()}
    own = sum(d for name, _s, d in modules if re.search(pattern, name))
    return ms_by_scope(events, n, words), own * 1e-6 / n


def decode_scopes(obs):
    """The decode step's `ms_by_scope` table of this traced run, made once
    and kept in `obs`; None without a trace, without a program that keeps
    a map, or without a decode step in the trace. The first call prints
    the table and writes `program_map.json` beside the trace."""
    if "scope_table" in obs:
        return obs["scope_table"]
    obs["scope_table"] = None
    pm, ops, modules = program_map(), obs.get("trace_ops"), \
        obs.get("trace_modules")
    if pm is None or not ops or not modules:
        return None
    t0 = time.perf_counter()
    program = pm.scopes(DECODE_LABEL)
    if program is None:
        return None
    t_map = time.perf_counter() - t0
    table, module_ms = decode_table(ops[min(ops)], modules[min(modules)],
                                    program, pm.scope_of)
    if not table:
        return None
    print(scope_line(table, module_ms), flush=True)
    share = scoped_share(table)
    if share < MIN_SCOPED_SHARE:
        print(f"scope: {share:.1f} % of the decode step lies under a word: "
              "this executable came from a compile cache that a tree "
              "without scopes filled; no decode_*_ms is read", flush=True)
    where = this_run_dir()
    if where:
        t0 = time.perf_counter()
        path = pm.dump(where)
        print(f"scope: the decode step's map ({len(program['ops'])} "
              f"instructions) took {t_map:.2f} s to read off the "
              f"executable; all {len(pm.labels())} programs' maps written "
              f"to {path} in {time.perf_counter() - t0:.2f} s", flush=True)
    obs["scope_table"] = table
    return table


def decode_ms(obs, *words):
    """ms a decode step under `words` together, None where
    `decode_scopes` has nothing or the step is mostly unscoped."""
    table = decode_scopes(obs)
    if not table or scoped_share(table) < MIN_SCOPED_SHARE:
        return None
    return sum(table["words"].get(w, 0.0) for w in words)


def prefill_spans(xplane=None):
    """The `serve/prefill` spans of a trace (default: this run's)."""
    return [e for line in host_spans.this_run_lines(xplane).values()
            for e in line if e[0] == PREFILL_SPAN]
