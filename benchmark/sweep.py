#!/usr/bin/env python3
"""Find the knee of a serve mix: offer it at each of a few Poisson rates,
one process, one set-up, so that every rate shares the compilation.

    python3 benchmark/sweep.py --workload gpt2xl_chat --rates 1.5,2,2.5,3,4 --seconds 60

For each rate: the driver's own plan()/measure() with the mix's arrival
rate replaced, a fresh seed, then the loop is cut idle. Printed per rate,
one JSON line: offered and completed requests/s over the window, tokens/s,
ms per decode beat, the mean wait for a slot, the share of decode slots
that produced a token, queue depth early and late, TTFT from the due time,
how late the generator ran. The last line reads the knee, the way PERF.md
section 6 (PR 23) says to: capacity in tokens/s is the plateau, the mean of
tokens/s over the two highest rates, which must both have a queue (wait
for a slot in seconds, slots over 90 % full) for the plateau to be one;
the knee is that capacity over the mix's mean tokens a request. Completed
within 3 % of offered alone reads it too low: just under capacity the
window still ends with requests in flight. The knee goes into the mix's
file by hand (`knee_rps`, `arrival.rate`), the table into PERF.md. This is
a tool for the PR that defines or re-rates a cell; the driver never runs it.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import run as runner  # noqa: E402
from benchmark.lib.stats import (lateness_ms, percentile,  # noqa: E402
                                 queue_wait_ms, slot_fill, ttft_ms)


def read_knee(table, tokens_per_request):
    """Capacity and knee from a sweep's rows: the plateau of tokens/s over
    the two highest rates over the mix's mean tokens a request;
    `plateau` is false when either of the two ran without a queue (slots
    under 90 % full), and the sweep then has to go higher."""
    top = sorted(table, key=lambda r: r["rate"])[-2:]
    capacity = sum(r["tokens_per_s"] for r in top) / len(top)
    return {"capacity_tokens_per_s": capacity,
            "tokens_per_request": tokens_per_request,
            "knee_rps": capacity / tokens_per_request,
            "plateau": all((r["slot_fill_pct"] or 0.0) >= 90.0
                           for r in top),
            "plateau_rates": [r["rate"] for r in top]}


def sweep(cell, rates, seconds, seed):
    """Offer the cell's mix at each of `rates`; (rows, knee reading)."""
    drv = runner.load_module("drivers", cell.config["driver"])
    _net, loop = drv.build_server(cell.config, cell.seed)
    cap = int(cell.config["serve"]["max_seq_len"])
    table, wanted = [], []
    loop.start()
    try:
        drv.warm_up(loop, drv.mix_buckets(cell.traffic, cap - 1),
                    int(cell.config["vocab_size"]), cap)
        for k, rate in enumerate(rates):
            mix = copy.deepcopy(cell.traffic)
            mix["arrival"] = {"kind": "poisson", "rate": rate}
            schedule = drv.plan(cell.config, mix, seed + k, seconds)
            burst = int((mix.get("seed_burst") or {}).get("count", 0))
            wanted += [r.new_tokens for r in schedule[burst:]]
            m = drv.measure(loop, schedule, mix, seconds)
            rows, w = m["rows"], m["window_s"]
            d = {c: m["close"]["counters"][c] - m["open"]["counters"][c]
                 for c in drv.COUNTERS}
            ttft = [ttft_ms(r["t_due"], r["t_first"]) for r in rows
                    if r["t_first"] is not None]
            late = [lateness_ms(r["t_due"], r["t_submit"]) for r in rows
                    if r["t_submit"] is not None]
            queue = [s["queue_depth"] for s in m["samples"]]
            tenth = len(queue) // 10 + 1
            steps = m["close"]["steps"] - m["open"]["steps"]
            fill = slot_fill(m["samples"], loop.stats()["max_active"])
            table.append({
                "rate": rate, "offered_rps": len(rows) / w,
                "completed_rps": d["serve.requests_completed"] / w,
                "tokens_per_s": d["serve.tokens_generated"] / w,
                "beat_ms": w / steps * 1e3 if steps else None,
                "queue_wait_mean_ms": queue_wait_ms(m["samples"]),
                "slot_fill_pct": None if fill is None else 100.0 * fill,
                "queue_first_tenth": sum(queue[:tenth]) / tenth,
                "queue_last_tenth": sum(queue[-tenth:]) / tenth,
                "queue_max": max(queue),
                "finished_of_due": [sum(r["finished"] for r in rows),
                                    len(rows)],
                "ttft_p50_ms": percentile(ttft, 50),
                "ttft_p95_ms": percentile(ttft, 95),
                "gen_late_p95_ms": percentile(late, 95),
                "preempted": d["serve.preempted"],
                "compiles_in_window": m["compiles_in_window"],
            })
            print(json.dumps(table[-1]), flush=True)
    finally:
        loop.stop(timeout=120)
    knee = read_knee(table, sum(wanted) / len(wanted))
    print(json.dumps(knee), flush=True)
    return table, knee


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=100)
    args = ap.parse_args(argv)
    bench = runner.load_json(ROOT, "BENCHMARK.json")
    cell = runner.Cell.from_manifest(bench, args.workload, args.seed,
                                     args.seconds, 0)
    rc = runner.start_jax(cell.chips)
    if rc is not None:
        return rc
    sweep(cell, [float(r) for r in args.rates.split(",")], args.seconds,
          args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
