#!/usr/bin/env python3
"""Find the knee of a serve mix: offer it at each of a few Poisson rates,
one process, one set-up, so that every rate shares the compilation.

    python3 benchmark/sweep.py --workload gpt2xl_chat --rates 0.55,0.7,0.85 --seconds 60

For each rate: the driver's own plan()/measure() with the mix's arrival
rate replaced, a fresh seed, then the loop is cut idle. Printed per rate:
offered and completed requests/s over the window, tokens/s, queue depth at
the window's start and end, TTFT p50/p95 from the due time. The knee is
the highest rate at which completed/s stays within 3% of offered and the
queue does not grow through the window; it goes into the mix's file by
hand, as a number, with the table into PERF.md. This is a tool for the PR
that defines a cell; the driver never runs it.
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
from benchmark.lib.stats import percentile, ttft_ms  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=100)
    args = ap.parse_args(argv)
    bench = runner.load_json(ROOT, "BENCHMARK.json")
    cell = runner.Cell.from_manifest(bench, args.workload, args.seed,
                                     args.seconds, 0)
    rc = runner.start_jax(cell.chips)
    if rc is not None:
        return rc
    drv = runner.load_module("drivers", cell.config["driver"])
    net, loop = drv.build_server(cell.config, cell.seed)
    cap = int(cell.config["serve"]["max_seq_len"])
    loop.start()
    try:
        drv.warm_up(loop, drv.mix_buckets(cell.traffic, cap - 1),
                    int(cell.config["vocab_size"]), cap)
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix = copy.deepcopy(cell.traffic)
            mix["arrival"] = {"kind": "poisson", "rate": rate}
            schedule = drv.plan(cell.config, mix, args.seed + k, args.seconds)
            m = drv.measure(loop, schedule, mix, args.seconds)
            rows, w = m["rows"], m["window_s"]
            d = {c: m["close"]["counters"][c] - m["open"]["counters"][c]
                 for c in drv.COUNTERS}
            ttft = [ttft_ms(r["t_due"], r["t_first"]) for r in rows
                    if r["t_first"] is not None]
            queue = [s["queue_depth"] for s in m["samples"]]
            print(json.dumps({
                "rate": rate, "offered_rps": len(rows) / w,
                "completed_rps": d["serve.requests_completed"] / w,
                "tokens_per_s": d["serve.tokens_generated"] / w,
                "steps": m["close"]["steps"] - m["open"]["steps"],
                "queue_first_tenth": sum(queue[:len(queue) // 10 + 1])
                / (len(queue) // 10 + 1),
                "queue_last_tenth": sum(queue[-(len(queue) // 10 + 1):])
                / (len(queue) // 10 + 1),
                "queue_max": max(queue),
                "finished_of_due": [sum(r["finished"] for r in rows),
                                    len(rows)],
                "ttft_p50_ms": percentile(ttft, 50),
                "ttft_p95_ms": percentile(ttft, 95),
                "preempted": d["serve.preempted"],
            }), flush=True)
    finally:
        loop.stop(timeout=120)
    return 0


if __name__ == "__main__":
    sys.exit(main())
