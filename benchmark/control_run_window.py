#!/usr/bin/env python3
"""The second run that a window-cache cell's limits must REFUSE
(benchmark/README_window.md): the cell as run.py runs it, but with every
key and value rounded one precision down on its way into the cache, the
rings of the sliding layers and the pages of the full ones alike (the
model's `kv_round_to`, set here through `model.config_kwargs`, never by a
cell), the weights as they are. benchmark/control_run.py is the first,
the weights one precision down. Prints run.py's result line; exits 0 when
the run came out not correct (the limits caught it), 1 when it passed. A
tool for the PR that sets or re-sets the limits; the driver never runs it.

    python3 benchmark/control_run_window.py --workload \\
        laguna_agent_mixed_sat --seed 5 --seconds 20 --cache-dtype \\
        float8_e4m3fn
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import run as runner  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--cache-dtype", default="float8_e4m3fn")
    args = ap.parse_args(argv)
    bench = runner.load_json(runner.ROOT, "BENCHMARK.json")
    cell = runner.Cell.from_manifest(bench, args.workload, args.seed,
                                     args.seconds, 0)
    cell.config["model"]["config_kwargs"]["kv_round_to"] = args.cache_dtype
    rc = runner.start_jax(cell.chips)
    if rc is not None:
        return rc
    obs = runner.load_module("drivers", cell.config["driver"]).run(cell)
    for why in obs["why_incorrect"]:
        print(f"INCORRECT: {why}", flush=True)
    print(json.dumps({"control": {"kv_round_to": args.cache_dtype},
                      "correct": bool(obs["correct"]),
                      "compared": obs["compared"]}), flush=True)
    return 1 if obs["correct"] else 0


if __name__ == "__main__":
    sys.exit(main())
