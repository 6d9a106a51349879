#!/usr/bin/env python3
"""The third run that a sink cell's limits must REFUSE
(benchmark/README_sink.md): the cell as run.py runs it, its weights and
its cache as they are, but with every sliding layer's sink logits at
-1e30 in the SERVED net (seated after the driver's `load_weights`, as
`control_run.py`'s rounding is: the program has no switch for it): the
kernel's first block then wipes the sink's share of the denominator out,
and what is served is the softmax of a window without its sink, which is
another model and not a rounding of this one. The reference keeps the
sinks. benchmark/control_run.py (the weights one precision down) and
benchmark/control_run_window.py (keys and values one precision down on
their way into rings and pages) are the first two, and take this cell by
name as they are. Prints run.py's result line; exits 0 when the run came
out not correct (the limits caught it), 1 when it passed. A tool for the
PR that sets or re-sets the limits; the driver never runs it.

    python3 benchmark/control_run_sink.py --workload \\
        mimo_v2_flash_reason_sat --seed 5 --seconds 51
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import run as runner  # noqa: E402
from benchmark.drivers import serve_open_loop_ref as ref_driver  # noqa: E402

NO_SINK = -1e30     # exp(NO_SINK - any score) is 0.0 in float32


def drop_sinks(net):
    """Every `attn.sinks` leaf of `net` at NO_SINK -> how many."""
    import jax.numpy as jnp
    leaves = [p for name, p in net.named_parameters()
              if name.endswith("attn.sinks")]
    for p in leaves:
        p._value = jnp.full_like(p._value, NO_SINK)
    return len(leaves)


def without_sinks(load_weights):
    """`load_weights` followed by `drop_sinks`; a net with no sink leaf
    is no cell for this control."""
    def load(net, config, seed):
        load_weights(net, config, seed)
        if not drop_sinks(net):
            raise SystemExit("control_run_sink: the net has no sinks")
    return load


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    bench = runner.load_json(runner.ROOT, "BENCHMARK.json")
    cell = runner.Cell.from_manifest(bench, args.workload, args.seed,
                                     args.seconds, 0)
    ref_driver.load_weights = without_sinks(ref_driver.load_weights)
    rc = runner.start_jax(cell.chips)
    if rc is not None:
        return rc
    obs = runner.load_module("drivers", cell.config["driver"]).run(cell)
    for why in obs["why_incorrect"]:
        print(f"INCORRECT: {why}", flush=True)
    print(json.dumps({"control": {"sinks": NO_SINK},
                      "correct": bool(obs["correct"]),
                      "compared": obs["compared"]}), flush=True)
    return 1 if obs["correct"] else 0


if __name__ == "__main__":
    sys.exit(main())
