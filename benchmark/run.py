#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process, on the TPU.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

BENCHMARK.json (repo root) names the cell's configuration and traffic mix;
benchmark/configs/<config>.json and benchmark/traffic/<traffic>.json are
data; benchmark/drivers/<driver>.py runs one kind of cell;
benchmark/end_to_end/<metric>.py and benchmark/layer_metrics/<metric>.py
each read one metric from what the driver observed. Nothing here names a
cell, a model or a mix: a new one is new files plus one entry.

The last stdout line is one JSON object: correct, attempted, failed,
metrics, device (with --trace 1, breakdown) and last `compared`: every
number that decided `correct`, `[value, limit]`. With --trace 0 the
metrics are the cell's end-to-end metrics, with --trace 1 its per-layer
metrics. Any platform but "tpu", or fewer chips than the cell asks for,
exits 2 and prints no result; there is no CPU mode (benchmark/tests/ calls
the same functions at toy size).
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """benchmark/<kind>/<name>.py, found by file name. None if absent."""
    if not os.path.exists(os.path.join(HERE, kind, f"{name}.py")):
        return None
    return importlib.import_module(f"benchmark.{kind}.{name}")


class Cell:
    """Everything data says about one cell, and how it is to be run."""

    def __init__(self, bench, name, chips, config, traffic, seed, seconds,
                 trace, trace_dir=None):
        self.bench, self.name, self.chips = bench, name, int(chips)
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.trace = int(seed), float(seconds), \
            bool(trace)
        self.trace_dir = trace_dir or os.path.join(HERE, ".trace", name)
        self.t_process_start = T_PROCESS_START

    @classmethod
    def from_manifest(cls, bench, workload, seed, seconds, trace):
        """The cell `workload` of BENCHMARK.json, its files loaded."""
        entry = next((w for w in bench["workloads"]
                      if w["name"] == workload), None)
        if entry is None:
            raise SystemExit(f"run.py: no workload {workload!r} in "
                             "BENCHMARK.json")
        cfg_entry = next(c for c in bench["configs"]
                         if c["name"] == entry["config"])
        return cls(bench, workload, entry["chips"],
                   load_json(ROOT, cfg_entry["file"]),
                   load_json(HERE, "traffic", f"{entry['traffic']}.json"),
                   seed, seconds, trace)

    def metric_entries(self, group):
        """The entries of BENCHMARK.json[group] that this cell reports."""
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]


def start_jax(chips):
    """Import the program (which places the compile cache), make every
    program cacheable, listen for compiles. Returns an exit code when the
    program is missing or the machine is not `chips` TPU chips, else None:
    the benchmark has no CPU mode."""
    try:
        import paddle_tpu  # noqa: F401
    except ImportError as e:
        print(f"run.py: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 3
    import jax

    from benchmark.lib import accounting

    # every program, the sub-second eager ones of weight initialisation
    # too, is served from the persistent cache after a checkout's first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    accounting.listen()
    devices = jax.devices()
    print(f"jax={jax.__version__} platform={devices[0].platform} "
          f"device_kind={devices[0].device_kind!r} count={len(devices)} "
          f"compile_cache_dir={jax.config.jax_compilation_cache_dir}",
          flush=True)
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"run.py: needs {chips} TPU chip(s); found {len(devices)} x "
              f"{devices[0].platform!r}. No result: the benchmark has no "
              "CPU mode.", file=sys.stderr)
        return 2
    return None


def device_report(chips):
    """Platform, kind, count as JAX reports them; the peak on the fullest
    of the chips this cell used."""
    import jax
    devices = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices[:chips]]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def read_metrics(cell, obs, group, kind):
    """{name: {"value", "unit"}} from one reader file per metric. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for entry in cell.metric_entries(group):
        reader = load_module(kind, entry["name"])
        if reader is None:
            raise SystemExit(f"run.py: no reader benchmark/{kind}/"
                             f"{entry['name']}.py")
        value = reader.read(obs)
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def trace_observations(cell, obs):
    """Reduce the profiler's trace to what readers and `device` need."""
    from benchmark.lib import host_spans
    from benchmark.lib import trace_reduce as tr
    path = tr.find_xplane(cell.trace_dir)
    if path is None:
        raise SystemExit("run.py: --trace 1 and the profiler wrote no "
                         f"xplane file under {cell.trace_dir}")
    lines = tr.device_lines(path, (tr.OPS_LINE, tr.MODULES_LINE))
    ops, modules = lines[tr.OPS_LINE], lines[tr.MODULES_LINE]
    used = sorted(ops)[:cell.chips]
    if not used or not any(ops[d] for d in used):
        raise SystemExit("run.py: the trace holds no device operation")
    for d in used:
        print(f"trace: device {d}: {len(ops[d])} ops, busy "
              f"{tr.busy_s(ops[d]):.4f} s of {tr.span_s(ops[d]):.4f} s",
              flush=True)
    obs["trace_ops"] = {d: ops[d] for d in used}
    obs["trace_modules"] = {d: modules.get(d, []) for d in used}
    for name, s in tr.top_ops(modules.get(used[0], []), 6):
        print(f"trace: program {name}: {s:.4f} s", flush=True)
    obs["trace_busy_s"] = sum(tr.busy_s(ops[d]) for d in used) / len(used)
    obs["trace_window_s"] = max(tr.span_s(ops[d]) for d in used)
    # the idle time between programs, each gap under the program's own
    # span that covers it; between single operations (and without a
    # program line) only the operations on either side are known
    programs = modules.get(used[0], [])
    host = [e for line in host_spans.host_lines(path).values() for e in line]
    return {"device_ops": tr.top_ops(ops[used[0]], 10),
            "idle_gaps": host_spans.breakdown_gaps(programs, host, 10)
            or tr.idle_gaps(ops[used[0]], 10)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    seconds = bench["run_seconds"] if args.seconds is None else args.seconds
    cell = Cell.from_manifest(bench, args.workload, args.seed, seconds,
                              args.trace)
    rc = start_jax(cell.chips)
    if rc is not None:
        return rc
    from benchmark.lib import accounting

    driver = load_module("drivers", cell.config["driver"])
    obs = driver.run(cell)

    device = device_report(cell.chips)
    line = {"correct": bool(obs["correct"]),
            "attempted": int(obs["attempted"]), "failed": int(obs["failed"])}
    if cell.trace:
        line["breakdown"] = trace_observations(cell, obs)
        line["metrics"] = read_metrics(cell, obs, "per_layer",
                                       "layer_metrics")
    else:
        line["metrics"] = read_metrics(cell, obs, "end_to_end", "end_to_end")
    line["device"] = device
    if cell.trace:
        line["device"]["busy_s"] = obs["trace_busy_s"]
        line["device"]["window_s"] = obs["trace_window_s"]
    acct = accounting.snapshot()
    print(f"compile cache: hits={acct['cache_hits']} "
          f"misses={acct['cache_misses']} backend_compiles="
          f"{acct['backend_compiles']} ({acct['backend_compile_s']:.1f} s)",
          flush=True)
    for why in obs.get("why_incorrect", []):
        print(f"INCORRECT: {why}", flush=True)
    # every number `correct` compared, beside its limit: the last lines of
    # stderr and the last key of the result, which is what the driver's
    # record keeps of a run that was not correct
    line["compared"] = obs.get("compared", {})
    for name, (value, limit) in line["compared"].items():
        print(f"compared: {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    faulthandler.enable()
    sys.exit(main())
