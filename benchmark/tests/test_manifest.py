"""BENCHMARK.json against the contract's limits and against the files it
names: every name and unit is of the allowed characters, every file a
workload names exists, every metric has a reader that declares the same
layer, unit, source and moved metric."""
import json
import os
import re

import pytest

from benchmark import run as runner
from benchmark.tests.toy import BENCH_DIR, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def line_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 2 <= len(BENCH["workloads"]) <= 24
    assert all(line_ok(w) for w in BENCH["command"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


def test_names_units_and_whys():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group, e["name"]))
    assert len(set(names)) == len(names)
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert line_ok(m["layer"])
    for e in BENCH["configs"] + BENCH["workloads"]:
        assert line_ok(e["why"]), e["name"]


def test_files_exist_and_configs_are_used():
    used = {w["config"] for w in BENCH["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert line_ok(c["source"]) and len(c["reduced"]) <= 16
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        cfg = runner.load_json(ROOT, c["file"])
        assert cfg["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(
            BENCH_DIR, "drivers", cfg["driver"] + ".py"))
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(
            BENCH_DIR, "traffic", w["traffic"] + ".json"))


@pytest.mark.parametrize("w", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_reports_what_the_contract_asks(w):
    def of(group):
        return [m for m in BENCH[group]
                if "workloads" not in m or w in m["workloads"]]
    e2e = {m["name"] for m in of("end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert of("per_layer")
    for m in of("per_layer"):   # reported only where the moved metric is
        assert m["moves"] in e2e, (w, m["name"])


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_each_metric_has_a_reader_that_agrees(m):
    kind = "layer_metrics" if "layer" in m else "end_to_end"
    reader = runner.load_module(kind, m["name"])
    assert reader is not None and callable(reader.read)
    assert reader.UNIT == m["unit"] and reader.SOURCE == m["source"]
    if "layer" in m:
        assert reader.LAYER == m["layer"] and reader.MOVES == m["moves"]
    assert reader.read({"rows": [], "attempted": 0, "setup_s": 1.0}) in (
        None, 1.0)   # nothing to read -> nothing reported


@pytest.mark.parametrize("traffic", sorted(
    f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, "traffic"))
    if f.endswith(".json")))
def test_a_saturating_mix_is_rated_above_its_knee(traffic):
    """A mix that declares `headroom` is offered at that many times the
    knee it names (rounded to 0.5/s), open loop at a constant rate, and
    its cell reports `decode_slot_fill`, the number that says when the
    knee has moved (README: Re-rate a saturating mix). A mix that declares
    none makes no such claim and the driver prints no verdict on it."""
    from benchmark.drivers.serve_open_loop import knee_line
    mix = runner.load_json(BENCH_DIR, "traffic", traffic + ".json")
    if "headroom" not in mix:
        assert knee_line(mix, [], 32) is None
        return
    assert mix["arrival"]["kind"] == "poisson"
    assert mix["knee_rps"] > 0 and mix["headroom"] >= 1.25
    assert mix["arrival"]["rate"] >= mix["headroom"] * mix["knee_rps"] - 0.25
    assert mix["arrival"]["rate"] <= mix["headroom"] * mix["knee_rps"] + 0.25
    cells = [w["name"] for w in BENCH["workloads"] if w["traffic"] == traffic]
    fill = next(m for m in BENCH["per_layer"]
                if m["name"] == "decode_slot_fill")
    assert cells and all(c in fill["workloads"] for c in cells)
    samples = [{"steps": 0, "decode_tokens": 0},
               {"steps": 100, "decode_tokens": 3100}]
    assert "above its knee" in knee_line(mix, samples, 32)
    samples[1]["decode_tokens"] = 2800
    assert "below its knee" in knee_line(mix, samples, 32)
