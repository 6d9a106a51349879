"""lib/scope_reduce.py and the seven readers PR 41 added: the arithmetic on
hand-made lists (a nested `while`, a prefill cut by the trace's edge, two
fingerprints of one bucket), the readers' contract with BENCHMARK.json and
with a parent that keeps no map, and both tables from a live CPU trace of
the toy serve loop (no device plane on the CPU: the device's events are
made up from the program's own instruction names)."""
import json
import os

import numpy as np
import pytest

from benchmark import run as runner
from benchmark.lib import profiler
from benchmark.lib import scope_reduce as sr
from benchmark.lib.trace_reduce import find_xplane
from benchmark.tests import toy
from benchmark.tests.toy import ROOT

NEW = ("decode_scoped_share", "decode_attn_ms", "decode_ffn_ms",
       "decode_experts_ms", "decode_linear_attn_ms", "prefill_pad_share",
       "prefill_us_per_token")

# one device, ns: a prefill, then two decode steps; the second step's
# `while` holds two events of its body, and a copy runs between programs
MODULES = [["jit_prefill(77)", 0, 900],
           ["jit_decode_step(42)", 1000, 1000],
           ["jit_decode_step(42)", 2100, 1000]]
OPS = [["fusion fusion.9", 10, 800],                       # the prefill's
       ["fusion fusion.1", 1000, 300],
       ["custom-call[tpu_custom_call] _latent_paged_call_once.2", 1300, 200],
       ["while while.3", 1500, 400],
       ["fusion fusion.7", 1510, 100],                     # in the body
       ["fusion multiply_convert_fusion.8", 1620, 250],    # in the body
       ["async-done slice-done.4", 1900, 100],
       ["copy copy.5", 2020, 50],                          # between programs
       ["fusion fusion.1", 2100, 340],
       ["custom-call[tpu_custom_call] _latent_paged_call_once.2", 2440, 160],
       ["while while.3", 2600, 380],
       ["fusion fusion.7", 2610, 100],
       ["async-done slice-done.4", 2980, 120]]
WORDS = {"fusion.1": "attn", "_latent_paged_call_once.2": "attn",
         "while.3": "experts", "fusion.7": "experts",
         "multiply_convert_fusion.8": "experts", "slice-done.4": None}


def test_program_ops_keeps_top_level_events_of_the_matching_programs():
    events, n = sr.program_ops(OPS, MODULES, r"^jit_decode_step")
    assert n == 2
    names = [e[0].split(" ", 1)[1] for e in events]
    # the body's events are left to their `while`; the prefill's fusion
    # and the copy between programs are no part of a decode step
    assert names == ["fusion.1", "_latent_paged_call_once.2", "while.3",
                     "slice-done.4"] * 2
    assert sr.program_ops(OPS, MODULES, "^jit_step") == ([], 0)
    prefill, one = sr.program_ops(OPS, MODULES, "prefill")
    assert one == 1 and [e[0] for e in prefill] == ["fusion fusion.9"]


def test_ms_by_scope_adds_up_to_the_step():
    events, n = sr.program_ops(OPS, MODULES, r"^jit_decode_step")
    table = sr.ms_by_scope(events, n, WORDS)
    assert table["words"] == pytest.approx(
        {"attn": (300 + 200 + 340 + 160) / 2 * 1e-6,
         "experts": (400 + 380) / 2 * 1e-6,
         sr.UNSCOPED: (100 + 120) / 2 * 1e-6})
    assert table["total_ms"] == pytest.approx(sum(table["words"].values()))
    assert table["total_ms"] == pytest.approx(1000e-6)   # a step is full
    assert table["stems"]["attn"] == pytest.approx(
        {"fusion": 320e-6, "_latent_paged_call_once": 180e-6})
    assert set(table["stems"][sr.UNSCOPED]) == {"slice-done"}
    assert sr.scoped_share(table) == pytest.approx(89.0)
    # an instruction the map does not hold is unscoped too
    bare = sr.ms_by_scope(events, n, {})
    assert bare["words"] == pytest.approx({sr.UNSCOPED: 1000e-6})
    assert sr.scoped_share(bare) == 0.0
    assert sr.ms_by_scope([], 0, WORDS) == {} \
        and sr.scoped_share({}) is None
    line = sr.scope_line(table, 1.0)
    assert line.startswith("scope: decode step 0.00 ms (the program's own "
                           "events: 1.00 ms) = attn ")
    assert line.index("attn") < line.index("experts") < line.index(
        sr.UNSCOPED)


def span(start, bucket, prompt_len):
    return ["serve/prefill", start, 50, {"bucket": bucket,
                                         "prompt_len": prompt_len}]


def test_pair_prefills_by_bucket():
    # the trace opens while a prefill of 512 runs (dispatched before it:
    # no span) and closes after the last span's program began elsewhere
    modules = [["jit_prefill(5)", 0, 400],             # cut by the edge
               ["jit_decode_step(42)", 450, 100],
               ["jit_prefill(1)", 1010, 1000],
               ["jit_prefill(5)", 2110, 420],
               ["jit_prefill(9)", 3010, 1100],         # bucket 1024 again:
               ["jit_prefill(1)", 4210, 900]]          # another fingerprint
    spans = [span(1000, 1024, 900), span(2100, 512, 300),
             span(3000, 1024, 700), span(4200, 1024, 800),
             span(5300, 2048, 1500)]                   # its program: later
    table = sr.pair_prefills(spans, modules, "prefill")
    assert list(table) == [512, 1024]
    assert table[512] == {"calls": 2, "ms": pytest.approx(410e-6),
                          "tokens": 300.0}
    assert table[1024] == {"calls": 3, "ms": pytest.approx(1000e-6),
                           "tokens": pytest.approx(800.0)}
    # the calls add up to the trace's prefill programs
    assert sum(r["calls"] for r in table.values()) == 5
    said = sr.prefill_line(table)
    assert said == ("prefill: bucket 512: 2 calls x 0.0 ms, 300 real tokens "
                    "a call; bucket 1024: 3 calls x 0.0 ms, 800 real tokens "
                    "a call")


def test_pair_prefills_when_the_host_runs_ahead_or_nothing_pairs():
    # the host dispatched span 0's prefill behind one that it had
    # dispatched before the trace began and that starts after span 0:
    # first-after-first pairs everything one too early, and the shift
    # under which fingerprints and buckets agree is taken instead
    modules = [["jit_prefill(5)", 1005, 400],          # an earlier dispatch
               ["jit_prefill(1)", 1500, 1000], ["jit_prefill(5)", 2600, 400],
               ["jit_prefill(1)", 3100, 1000], ["jit_prefill(5)", 4200, 400]]
    spans = [span(1000, 1024, 900), span(1100, 512, 300),
             span(2700, 1024, 700), span(3200, 512, 500)]
    table = sr.pair_prefills(spans, modules, "prefill")
    assert {b: r["calls"] for b, r in table.items()} == {512: 3, 1024: 2}
    assert table[512]["tokens"] == 400.0 and table[1024]["tokens"] == 800.0
    # no span (a program that stamps none): every program is there, under
    # no bucket
    table = sr.pair_prefills([], modules, "prefill")
    assert list(table) == [None] and table[None]["calls"] == 5
    assert table[None]["tokens"] is None
    assert "no span paired" in sr.prefill_line(table)
    assert sr.pair_prefills(spans, [], "prefill") == {}


@pytest.mark.parametrize("name", NEW)
def test_reader_contract(name, monkeypatch):
    """Each new reader declares what BENCHMARK.json says of its metric,
    its entry stands after every entry the benchmark had, and it reports
    nothing from a run that is not traced, from a program without the map
    or the count (the parent), or from a trace without a match."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    assert [m["name"] for m in per_layer[-len(NEW):]] == list(NEW)
    entry = next(m for m in per_layer if m["name"] == name)
    reader = runner.load_module("layer_metrics", name)
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    assert entry["moves"] == "serve_tokens_per_s" and entry["workloads"]
    assert reader.read({"rows": [], "attempted": 0, "setup_s": 1.0}) is None
    traced = {"trace_modules": {0: MODULES}, "trace_ops": {0: OPS},
              "module_patterns": {"prefill": "prefill"}, "max_active": 4,
              "samples": [{"steps": 1, "prefill_tokens": 10},
                          {"steps": 9, "prefill_tokens": 90}]}
    monkeypatch.setattr(sr, "program_map", lambda: None)   # the parent
    assert reader.read(dict(traced)) is None
    # this PR's program, untraced: nothing either
    assert reader.read({"samples": [
        {"prefill_tokens": 10, "prefill_rows": 16},
        {"prefill_tokens": 90, "prefill_rows": 128}]}) is None


def test_pad_share_takes_first_to_last_sample_differences(capsys):
    read = runner.load_module("layer_metrics", "prefill_pad_share").read
    obs = {"trace_modules": {0: MODULES}, "samples": [
        {"prefill_tokens": 100, "prefill_rows": 128},
        {"prefill_tokens": 500, "prefill_rows": 700},
        {"prefill_tokens": 960, "prefill_rows": 1408}]}
    assert read(obs) == pytest.approx(100.0 * (1 - 860 / 1280))
    assert "860 prompt tokens in 1280 rows" in capsys.readouterr().out
    assert read(dict(obs, samples=obs["samples"][:1] * 2)) is None


class _Map:
    """A stand-in for `paddle_tpu.core.program_map` over one program."""

    def __init__(self, program, scope_of):
        self.program, self.scope_of = program, scope_of

    def scopes(self, label):
        return self.program if label == sr.DECODE_LABEL else None


def test_decode_readers_share_one_table(monkeypatch, capsys):
    from paddle_tpu.core import program_map
    ops = {k: {"attn": "jit(decode_step)/layer0/attn/dot_general",
               "experts": "jit(decode_step)/layer0/experts/jit(f)/while"
               }.get(v, "jit(decode_step)/iota")
           for k, v in WORDS.items() if k != "slice-done.4"}
    monkeypatch.setattr(sr, "program_map", lambda: _Map(
        {"module": "jit_decode_step", "ops": ops}, program_map.scope_of))
    obs = {"trace_modules": {0: MODULES}, "trace_ops": {0: OPS}}
    read = {n: runner.load_module("layer_metrics", n).read for n in NEW[:5]}
    assert read["decode_attn_ms"](obs) == pytest.approx(500e-6)
    assert read["decode_experts_ms"](obs) == pytest.approx(390e-6)
    assert read["decode_scoped_share"](obs) == pytest.approx(89.0)
    # a word this net does not speak reads 0, and the metric's `workloads`
    # keep it out of such a cell
    assert read["decode_ffn_ms"](obs) == 0.0
    assert read["decode_linear_attn_ms"](obs) == 0.0
    out = capsys.readouterr().out
    assert out.count("scope: decode step") == 1          # printed once
    # an executable from a cache that a tree without scopes filled
    stale = {"trace_modules": {0: MODULES}, "trace_ops": {0: OPS}}
    monkeypatch.setattr(sr, "program_map", lambda: _Map(
        {"module": "jit_decode_step", "ops": {}}, program_map.scope_of))
    assert read["decode_attn_ms"](stale) is None
    assert read["decode_scoped_share"](stale) == 0.0
    assert "no decode_*_ms is read" in capsys.readouterr().out
    # a trace without a decode step
    assert read["decode_scoped_share"]({
        "trace_modules": {0: MODULES[:1]}, "trace_ops": {0: OPS}}) is None


@pytest.fixture
def _interpret(tmp_path):
    """Pallas interpreted, and an empty compile cache: metadata is no part
    of a cache key, so an executable that another tree compiled would come
    back with that tree's scopes (README_scopes.md, the stale cache)."""
    import jax
    import paddle_tpu as paddle
    from jax._src import compilation_cache
    from benchmark.lib import accounting
    accounting.listen()
    shared = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    compilation_cache.reset_cache()
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    yield
    paddle.set_flags({"FLAGS_pallas_interpret": False})
    jax.config.update("jax_compilation_cache_dir", shared)
    compilation_cache.reset_cache()


def test_live_toy_loop_both_tables(tmp_path, _interpret, monkeypatch,
                                   capsys):
    """The toy serve loop under the benchmark's profiler options, on the
    CPU. The pad share over the run's samples equals, to the last digit,
    the same sum over the requests' own prompt lengths (both cover the
    same prefills); the per-bucket table pairs the real `serve/prefill`
    spans with made-up prefill programs; the decode step's table is made
    from the program's own map and events named by its instructions, and
    `inspect_scopes.py` prints both from the directory alone."""
    from paddle_tpu.core import program_map
    from benchmark import inspect_scopes
    drv = runner.load_module("drivers", "serve_open_loop")
    _net, loop = drv.build_server(toy.gpt_toy(), 0)
    loop.serve([np.arange(1, 9)], max_new_tokens=2)      # compile first
    lens = (5, 9, 17, 30, 12, 6)
    trace_dir = str(tmp_path / "gpt2xl_chat")
    samples = [loop.stats()]
    profiler.start(trace_dir)
    loop.start()
    try:
        reqs = [loop.submit(np.arange(1, 1 + n), max_new_tokens=4)
                for n in lens]
        for r in reqs:
            r.result(timeout=120)
    finally:
        loop.stop(timeout=60)
        profiler.stop()
    samples.append(loop.stats())
    path = find_xplane(trace_dir)
    spans = sr.prefill_spans(path)
    assert sorted(int(e[3]["prompt_len"]) for e in spans) == sorted(lens)
    rows = sum(drv.bucket_of(n) for n in lens)
    assert sum(int(e[3]["bucket"]) for e in spans) == rows
    pad = runner.load_module("layer_metrics", "prefill_pad_share").read(
        {"trace_modules": {0: []}, "samples": samples})
    assert pad == 100.0 * (1.0 - sum(lens) / rows)
    # made-up programs: bucket b's program runs b ns, right after its span
    modules = [[f"jit_prefill({int(e[3]['bucket'])})", e[1] + 1.0,
                float(e[3]["bucket"])] for e in spans]
    obs = {"trace_modules": {0: modules}, "samples": samples,
           "module_patterns": {"prefill": "prefill"}}
    us = runner.load_module("layer_metrics", "prefill_us_per_token").read(
        obs, xplane=path)
    assert us == pytest.approx(rows / len(lens) * 1e-3
                               / (sum(lens) / len(lens)))
    table = sr.pair_prefills(spans, modules, "prefill")
    assert {b: r["calls"] for b, r in table.items()} == {
        b: sum(drv.bucket_of(n) == b for n in lens)
        for b in sorted({drv.bucket_of(n) for n in lens})}
    assert all(r["ms"] == pytest.approx(b * 1e-6) for b, r in table.items())
    # the decode step: one made-up event an instruction the map names,
    # 100 ns each, and a prefetch the map does not know
    program = program_map.scopes(sr.DECODE_LABEL)
    assert program["module"] == "jit_decode_step"
    names = sorted(program["ops"])
    ops = [[f"fusion {n}", 10_000.0 + 100.0 * i, 100.0]
           for i, n in enumerate(names)]
    ops.append(["async-done slice-done.1", ops[-1][1] + 100.0, 100.0])
    step = [["jit_decode_step(1)", 10_000.0, 100.0 * len(ops)]]
    monkeypatch.setattr(sr, "this_run_dir", lambda: trace_dir)
    obs = {"trace_modules": {0: step}, "trace_ops": {0: ops}}
    capsys.readouterr()
    read = {n: runner.load_module("layer_metrics", n).read for n in NEW[:3]}
    by_word = {}
    for n in names:
        w = program_map.scope_of(program["ops"][n]) or sr.UNSCOPED
        by_word[w] = by_word.get(w, 0) + 1
    assert read["decode_attn_ms"](obs) == pytest.approx(
        by_word["attn"] * 100e-6)
    assert read["decode_ffn_ms"](obs) == pytest.approx(
        by_word["ffn"] * 100e-6)
    assert read["decode_scoped_share"](obs) == pytest.approx(
        100.0 * (1 - (by_word.get(sr.UNSCOPED, 0) + 1) / len(ops)))
    out = capsys.readouterr().out
    assert "scope: decode step" in out and "program_map.json" in out
    # the map beside the trace is what another process reads
    with open(os.path.join(trace_dir, "program_map.json")) as f:
        said = json.load(f)
    assert said["programs"][sr.DECODE_LABEL] == program
    assert set(said["programs"]) == set(program_map.labels())
    inspect_scopes.main(trace_dir)
    out = capsys.readouterr().out
    assert "serve/decode: jit_decode_step" in out
    assert "prefill: no device plane" in out or "prefill: bucket" in out
