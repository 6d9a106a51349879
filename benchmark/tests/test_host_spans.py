"""Program spans against device idle time (lib/host_spans.py) and the six
readers PR 25 added: the arithmetic on hand-made lists and on the small
trace kept beside this file, the xplane reading on a live CPU trace of the
toy serve loop and the toy fit."""
import json
import math
import os

import numpy as np
import pytest

from benchmark import run as runner
from benchmark.lib import host_spans as hs
from benchmark.lib import profiler
from benchmark.lib.trace_reduce import find_xplane
from benchmark.tests import toy

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# one thread: a beat with three phases (one of them holding a wait), then
# a second beat; times in ns
BEATS = [["serve/tick", 0, 1000, {"beat": 0}],
         ["serve/settle", 100, 400, {}],
         ["serve/settle_wait", 150, 300, {}],
         ["serve/upload", 600, 100, {}],
         ["serve/decode_step", 700, 250, {}],
         ["serve/tick", 1000, 500, {"beat": 1}],
         ["serve/settle", 1000, 200, {}],
         ["serve/settle_wait", 1010, 100, {}]]
# the device: programs with idle time between them at 300..600, 900..1050
# and 1400..1450
PROGRAMS = [["jit_decode_step", 0, 300], ["jit_decode_step", 600, 300],
            ["jit_prefill", 1050, 350], ["jit_decode_step", 1450, 100]]


def test_self_time_is_duration_minus_what_children_cover():
    selfs = hs.self_ns(BEATS)
    assert selfs == [250, 100, 300, 100, 250, 300, 100, 100]
    # a beat's phases add up: the self times under a span sum to it
    assert sum(selfs[:5]) == 1000 and sum(selfs[5:]) == 500
    # order of the input does not matter
    rev = hs.self_ns(BEATS[::-1])
    assert rev == selfs[::-1]
    assert hs.self_ns([]) == []


def test_work_is_the_phase_table_without_the_waits():
    lines = {"python#0": BEATS}
    tick = hs.phase_ms(lines, "serve/tick")
    # beat 0: 1000 - 300 waited; beat 1: 500 - 100
    assert hs.work_ms(tick, ("serve/settle_wait",)) == pytest.approx(
        (700 + 400) / 2 * 1e-6)
    # a wait under a wait is counted once
    assert hs.work_ms(tick, ("serve/settle", "serve/settle_wait")) \
        == pytest.approx((600 + 300) / 2 * 1e-6)
    # only roots that hold the named span: beat 1 dispatched nothing
    assert hs.work_ms(hs.phase_ms(lines, "serve/tick",
                                  must_hold="serve/decode_step"), ()) \
        == pytest.approx(1000e-6)
    assert hs.work_ms(hs.phase_ms(lines, "fit/step"), ()) is None
    assert hs.work_ms(hs.phase_ms({}, "serve/tick"), ()) is None


def test_phase_table_sums_to_the_mean_beat(capsys):
    lines = {"python#0": BEATS}
    phases = hs.phase_ms(lines, "serve/tick")
    assert phases == pytest.approx({
        "serve/tick": (250 + 300) / 2 * 1e-6,
        "serve/settle": (100 + 100) / 2 * 1e-6,
        "serve/settle_wait": (300 + 100) / 2 * 1e-6,
        "serve/upload": 100 / 2 * 1e-6, "serve/decode_step": 250 / 2 * 1e-6})
    assert sum(phases.values()) == pytest.approx(750e-6)
    only = hs.phase_ms(lines, "serve/tick", must_hold="serve/upload")
    assert sum(only.values()) == pytest.approx(1000e-6)
    assert hs.phase_ms(lines, "fit/step") == {}
    hs.print_phases("serve/tick", phases)
    hs.print_phases("fit/step", {})             # nothing to print
    (out,) = capsys.readouterr().out.splitlines()
    assert out.startswith("trace: ms per serve/tick by span (self time): "
                          "serve/tick 0.000")


def test_gaps_between_programs_get_the_innermost_covering_span():
    assert hs.program_gaps(PROGRAMS) == [(300, 600), (900, 1050),
                                         (1400, 1450)]
    labelled, share = hs.attribute_gaps(PROGRAMS, BEATS, 10)
    # 300..600 lies under settle (100..500: 200 of 300, most of it) and
    # under settle_wait (150..450: 150 of 300, half): both cover most, the
    # shorter one wins. 900..1050 is a third under decode_step (50), a
    # third under the next settle (50), nearly all under the two ticks
    # (100 + 50): no span covers most but tick 0, which does (100 of 150).
    # 1400..1450 is under tick 1 only.
    assert [g[0] for g in labelled] == ["serve/settle_wait", "serve/tick",
                                        "serve/tick"]
    assert [g[1] for g in labelled] == pytest.approx([300e-9, 150e-9,
                                                      50e-9])
    assert share == pytest.approx(1.0)
    assert [g[0] for g in hs.attribute_gaps(PROGRAMS, BEATS, 1)[0]] == [
        "serve/settle_wait"]
    # nothing on the host: every gap is unnamed
    labelled, share = hs.attribute_gaps(PROGRAMS, [], 10)
    assert share == 0.0 and {g[0] for g in labelled} == {hs.NO_SPAN}
    # a span that covers less than half of every gap still names the gap
    # it touches most
    labelled, share = hs.attribute_gaps(
        PROGRAMS, [["io/collate", 310, 30, {}]], 10)
    assert labelled[0][0] == "io/collate"
    assert share == pytest.approx(30 / 500)
    # a device that was never idle between programs has no share
    assert hs.attribute_gaps(PROGRAMS[:1], BEATS) == ([], None)
    assert hs.attribute_gaps([], BEATS) == ([], None)
    assert hs.breakdown_gaps(PROGRAMS[:1], BEATS) == []
    # the profiler's `jit_decode_step(<fingerprint>)` is cut to the name
    stamped = [[f"{name}(1344576564943856)", s, d] for name, s, d in PROGRAMS]
    assert hs.breakdown_gaps(stamped, BEATS, 1) == [
        ["serve/settle_wait after jit_decode_step before jit_decode_step",
         pytest.approx(300e-9)]]


FIRST_GAP = {
    "chat_three_beats":
        "no program span after jit_decode_step before jit_decode_step",
    "v5e_gpt2xl_chat_admissions":
        "serve/admit after jit__threefry_seed before "
        "jit_convert_element_type"}


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(DATA, "spans"))))
def test_recorded_beats(name):
    """The small traces in data/spans/: `chat_three_beats`, hand-made after
    the v5e chat cell (three 305 ms beats, an admission in the second), its
    expectations worked out by hand; `v5e_gpt2xl_chat_admissions`, cut from
    a chip trace of PR 25 (two admissions in one beat), its expectations
    what this code gave then, the beats' work checked by hand. The readers'
    arithmetic end to end."""
    with open(os.path.join(DATA, "spans", name + ".json")) as f:
        rec = json.load(f)
    host = [e for line in rec["host"].values() for e in line]
    labelled, share = hs.attribute_gaps(rec["programs"], host,
                                        len(rec["expect"]["gaps_ms"]))
    assert [[label, round(s * 1e3, 3)] for label, s in labelled] == \
        rec["expect"]["gaps_ms"]
    assert 100 * share == pytest.approx(rec["expect"]["named_share"])
    # the result line's breakdown.idle_gaps: the same gaps, the span first
    # (the ledger cuts a label short), then the programs on either side
    gaps = hs.breakdown_gaps(rec["programs"], host, len(labelled))
    assert [[g[0].split(" after ")[0], g[1]] for g in gaps] == labelled
    assert gaps[0][0] == FIRST_GAP[name]
    assert hs.work_ms(hs.phase_ms(rec["host"], "serve/tick"),
                      ("serve/settle_wait", "serve/retire_wait")) \
        == pytest.approx(rec["expect"]["tick_host_ms"])
    for line in rec["host"].values():
        selfs = hs.self_ns(line)
        for tick in (e for e in line if e[0] == "serve/tick"):
            under = [s for e, s in zip(line, selfs)
                     if tick[1] <= e[1] and e[1] + e[2] <= tick[1] + tick[2]]
            assert sum(under) == pytest.approx(tick[2], rel=0.01)


NEW_READERS = ("tick_host_ms", "serve_idle_named_share",
               "train_idle_named_share", "fit_host_ms_per_step",
               "decode_slot_fill", "queue_wait_mean_ms")


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_report_nothing_without_their_inputs(name):
    read = runner.load_module("layer_metrics", name).read
    assert read({"rows": [], "attempted": 0, "setup_s": 1.0}) is None
    # a traced run of a program without the spans and counts (the parent
    # of PR 25): a trace, samples, but nothing of this PR's to read
    old = {"trace_modules": {0: PROGRAMS}, "max_active": 4,
           "samples": [{"steps": 1}, {"steps": 9}]}
    assert read(old) is None
    # not a traced run: nothing, whatever the samples hold
    assert read({"samples": [SAMPLES[0], SAMPLES[-1]],
                 "max_active": 4}) is None


SAMPLES = [{"steps": 10, "decode_tokens": 30, "admitted": 4,
            "queue_wait_s": 1.0},
           {"steps": 15, "decode_tokens": 48, "admitted": 4,
            "queue_wait_s": 1.0},
           {"steps": 20, "decode_tokens": 68, "admitted": 6,
            "queue_wait_s": 1.5}]


def test_counter_readers_take_first_to_last_sample_differences():
    obs = {"trace_modules": {0: PROGRAMS}, "samples": SAMPLES,
           "max_active": 4}
    fill = runner.load_module("layer_metrics", "decode_slot_fill").read
    wait = runner.load_module("layer_metrics", "queue_wait_mean_ms").read
    assert fill(obs) == pytest.approx(100.0 * 38 / (10 * 4))
    assert wait(obs) == pytest.approx(250.0)
    # no beat, no admission in the window: nothing to divide by
    still = dict(obs, samples=[SAMPLES[0], SAMPLES[0]])
    assert fill(still) is None and wait(still) is None


@pytest.fixture
def _interpret():
    import paddle_tpu as paddle
    from benchmark.lib import accounting
    accounting.listen()
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    yield
    paddle.set_flags({"FLAGS_pallas_interpret": False})


def test_live_serve_trace_holds_the_beats(tmp_path, _interpret, capsys):
    """The toy serve loop under the benchmark's profiler options, on the
    CPU: its spans come back from the xplane with their attributes, a
    beat's self times add up, and the span readers find their inputs.
    (No device plane on the CPU: the programs are made up.)"""
    drv = runner.load_module("drivers", "serve_open_loop")
    _net, loop = drv.build_server(toy.gpt_toy(), 0)
    loop.serve([np.arange(1, 9)], max_new_tokens=2)      # compile first
    trace_dir = str(tmp_path / "serve")
    profiler.start(trace_dir)
    loop.start()
    try:
        reqs = [loop.submit(np.arange(1, 6 + i), max_new_tokens=6)
                for i in range(3)]
        for r in reqs:
            r.result(timeout=120)
    finally:
        loop.stop(timeout=60)
        profiler.stop()
    path = find_xplane(trace_dir)
    lines = hs.host_lines(path)
    (line,) = [ev for ev in lines.values()
               if any(e[0] == "serve/tick" for e in ev)]
    ticks = [e for e in line if e[0] == "serve/tick"]
    assert len(ticks) >= 6
    assert set(ticks[0][3]) == {"beat", "active", "queued"}
    assert [t[3]["beat"] for t in ticks] == sorted(t[3]["beat"]
                                                   for t in ticks)
    names = {e[0] for e in line}
    assert {"serve/wait_work", "serve/settle", "serve/settle_wait",
            "serve/admit", "serve/prefill", "serve/grow", "serve/upload",
            "serve/decode_step", "serve/dispatch", "serve/retire_wait",
            "serve/retire"} <= names
    assert {e[3]["kind"] for e in line if e[0] == "serve/settle_wait"} == {
        "prefill", "decode"}
    selfs = hs.self_ns(line)
    for tick in ticks:
        under = [s for e, s in zip(line, selfs)
                 if tick[1] <= e[1] and e[1] + e[2] <= tick[1] + tick[2]]
        assert sum(under) == pytest.approx(tick[2], rel=0.01)
    # the readers, given where the trace is and made-up programs laid
    # over the first two beats
    t0, t1, t2 = ticks[0][1], ticks[1][1], ticks[2][1]
    obs = {"trace_modules": {0: [["jit_prefill", t0, (t1 - t0) / 2],
                                 ["jit_decode_step", t1, (t2 - t1) / 2],
                                 ["jit_decode_step", t2, 1000.0]]}}
    read = runner.load_module("layer_metrics", "tick_host_ms").read
    value = read(obs, xplane=path)
    assert math.isfinite(value) and 0 < value < sum(
        t[2] for t in ticks) * 1e-6
    share = runner.load_module(
        "layer_metrics", "serve_idle_named_share").read(obs, xplane=path)
    assert 90.0 < share <= 100.0   # all but the turn between two beats
    out = capsys.readouterr().out
    gaps = [ln for ln in out.splitlines() if ln.startswith("trace: gap ")]
    assert len(gaps) == 2 and all(" ms under serve/" in g for g in gaps)
    # fit's reader finds no fit/step in a serve trace
    assert runner.load_module("layer_metrics", "fit_host_ms_per_step").read(
        obs, xplane=path) is None


def test_live_fit_trace_holds_the_steps(tmp_path, _interpret):
    """The toy train cell with --trace 1's profiler window, on the CPU:
    fit/step spans come back with their phases and the reader reports the
    host's share of them."""
    cell = toy.cell("bert_base_s128", toy.bert_toy(), toy.pretrain_toy(),
                    seconds=1.0, trace_dir=str(tmp_path / "train"))
    obs = runner.load_module("drivers", "train_fit").run(cell)
    assert obs["correct"], obs["why_incorrect"]
    path = find_xplane(cell.trace_dir)
    lines = hs.host_lines(path)
    (line,) = [ev for ev in lines.values()
               if any(e[0] == "fit/step" for e in ev)]
    steps = [e for e in line if e[0] == "fit/step"]
    assert len(steps) >= 3 and all("step" in e[3] for e in steps)
    def inside(span):
        return [e[0] for e in line if e is not span and e[1] >= span[1]
                and e[1] + e[2] <= span[1] + span[2]]

    stubs = 0
    for step in steps:
        kids = inside(step)
        if "fit/dispatch" in kids:
            assert {"fit/next_batch", "fit/callbacks"} <= set(kids), kids
        else:     # the iteration that found an epoch's loader empty
            assert set(kids) <= {"fit/next_batch"}, kids
            stubs += 1
    assert 0 < stubs < len(steps) / 4      # epochs of four steps
    assert any(e[0] == "fit/drain" for e in line)
    # the loader's producer thread has a line of its own
    assert any(e[0] == "io/produce_batch" for other in lines.values()
               if other is not line for e in other)
    obs["trace_modules"] = {0: [["jit_step", e[1], e[2] / 2]
                                for e in steps]}
    value = runner.load_module(
        "layer_metrics", "fit_host_ms_per_step").read(obs, xplane=path)
    real = [e for e in steps if "fit/dispatch" in inside(e)]
    whole = sum(e[2] for e in real) / len(real) * 1e-6
    assert math.isfinite(value) and 0 < value <= whole
    share = runner.load_module(
        "layer_metrics", "train_idle_named_share").read(obs, xplane=path)
    assert 0 < share <= 100.0
    assert runner.load_module("layer_metrics", "tick_host_ms").read(
        obs, xplane=path) is None


def test_this_run_xplane_is_found_from_the_command_line(tmp_path,
                                                        monkeypatch):
    import sys
    monkeypatch.setattr(sys, "argv", ["run.py", "--seed", "3"])
    assert hs.this_run_xplane() is None and hs.this_run_lines() == {}
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload",
                                      "no_such_cell_ever", "--trace", "1"])
    assert hs.this_run_xplane() is None      # no trace was written there
    here = os.path.dirname(os.path.dirname(os.path.abspath(hs.__file__)))
    made = os.path.join(here, ".trace", "_test_cell", "plugins", "profile",
                        "t0")
    os.makedirs(made)
    try:
        with open(os.path.join(made, "host.xplane.pb"), "wb"):
            pass
        monkeypatch.setattr(sys, "argv", ["run.py", "--workload",
                                          "_test_cell"])
        assert hs.this_run_xplane() == os.path.join(made, "host.xplane.pb")
    finally:
        import shutil
        shutil.rmtree(os.path.join(here, ".trace", "_test_cell"))
