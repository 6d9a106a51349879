"""Each driver at toy size on the CPU, end to end through run.py's
functions; the dp4 driver on four virtual devices; run.py refuses the CPU."""
import pytest

import paddle_tpu as paddle
from benchmark import run as runner
from benchmark.lib import accounting
from benchmark.tests import toy


@pytest.fixture(autouse=True)
def _clean():
    from paddle_tpu.distributed import mesh as mesh_mod
    accounting.listen()
    mesh_mod.reset_mesh()
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    yield
    paddle.set_flags({"FLAGS_pallas_interpret": False})
    mesh_mod.reset_mesh()


def metrics(cell, obs):
    return (runner.read_metrics(cell, obs, "end_to_end", "end_to_end"),
            runner.read_metrics(cell, obs, "per_layer", "layer_metrics"))


@pytest.mark.parametrize("name,chips", [("bert_base_s128", 1),
                                        ("bert_base_s128_dp4", 4)])
def test_train_fit_toy(name, chips):
    cell = toy.cell(name, toy.bert_toy(), toy.pretrain_toy(chips > 1),
                    chips=chips)
    obs = runner.load_module("drivers", "train_fit").run(cell)
    assert obs["correct"], obs["why_incorrect"]
    assert obs["failed"] == 0 and obs["attempted"] == obs["steps"] > 0
    assert obs["tokens"] == obs["steps"] * 4 * chips * 16
    assert obs["compiles_in_window"] == 0
    assert 1.5 <= obs["window_s"] < 4.0
    # train_mfu needs a peak: an unknown device kind (the CPU) is an error
    with pytest.raises(KeyError):
        runner.load_module("layer_metrics", "train_mfu").read(obs)
    obs["device_kind"] = "TPU v5 lite"
    e2e, layer = metrics(cell, obs)
    assert set(e2e) == {"train_tokens_per_s_chip", "setup_s"}
    assert e2e["train_tokens_per_s_chip"]["value"] == pytest.approx(
        obs["tokens"] / obs["window_s"] / chips)
    # no trace, so no collective_exposed_share: left out, not reported as 0
    assert set(layer) == {"loader_ms_per_step", "train_mfu",
                          "compiles_in_window"}
    assert layer["train_mfu"]["value"] > 0


@pytest.mark.parametrize("name,mix,rate", [("gpt2xl_chat", "chat_sat", 40.0),
                                           ("gpt2xl_doc", "doc_p80", 8.0)])
def test_serve_open_loop_toy(name, mix, rate, capsys):
    cell = toy.cell(name, toy.gpt_toy(), toy.serve_mix_toy(mix, rate),
                    seconds=2.0)
    obs = runner.load_module("drivers", "serve_open_loop").run(cell)
    assert obs["correct"], obs["why_incorrect"]
    # the toy drains 40/s: a mix that declares headroom over its knee is
    # told so; a mix that declares none (doc) gets no such line
    knee = [ln for ln in capsys.readouterr().out.splitlines()
            if "its knee" in ln]
    assert len(knee) == ("headroom" in cell.traffic)
    assert all("below its knee" in ln for ln in knee)
    assert obs["failed"] == 0 and obs["attempted"] == len(obs["rows"]) > 0
    assert obs["compiles_in_window"] == 0
    assert obs["counters"]["serve.tokens_generated"] > 0
    e2e, layer = metrics(cell, obs)
    # without a trace the device_trace readers report nothing
    if name == "gpt2xl_chat":  # above the knee: completed tokens/s judges
        assert set(e2e) == {"serve_tokens_per_s", "setup_s"}
        assert set(layer) == {"gen_late_p95_ms", "beat_ms", "kv_used_share",
                              "chat_ttft_p50_ms", "chat_tpot_p50_ms",
                              "compiles_in_window"}
        assert all(v["value"] > 0 for v in e2e.values())
    else:
        # the doc mix is in no cell yet (PERF.md section 7): its readers
        # are called directly. Below the knee every request comes back.
        assert all(r["finished"] for r in obs["rows"])
        for kind, reader in (("end_to_end", "ttft_p95_ms"),
                             ("end_to_end", "tpot_p50_ms"),
                             ("layer_metrics", "beat_loaded_ms")):
            assert runner.load_module(kind, reader).read(obs) > 0
        assert runner.load_module(
            "layer_metrics", "preempt_per_100req").read(obs) == 0


def test_a_saturated_toy_runs_above_its_knee(capsys):
    """Four slots, outputs of 20-40 tokens, 60 requests/s: more than the
    toy can hold, so every slot decodes on every beat and the driver's
    line does not ask for a re-rate; `correct` does not depend on it."""
    cell = toy.cell("gpt2xl_chat", toy.gpt_toy(),
                    toy.serve_mix_toy("chat_sat", 60.0, new=(20, 40)),
                    seconds=2.0)
    obs = runner.load_module("drivers", "serve_open_loop").run(cell)
    assert obs["correct"], obs["why_incorrect"]
    assert obs["attempted"] > sum(r["finished"] for r in obs["rows"])
    out = capsys.readouterr().out.splitlines()
    knee = [ln for ln in out if "its knee" in ln]
    assert len(knee) == 1 and "above its knee" in knee[0]
    assert "below its knee" not in knee[0] and "re-rate" not in knee[0]
    load = [ln for ln in out if ln.startswith("serve_open_loop: load:")]
    assert len(load) == 1 and f"{obs['steps']} beats in" in load[0]
    assert "nan" not in load[0]


def test_the_load_line_tells_a_stalled_run_from_a_slow_program():
    """The scheduler's count standing still over several samples, or two
    samples seconds apart, is a stall; beats that are merely long are not.
    The driver's line carries both and the generator's worst lateness."""
    from benchmark.lib.stats import longest_still_s
    drv = runner.load_module("drivers", "serve_open_loop")
    sound = [{"t": 0.1 * i, "steps": i} for i in range(50)]
    slow = [{"t": 0.1 * i, "steps": i // 2} for i in range(50)]
    stalled = [{"t": 0.1 * i, "steps": min(i, 10) + max(0, i - 40)}
               for i in range(50)]
    frozen = sound[:10] + [{"t": s["t"] + 3.0, "steps": s["steps"]}
                           for s in sound[10:]]
    assert longest_still_s(sound) == pytest.approx((0.0, 0.1))
    assert longest_still_s(slow) == pytest.approx((0.1, 0.1))
    assert longest_still_s(stalled) == pytest.approx((3.0, 0.1))
    assert longest_still_s(frozen) == pytest.approx((0.0, 3.1))
    assert longest_still_s([{"steps": 1}, {"steps": 2}]) is None
    m = {"samples": stalled, "window_s": 5.0,
         "open": {"steps": 0}, "close": {"steps": 19},
         "rows": [{"t_due": 1.0, "t_submit": 1.001},
                  {"t_due": 2.0, "t_submit": 3.8},
                  {"t_due": 3.0, "t_submit": None}]}
    line = drv.load_line(m)
    assert "19 beats in 5.000 s" in line
    assert "longest without a beat 3.000 s" in line
    assert "generator latest 1800.0 ms with 1 over 100 ms" in line


def test_traced_runs_write_a_trace_after_the_window(tmp_path):
    from benchmark.lib.trace_reduce import find_xplane
    cell = toy.cell("bert_base_s128", toy.bert_toy(), toy.pretrain_toy(),
                    trace_dir=str(tmp_path / "train"))
    obs = runner.load_module("drivers", "train_fit").run(cell)
    assert obs["correct"] and find_xplane(cell.trace_dir)
    assert 1.5 <= obs["window_s"] < 2.5      # the profiler is outside it
    cell = toy.cell("gpt2xl_chat", toy.gpt_toy(),
                    toy.serve_mix_toy("chat_sat", 40.0), seconds=1.0,
                    trace_dir=str(tmp_path / "serve"))
    obs = runner.load_module("drivers", "serve_open_loop").run(cell)
    assert obs["correct"] and find_xplane(cell.trace_dir)
    assert 1.0 <= obs["window_s"] < 1.2


def test_mix_buckets_cover_the_real_mixes():
    drv = runner.load_module("drivers", "serve_open_loop")
    assert drv.mix_buckets(toy.load("traffic", "chat_sat"), 1023) == [
        64, 128, 256]
    assert drv.mix_buckets(toy.load("traffic", "doc_p80"), 1023) == [
        512, 1024]
    assert [drv.bucket_of(n) for n in (1, 8, 9, 300, 512, 513)] == [
        8, 8, 16, 512, 512, 1024]


def test_run_py_refuses_the_cpu(capsys):
    rc = runner.main(["--workload", "bert_base_s128", "--seed", "0",
                      "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 2 and "no CPU mode" in err
    assert not [ln for ln in out.splitlines() if ln.startswith("{")]
