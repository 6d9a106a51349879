"""The state-holding serving driver at toy size on the CPU (its check
passing; refusing a run whose weights, or whose recurrent state, are one
precision down; its teacher-forced check advancing every state once a
position, where the older driver's advances it twice), the benchmark's
copy of the reference against the program's, the seeded weights, the byte
and operation counts against the built net and the pool, the mix's
arithmetic, and the four readers."""
import inspect
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark import run as runner
from benchmark.lib import accounting
from benchmark.lib import bytes_olmo_hybrid as nbytes
from benchmark.lib import ref_olmo_hybrid as ref
from benchmark.tests import toy

CELL = "olmo_hybrid_docqa_sat"
CONFIG = "olmo_hybrid_7b_l16"


@pytest.fixture(autouse=True)
def _clean():
    accounting.listen()
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    yield
    paddle.set_flags({"FLAGS_pallas_interpret": False})


def olmo_toy(**check):
    cfg = toy.load("configs", CONFIG)
    cfg.update(vocab_size=128, hidden_size=64, intermediate_size=96,
               num_hidden_layers=4, num_attention_heads=2,
               num_key_value_heads=2, layer_types=cfg["layer_types"][:4],
               linear_num_key_heads=2, linear_num_value_heads=2,
               linear_key_head_dim=8, linear_value_head_dim=16,
               max_position_embeddings=256, dtype="float32")
    # std 0.02 at 64 wide leaves every gate at its centre and every L2
    # norm at its eps (tests/test_olmo_hybrid.py): 0.1 spreads them
    cfg["assumed"]["initializer_range"] = 0.1
    cfg["model"]["config_kwargs"]["init_std"] = 0.1
    cfg["serve"] = {"max_active": 4, "kv_blocks": 48, "block_size": 16,
                    "max_seq_len": 128, "temperature": 0.0}
    # float32 end to end: the program agrees with the reference to 1e-5
    cfg["reference_check"] = dict(
        cfg["reference_check"], sample=3, forced_decode_steps=4,
        gap_p99_limit=1e-4, gap_mean_limit=1e-5, forced_p75_limit=2e-4,
        forced_rms_limit=5e-5, **check)
    return cfg


def test_config_file_states_its_cut_consistently():
    cfg = toy.load("configs", CONFIG)
    period = ["linear_attention"] * 3 + ["full_attention"]
    assert cfg["layer_types"] == period * 4
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 16
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert set(cfg["changed"]) == set(cfg["reduced"])
    assert cfg["rope_parameters"] == {"rope_theta": None}
    assert {"norm_placement", "rotary", "initializer_range", "A_log",
            "dt_bias"} <= set(cfg["assumed"])
    assert cfg["state_dtype"] == "float32" and cfg["departures"]
    assert cfg["driver"] == "serve_open_loop_ref_state"
    serve = cfg["serve"]
    assert serve["max_seq_len"] == 4096 + 640 and serve["block_size"] == 128
    assert 512 <= serve["kv_blocks"] <= 640


def test_copy_of_the_reference_is_the_programs():
    from paddle_tpu.text.models.reference import olmo_hybrid as theirs
    for name in ("rms_norm", "l2_norm", "swiglu", "causal_conv",
                 "delta_rule", "linear_attention", "full_attention", "block",
                 "block_weights", "forward"):
        assert inspect.getsource(getattr(ref, name)) == inspect.getsource(
            getattr(theirs, name)), name


def test_weights_are_a_function_of_the_seed_and_the_programs_leaves():
    from benchmark.drivers import serve_open_loop_ref_state as drv
    cfg = olmo_toy()
    big = 2 ** 31 + 12345            # the driver's seeds are large
    a = dict(ref.make_weights(big, cfg))
    b = dict(ref.make_weights(big, cfg, prefix="blocks.1."))
    c = dict(ref.make_weights(big + 1, cfg))
    assert set(b) == {k for k in a if k.startswith("blocks.1.")}
    for k in b:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert not np.array_equal(np.asarray(a["head"]), np.asarray(c["head"]))
    assert abs(float(np.std(np.asarray(a["head"]))) - 0.1) < 1e-2
    assert np.all(np.asarray(a["blocks.0.mixer_norm"]) == 1)
    decay = np.exp(np.asarray(a["blocks.0.mixer.A_log"]))
    assert decay.dtype == np.float32 and np.all((1 <= decay) & (decay <= 16))
    dt = np.log1p(np.exp(np.asarray(a["blocks.0.mixer.dt_bias"])))
    assert np.all((0.000999 <= dt) & (dt <= 0.1001))
    net, loop = drv.build_server(cfg, big)
    params, _ = net.functional_state()
    assert set(params) == set(a)
    for k in a:
        np.testing.assert_array_equal(np.asarray(params[k]),
                                      np.asarray(a[k]))
    ids = np.random.RandomState(0).randint(1, 128, 40)
    got = np.asarray(net(ids[None])._value)[0]
    want = np.asarray(ref.forward(a, ref.ref_config(cfg), ids))
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-4
    # layer by layer and padded, the reference is the same reference
    rows, = ref.reference_logits(big, cfg, [ids], [29], pad_to=16)
    assert rows.shape == (10, 128)
    assert np.abs(rows - want[29:39]).max() / np.abs(want).max() < 1e-5


def run_toy(cfg, rate=30.0):
    cell = toy.cell(CELL, cfg, toy.serve_mix_toy("docqa_sat", rate),
                    seconds=2.0)
    return cell, runner.load_module(
        "drivers", "serve_open_loop_ref_state").run(cell)


def test_driver_toy_is_correct_and_reports_the_cells_metrics(capsys):
    cell, obs = run_toy(olmo_toy())
    assert obs["correct"], obs["why_incorrect"]
    assert obs["failed"] == 0 and obs["attempted"] == len(obs["rows"]) > 0
    assert obs["compiles_in_window"] == 0
    compared = obs["compared"]
    assert set(compared) == {"requests_errored", "outputs_malformed",
                             "compiles_in_window", "ref_gap_p99",
                             "ref_gap_mean", "forced_logits_err_p75",
                             "forced_logits_rms"}
    assert all(value <= limit for value, limit in compared.values())
    out = capsys.readouterr().out
    assert "its knee" in out
    said = re.search(r"positions, (\d+) slots live.*argmax at (\d+) of (\d+)",
                     out)
    assert int(said[1]) == cell.config["serve"]["max_active"]
    assert said[2] == said[3] != "0"
    assert "linear_decode_layer_steps" in obs["samples"][0]
    e2e = runner.read_metrics(cell, obs, "end_to_end", "end_to_end")
    layer = runner.read_metrics(cell, obs, "per_layer", "layer_metrics")
    assert set(e2e) == {"serve_tokens_per_s", "setup_s"}
    # without a trace the device_trace and program_span readers report
    # nothing
    assert set(layer) == {"gen_late_p95_ms", "beat_ms", "kv_used_share",
                          "chat_ttft_p50_ms", "chat_tpot_p50_ms",
                          "compiles_in_window"}


@pytest.mark.parametrize("control", [{"round_experts_to": "float8_e4m3fn"},
                                     {"state_dtype": "bfloat16"}])
def test_driver_toy_refuses_a_run_one_precision_down(control):
    cfg = olmo_toy()
    cfg["control"] = control
    _cell, obs = run_toy(cfg)
    assert not obs["correct"]
    for name in ("forced_logits_err_p75", "forced_logits_rms"):
        assert any(name in why for why in obs["why_incorrect"])
        value, limit = obs["compared"][name]
        assert value > 10 * limit


def served(cfg, seed=7):
    from benchmark.drivers import serve_open_loop_ref_state as drv
    net, loop = drv.build_server(cfg, seed)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, 128, n) for n in (5, 11, 19)]
    outs = loop.serve(prompts, max_new_tokens=7)
    sample = [({"prompt_len": len(p), "index": k}, np.concatenate([p, o]))
              for k, (p, o) in enumerate(zip(prompts, outs))]
    return net, loop, prompts, outs, sample


def test_forced_logits_advance_every_state_once_a_position():
    from benchmark.drivers import serve_open_loop_ref_state as drv
    net, loop, prompts, outs, sample = served(olmo_toy())
    programs = (loop._step_jit._cache_size(),
                loop._prefill_jit._cache_size())
    rows, live, agree, positions = drv.forced_logits(net, loop, sample, 4)
    # the window's programs, not new ones: nothing was traced again
    assert (loop._step_jit._cache_size(),
            loop._prefill_jit._cache_size()) == programs
    assert live == 4 and agree == positions == 12
    assert [r.shape for r in rows] == [(4, 128)] * 3
    # float32: teacher-forced on what was served, the argmax is the next
    # served token, at every step: the state was advanced once
    for row, (p, o) in zip(rows, zip(prompts, outs)):
        np.testing.assert_array_equal(row.argmax(-1), o[1:5])
    assert loop._pool.used_blocks > 0 and loop._arenas is None


def test_the_older_drivers_forced_check_advances_a_state_twice():
    """Why this cell has a driver of its own (README_state.md): over the
    same served requests `serve_open_loop_ref.forced_logits` runs every
    position through two programs that both write the state, and from the
    second step on its logits are those of another sequence."""
    from benchmark.drivers import serve_open_loop_ref as old
    from benchmark.drivers import serve_open_loop_ref_state as drv
    cfg = olmo_toy()
    net, loop, _prompts, _outs, sample = served(cfg)
    once = drv.forced_logits(net, loop, sample, 4)[0]
    net, loop, _prompts, _outs, sample = served(cfg)
    twice = old.forced_logits(net, loop, sample, 4)[0]
    for a, b in zip(once, twice):
        np.testing.assert_allclose(a[0], b[0], atol=1e-5)   # not yet
        assert np.abs(a[1:] - b[1:]).max() > 1e-2 * np.abs(a).max()


def test_control_state_dtype_reaches_the_pool():
    from benchmark.drivers import serve_open_loop_ref_state as drv
    cfg = olmo_toy()
    _net, loop = drv.build_server(cfg, 3)
    assert {str(a.dtype) for a in loop._arenas[0]} == {"float32"}
    cfg["control"] = {"state_dtype": "bfloat16"}
    _net, loop = drv.build_server(cfg, 3)
    assert [str(a.dtype) for a in loop._arenas[0]] == ["bfloat16", "float32"]
    assert str(loop._arenas[3][0].dtype) == "float32"      # the paged layer


def test_byte_counts_against_the_net_and_the_pool():
    c = toy.load("configs", CONFIG)
    H, W = 3840, 11008
    assert nbytes.conv_channels(c) == 2880 + 2880 + 5760 == 11520
    assert nbytes.ffn_params(c) == 3 * H * W == 126_812_160
    assert nbytes.linear_mixer_params(c) == (
        H * 11520 + H * 5760 + 2 * H * 30 + 5760 * H + 4 * 11520
        + 60 + 192)
    assert round(nbytes.layer_params(c, "linear_attention") / 1e6, 2) \
        == 215.57
    assert round(nbytes.layer_params(c, "full_attention") / 1e6, 2) == 185.81
    assert round(nbytes.held_params(c) * 2 / 1e9, 2) == 8.20
    # the program's own leaves add up to the same count
    leaves = sum(int(np.prod(shape)) for _, shape, _ in ref.leaf_shapes(c))
    assert leaves == nbytes.held_params(c)
    assert nbytes.kv_bytes_per_token(c) == 4 * 2 * 30 * 128 * 2 == 61440
    assert nbytes.state_bytes_per_slot(c) \
        == 12 * (30 * 96 * 192 * 4 + 3 * 11520 * 2) == 27_371_520
    serve = c["serve"]
    pool = (serve["kv_blocks"] * serve["block_size"]
            * nbytes.kv_bytes_per_token(c)
            + serve["max_active"] * nbytes.state_bytes_per_slot(c))
    total = nbytes.held_params(c) * 2 + pool
    assert 0.80 < total / 16e9 < 0.88
    # a full decode step: 58 k tokens live, 32 slots
    step = nbytes.decode_step_bytes(c, 58_000, 32)
    assert 12.5e9 < step < 13.0e9
    ops, moved = nbytes.gdn_step_cost(c, 32)
    assert moved == 32 * (2 * 30 * 96 * 192 * 4 + 30 * 384 * 2
                          + 30 * 192 * 4 + 240)
    assert moved / 819e9 > ops / 197e12            # bound by the bytes
    ops, moved = nbytes.gdn_chunk_cost(c, 4096)
    assert ops == 30 * 64 * (6 * 64 * 96 * 192 + 2 * 64 * 64 * 192)
    assert nbytes.gdn_chunk_cost(c, 4033) == (ops, moved)   # whole chunks


def test_byte_counts_against_a_built_toy_net():
    from benchmark.drivers import serve_open_loop_ref_state as drv
    cfg = olmo_toy()
    net, loop = drv.build_server(cfg, 1)
    params, _ = net.functional_state()
    assert sum(int(p.size) for p in params.values()) \
        == nbytes.held_params(cfg)
    spec = net.paged_cache_spec()
    state = sum(x.nbytes for layer, a in zip(spec, loop._arenas)
                for x in a[len(layer.arenas):])
    assert state == loop.stats()["state_bytes"] \
        == 4 * nbytes.state_bytes_per_slot(cfg, itemsize=4)
    paged = sum(x.nbytes for layer, a in zip(spec, loop._arenas)
                for x in a[:len(layer.arenas)])
    assert paged == (48 + 1) * 16 * nbytes.kv_bytes_per_token(cfg, 4)


def test_mix_is_the_issues_table():
    from benchmark.drivers import serve_open_loop_ref_state as drv
    cfg = toy.load("configs", CONFIG)
    mix = toy.load("traffic", "docqa_sat")
    ten, = mix["tenants"]
    assert ten["prompt"] == {"kind": "lognormal", "median": 1536,
                             "sigma": 0.4, "lo": 640, "hi": 4096}
    assert ten["new"] == {"kind": "lognormal", "median": 192, "sigma": 0.5,
                          "lo": 48, "hi": 640}
    assert mix["stratify"] == {"size": 32, "order_seed": 37}
    assert mix["seed_burst"] == {"count": 40, "new_scale": [0.05, 1.0]}
    assert mix["lead_in_s"] == 6.0 and mix["arrival"]["kind"] == "poisson"
    assert mix["headroom"] in (2.0, 3.0)
    assert abs(mix["arrival"]["rate"]
               - mix["headroom"] * mix["knee_rps"]) <= 0.25
    cap = cfg["serve"]["max_seq_len"]
    assert drv.mix_buckets(mix, cap - 1) == [1024, 2048, 4096]
    big = 2 ** 31 + 4321
    a = drv.plan(cfg, mix, big, 51.0)
    b = drv.plan(cfg, mix, big, 51.0)
    assert [(r.t_due, r.new_tokens, r.prompt.tolist()) for r in a[:50]] \
        == [(r.t_due, r.new_tokens, r.prompt.tolist()) for r in b[:50]]
    rest = a[40:]
    lens = np.asarray([r.prompt.size for r in rest])
    news = np.asarray([r.new_tokens for r in rest])
    assert lens.min() == 640 and lens.max() == 4096
    assert news.min() == 48 and news.max() == 640
    assert all(r.prompt.size + r.new_tokens <= cap for r in a)
    assert 1550 < lens.mean() < 1750 and 205 < news.mean() < 230
    share = {b: float(np.mean([drv.bucket_of(n) == b for n in lens]))
             for b in (1024, 2048, 4096)}
    assert 0.12 < share[1024] < 0.20 and 0.55 < share[2048] < 0.67
    assert 0.19 < share[4096] < 0.29
    assert all(1 <= r.prompt.min() and r.prompt.max() < cfg["vocab_size"]
               for r in a[:60])
    # ids from the whole vocabulary, not a slice
    assert max(int(r.prompt.max()) for r in a[:200]) > 0.99 * 100352


def traced_obs():
    cfg = toy.load("configs", CONFIG)
    samples = [dict(steps=0, linear_decode_layer_steps=0,
                    kv_pool_used_blocks=480, active_slots=32),
               dict(steps=100, linear_decode_layer_steps=1200,
                    kv_pool_used_blocks=480, active_slots=32)]
    chunk = "custom-call[tpu_custom_call] _gdn_chunk_call.3"
    step = "custom-call[tpu_custom_call] _gdn_step_call.7"
    ops = [[chunk, 0.0, 2.0e6], ["fusion fusion.1", 2.0e6, 6.0e6],
           [step, 8.0e6, 0.25e6], [step, 9.0e6, 0.25e6],
           ["fusion fusion.2", 10.0e6, 9.5e6]]
    return {"samples": samples, "config": cfg, "block_size": 128,
            "max_active": 32, "device_kind": "TPU v5 lite",
            "kernel_patterns": cfg["kernel_patterns"],
            "module_patterns": cfg["module_patterns"],
            "trace_ops": {0: ops},
            "trace_modules": {0: [["jit_decode_step(1)", 0.0, 20e6],
                                  ["jit_prefill(2)", 20e6, 30e6],
                                  ["jit_decode_step(1)", 50e6, 20e6]]}}


def test_readers_read_the_kernels_from_a_trace(tmp_path, monkeypatch):
    from benchmark.lib import host_spans
    obs = traced_obs()
    read = {name: runner.load_module("layer_metrics", name).read
            for name in ("linear_attn_share", "gdn_chunk_roofline",
                         "gdn_step_roofline", "hybrid_decode_step_mbu")}
    assert read["linear_attn_share"](obs) == pytest.approx(
        100 * 2.5 / 18.0)
    ops, moved = nbytes.gdn_step_cost(obs["config"], 32)
    assert read["gdn_step_roofline"](obs) == pytest.approx(
        100 * (moved / 819e9) / 0.25e-3)
    assert 60 < read["gdn_step_roofline"](obs) < 80
    need = nbytes.decode_step_bytes(obs["config"], 448 * 128, 32)
    assert read["hybrid_decode_step_mbu"](obs) == pytest.approx(
        100 * need / (0.020 * 819e9))
    # the chunk kernel's prompts come from the program's spans
    monkeypatch.setattr(host_spans, "this_run_lines", lambda path=None: {
        "python#0": [["serve/prefill", 0.0, 1e6, {"prompt_len": 4000,
                                                  "bucket": 4096}]]})
    ops, moved = nbytes.gdn_chunk_cost(obs["config"], 4000)
    assert read["gdn_chunk_roofline"](obs) == pytest.approx(
        100 * max(ops / 197e12, moved / 819e9) / 2.0e-3)
    assert read["gdn_chunk_roofline"](obs) < 100


def test_readers_report_nothing_from_a_program_without_the_kernels():
    obs = traced_obs()
    obs["kernel_patterns"] = {"paged_attn": "_paged_call_once"}
    obs["samples"] = [{"steps": 0, "active_slots": 1,
                       "kv_pool_used_blocks": 2}] * 2
    for name in ("linear_attn_share", "gdn_chunk_roofline",
                 "gdn_step_roofline", "hybrid_decode_step_mbu"):
        assert runner.load_module("layer_metrics", name).read(obs) is None
    assert runner.load_module("layer_metrics", "gdn_chunk_roofline").read(
        traced_obs()) is None           # no span in no trace of this run
