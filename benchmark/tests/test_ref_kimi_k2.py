"""The reference-checked serving driver at toy size on the CPU (its check
passing, and refusing a run served one precision down), the benchmark's
copy of the reference against the program's, the seeded weights, and the
byte function of `decode_step_mbu` against the configuration's own
parameter arithmetic."""
import inspect
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark import run as runner
from benchmark.lib import accounting
from benchmark.lib import bytes_kimi_k2 as nbytes
from benchmark.lib import ref_kimi_k2 as ref
from benchmark.tests import toy

CELL = "kimi_k27_code_gen_sat"


@pytest.fixture(autouse=True)
def _clean():
    accounting.listen()
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    yield
    paddle.set_flags({"FLAGS_pallas_interpret": False})


def kimi_toy(**check):
    cfg = toy.load("configs", "kimi_k27_code_ep32")
    cfg.update(vocab_size=256, hidden_size=64, num_hidden_layers=3,
               num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               intermediate_size=96, moe_intermediate_size=32,
               n_routed_experts=8, max_position_embeddings=256,
               dtype="float32")
    cfg["rope_scaling"] = dict(cfg["rope_scaling"], factor=4,
                               original_max_position_embeddings=32)
    cfg["share"].update(router_width=16, experts_held=[4, 8])
    cfg["model"]["config_kwargs"].update(num_experts=16, experts_held=[4, 8])
    cfg["serve"] = {"max_active": 4, "kv_blocks": 48, "block_size": 16,
                    "max_seq_len": 128, "temperature": 0.0}
    # float32 end to end: the program agrees with the reference to 1e-5
    cfg["reference_check"] = dict(
        cfg["reference_check"], sample=3, forced_decode_steps=4,
        gap_p99_limit=1e-4, gap_mean_limit=1e-5, forced_p75_limit=1e-4,
        forced_rms_limit=1e-5, **check)
    return cfg


def test_config_file_states_its_share_consistently():
    cfg = toy.load("configs", "kimi_k27_code_ep32")
    kwargs, share = cfg["model"]["config_kwargs"], cfg["share"]
    assert kwargs["num_experts"] == share["router_width"] == 384
    assert kwargs["experts_held"] == share["experts_held"] == [0, 12]
    assert cfg["n_routed_experts"] == share["experts_held"][1]
    assert 384 // share["chips_per_layer"] == 12
    assert cfg["vocab_size"] * share["vocabulary_ways"] == 163840
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert set(cfg["changed"]) == set(cfg["reduced"])


def test_copy_of_the_reference_is_the_programs():
    from paddle_tpu.text.models.reference import kimi_k2 as theirs
    for name in ("yarn_mscale", "inv_freq", "softmax_scale", "rope",
                 "rms_norm", "swiglu", "attention", "route", "expert_layer",
                 "block", "block_weights", "forward"):
        assert inspect.getsource(getattr(ref, name)) == inspect.getsource(
            getattr(theirs, name)), name


def test_weights_are_a_function_of_the_seed_and_the_programs_leaves():
    from benchmark.drivers import serve_open_loop_ref as drv
    cfg = kimi_toy()
    big = 2 ** 31 + 12345            # the driver's seeds are large
    a = dict(ref.make_weights(big, cfg))
    b = dict(ref.make_weights(big, cfg, prefix="blocks.1."))
    c = dict(ref.make_weights(big + 1, cfg))
    assert set(b) == {k for k in a if k.startswith("blocks.1.")}
    for k in b:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert not np.array_equal(np.asarray(a["head"]), np.asarray(c["head"]))
    assert abs(float(np.std(np.asarray(a["head"]))) - 0.02) < 2e-3
    assert float(np.std(np.asarray(a["blocks.1.ffn.router_bias"]))) > 0
    assert np.all(np.asarray(a["blocks.0.attn_norm"]) == 1)
    net, loop = drv.build_server(cfg, big)
    params, _ = net.functional_state()
    assert set(params) == set(a)
    for k in a:
        np.testing.assert_array_equal(np.asarray(params[k]),
                                      np.asarray(a[k]))
    ids = np.random.RandomState(0).randint(1, 256, 40)
    got = np.asarray(net(ids[None])._value)[0]
    want = np.asarray(ref.forward(a, *ref.ref_config(cfg)[:1], ids,
                                  ref.ref_config(cfg)[1]))
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-5
    # layer by layer and padded, the reference is the same reference
    rows, = ref.reference_logits(big, cfg, [ids], [29], pad_to=16)
    assert rows.shape == (10, 256)
    assert np.abs(rows - want[29:39]).max() / np.abs(want).max() < 1e-5


def run_toy(cfg, rate=30.0):
    cell = toy.cell(CELL, cfg, toy.serve_mix_toy("codegen_sat", rate),
                    seconds=2.0)
    return cell, runner.load_module("drivers", "serve_open_loop_ref").run(
        cell)


def test_driver_toy_is_correct_and_reports_the_cells_metrics(capsys):
    cell, obs = run_toy(kimi_toy())
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
    # every decode slot was live in the teacher-forced steps, and the
    # loop's own decode step sampled what the compared logits say
    said = re.search(r"positions, (\d+) slots live.*argmax at (\d+) of (\d+)",
                     out)
    assert int(said[1]) == cell.config["serve"]["max_active"]
    assert said[2] == said[3] != "0"
    e2e = runner.read_metrics(cell, obs, "end_to_end", "end_to_end")
    layer = runner.read_metrics(cell, obs, "per_layer", "layer_metrics")
    assert set(e2e) == {"serve_tokens_per_s", "setup_s"}
    # without a trace the device_trace and program_span readers report
    # nothing; the expert counters are read from the window's samples
    assert set(layer) == {"gen_late_p95_ms", "beat_ms", "kv_used_share",
                          "chat_ttft_p50_ms", "chat_tpot_p50_ms",
                          "compiles_in_window", "moe_expert_peak_over_mean"}
    assert 1.0 <= layer["moe_expert_peak_over_mean"]["value"] <= 8.0


def test_driver_toy_refuses_a_run_one_precision_down():
    cfg = kimi_toy()
    cfg["control"] = {"round_experts_to": "float8_e4m3fn"}
    _cell, obs = run_toy(cfg)
    assert not obs["correct"]
    for name in ("forced_logits_err_p75", "forced_logits_rms"):
        assert any(name in why for why in obs["why_incorrect"])
        value, limit = obs["compared"][name]
        assert value > 10 * limit


def test_readers_report_nothing_from_a_program_without_the_counters():
    obs = {"samples": [{"steps": 0}, {"steps": 9}], "trace_modules": {},
           "module_patterns": {"decode": "^jit_decode_step"}}
    for name in ("moe_expert_peak_over_mean", "decode_step_mbu",
                 "prefill_busy_share"):
        assert runner.load_module("layer_metrics", name).read(obs) is None


def test_decode_step_mbu_reads_bytes_over_time_and_peak():
    cfg = toy.load("configs", "kimi_k27_code_ep32")
    samples = [dict(steps=0, moe_decode_layer_steps=0,
                    moe_decode_experts_touched=0, kv_pool_used_blocks=600,
                    active_slots=64),
               dict(steps=100, moe_decode_layer_steps=600,
                    moe_decode_experts_touched=5400,
                    kv_pool_used_blocks=600, active_slots=64)]
    obs = {"samples": samples, "config": cfg, "block_size": 128,
           "max_active": 64, "device_kind": "TPU v5 lite",
           "module_patterns": cfg["module_patterns"],
           "trace_modules": {0: [["jit_decode_step(1)", 0.0, 20e6],
                                 ["jit_prefill(2)", 20e6, 30e6],
                                 ["jit_decode_step(1)", 50e6, 20e6]]}}
    need = nbytes.decode_step_bytes(cfg, 9.0, 536 * 128, 64)
    got = runner.load_module("layer_metrics", "decode_step_mbu").read(obs)
    assert got == pytest.approx(100 * need / (0.020 * 819e9))
    assert 40 < got < 60
    share = runner.load_module("layer_metrics", "prefill_busy_share").read(obs)
    assert share == pytest.approx(100 * 30 / 70)


def test_byte_function_against_the_configurations_parameter_counts():
    c = toy.load("configs", "kimi_k27_code_ep32")
    assert nbytes.attention_params(c) == (
        7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256
        + 8192 * 7168) == 101_122_048
    assert nbytes.expert_params(c) == 3 * 7168 * 2048 == 44_040_192
    assert nbytes.router_params(c) == 7168 * 384
    assert nbytes.dense_layer_params(c) == 101_122_048 + 3 * 7168 * 18432
    assert round(nbytes.dense_layer_params(c) / 1e6, 1) == 497.5
    assert round(nbytes.expert_layer_params(c, 12) / 1e6, 1) == 676.4
    assert round(nbytes.held_params(c) * 2 / 1e9, 2) == 9.70
    assert nbytes.latent_bytes_per_token(c) == 7 * 1152
    # the program's own leaves add up to the same count (norms apart)
    leaves = sum(int(np.prod(shape)) for _, shape, kind
                 in ref.leaf_shapes(c) if kind == "matrix")
    assert leaves == nbytes.held_params(c)
    # a step that touches 9 of 12 experts a layer, 70 k tokens live
    step = nbytes.decode_step_bytes(c, 9, 70_000, 64)
    assert 8.0e9 < step < 8.8e9
    assert step < nbytes.held_params(c) * 2 + 70_000 * 8064


def test_driver_takes_model_and_reference_from_the_configuration():
    from benchmark.drivers import serve_open_loop_ref as drv
    cfg = kimi_toy()
    assert drv.reference_of(cfg) is ref
    cfg["model"]["class"] = "paddle_tpu.text.models.not_there.Net"
    with pytest.raises(SystemExit, match="has no .*not_there.Net to serve"):
        drv.build_server(cfg, 0)


def test_stratified_lengths_keep_the_stated_distribution():
    from benchmark.drivers import serve_open_loop_ref as drv
    from benchmark.lib import stratify
    from benchmark.lib.workload import sample_len, Stream
    cfg = toy.load("configs", "kimi_k27_code_ep32")
    mix = toy.load("traffic", "codegen_sat")
    size, burst = mix["stratify"]["size"], mix["seed_burst"]["count"]
    ten = mix["tenants"][0]
    assert ten["prompt"] == {"kind": "lognormal", "median": 768,
                             "sigma": 0.5, "lo": 256, "hi": 2048}
    assert ten["new"] == {"kind": "lognormal", "median": 320, "sigma": 0.5,
                          "lo": 64, "hi": 1024}
    big = 2 ** 31 + 4321
    plain = dict(mix)
    del plain["stratify"]
    a, b = (drv.plan(cfg, mix, big, 51.0) for _ in range(2))
    c, iid = drv.plan(cfg, mix, big + 1, 51.0), drv.plan(cfg, plain, big, 51.0)
    assert [(r.t_due, r.new_tokens, r.prompt.tolist()) for r in a] \
        == [(r.t_due, r.new_tokens, r.prompt.tolist()) for r in b]
    assert [r.t_due for r in a] == [r.t_due for r in iid]
    # a prefix of a longer horizon's schedule
    longer = drv.plan(cfg, mix, big, 60.0)
    assert [r.new_tokens for r in longer[:len(a)]] \
        == [r.new_tokens for r in a]
    # the same ids a request would have had, to its new length
    k = burst + 5
    n = min(a[k].prompt.size, iid[k].prompt.size)
    np.testing.assert_array_equal(a[k].prompt[:n], iid[k].prompt[:n])
    assert all(1 <= r.prompt.min() and r.prompt.max() < cfg["vocab_size"]
               for r in a[:80])
    # every block of `size` holds one length from each size-th of the
    # stated distribution: against `size` x 4000 independent draws
    draws = Stream(1, "draws")
    for key, of in (("prompt", lambda r: r.prompt.size),
                    ("new", lambda r: r.new_tokens)):
        pop = np.sort([sample_len(ten[key], draws, i, 3071)
                       for i in range(4000)])
        edges = pop[[len(pop) * j // size for j in range(1, size)]]
        for block in (a[burst:burst + size], c[burst + size:burst + 2 * size]):
            got = np.sort([of(r) for r in block])
            inner = got[1:-1]
            assert np.all(inner >= edges[:-1] * 0.93 - 2)
            assert np.all(inner <= edges[1:] * 1.07 + 2)
        # the order of the strata is the mix's, the place inside the seed's
        la, lc = ([of(r) for r in s[burst:burst + 4 * size]] for s in (a, c))
        assert la != lc
        assert np.corrcoef(la, lc)[0, 1] > 0.98
        lo, hi = ten[key]["lo"], ten[key]["hi"]
        every = [of(r) for r in a[burst:]]
        assert min(every) == lo and max(every) == hi     # the clips bind
    # every block fills the three upper prefill buckets (1.4 % of
    # prompts, under one a block, fall to 256), a schedule all four
    assert {drv.bucket_of(r.prompt.size) for r in a[burst:burst + size]} \
        >= {512, 1024, 2048}
    assert {drv.bucket_of(r.prompt.size) for r in a} \
        == {256, 512, 1024, 2048}
    # the burst's outputs are scaled down, staggered over the strata
    scaled = np.mean([r.new_tokens for r in a[:burst]])
    assert 0.4 < scaled / np.mean([r.new_tokens for r in a[burst:]]) < 0.65
    assert stratify.quantile_len({"kind": "uniform", "lo": 2, "hi": 6},
                                 0.999, 100) == 6


def test_forced_logits_run_the_loops_own_programs_at_full_fill():
    from benchmark.drivers import serve_open_loop_ref as drv
    cfg = kimi_toy()
    net, loop = drv.build_server(cfg, 7)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 256, n) for n in (5, 11, 19)]
    outs = loop.serve(prompts, max_new_tokens=7)
    programs = (loop._step_jit._cache_size(),
                loop._prefill_jit._cache_size())
    sample = [({"prompt_len": len(p), "index": k}, np.concatenate([p, o]))
              for k, (p, o) in enumerate(zip(prompts, outs))]
    rows, live, agree, positions = drv.forced_logits(net, loop, sample, 4)
    # the window's programs, not new ones: nothing was traced again
    assert (loop._step_jit._cache_size(),
            loop._prefill_jit._cache_size()) == programs
    assert live == 4 and agree == positions == 12
    assert [r.shape for r in rows] == [(4, 256)] * 3
    # float32: teacher-forced on what was served, the argmax is the next
    # served token
    for row, (p, o) in zip(rows, zip(prompts, outs)):
        np.testing.assert_array_equal(row.argmax(-1), o[1:5])
    assert loop._pool.used_blocks > 0 and loop._arenas is None
