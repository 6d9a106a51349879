"""The MiMo-V2-Flash share through the reference-checked serving driver at
toy size on the CPU (its check passing, and refusing a run served one
precision down, weights or cache, or with its sinks dropped), the
benchmark's copy of the reference against the program's, the seeded
weights, the configuration file against the catalog's row, the byte
functions against the built net's leaves and the pool's allocation, and
the two readers this cell brings."""
import inspect
import math
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark import control_run_sink
from benchmark import run as runner
from benchmark.drivers import serve_open_loop_ref as ref_driver
from benchmark.lib import accounting
from benchmark.lib import bytes_mimo_v2 as nbytes
from benchmark.lib import ref_mimo_v2 as ref
from benchmark.tests import toy

CELL = "mimo_v2_flash_reason_sat"
CONFIG = "mimo_v2_flash_ep16"
DRIVER = "serve_open_loop_ref_state"
NEW_READERS = ("sink_gqa_attn_roofline", "mimo_decode_step_mbu")
# the catalog's row (model-configs guide, architectures.jsonl), `config`:
# its numbers, flags and nulls; the per-layer lists are held below
PUBLISHED = {
    "attention_value_scale": 0.707, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 16384,
    "max_position_embeddings": 262144, "model_type": "mimo_v2_flash",
    "num_attention_heads": 64, "head_dim": 192, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "layernorm_epsilon": 1e-05,
    "rope_theta": 5000000, "tie_word_embeddings": False,
    "vocab_size": 152576, "partial_rotary_factor": 0.334,
    "sliding_window": 128, "swa_rope_theta": 10000, "attention_bias": False,
    "v_head_dim": 128, "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "sliding_window_size": 128,
    "attention_chunk_size": 128, "moe_intermediate_size": 2048,
    "n_routed_experts": 256, "n_shared_experts": None,
    "num_experts_per_tok": 8, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "routed_scaling_factor": None,
    "swa_num_attention_heads": 64, "swa_num_key_value_heads": 8,
    "swa_head_dim": 192, "swa_v_head_dim": 128}
PATTERN = [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0]


@pytest.fixture(autouse=True)
def _clean():
    accounting.listen()
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    yield
    paddle.set_flags({"FLAGS_pallas_interpret": False})


def mimo_toy(**check):
    cfg = toy.load("configs", CONFIG)
    cfg.update(vocab_size=256, hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32, num_attention_heads=16,
               swa_num_attention_heads=16, head_dim=24, v_head_dim=16,
               sliding_window=16, n_routed_experts=8,
               num_experts_per_tok=3, rope_theta=10000.0,
               swa_rope_theta=100.0, max_position_embeddings=256,
               dtype="float32")
    cfg["share"].update(router_width=16, experts_held=[4, 8])
    cfg["model"]["config_kwargs"].update(n_routed_experts=16,
                                         experts_held=[4, 8], ring_block=16)
    cfg["assumed"].update(initializer_range=0.1, sink_mean=math.log(4.0))
    cfg["serve"] = {"max_active": 4, "kv_blocks": 48, "block_size": 16,
                    "max_seq_len": 128, "temperature": 0.0}
    # float32 end to end: the program agrees with the reference to 1e-5
    cfg["reference_check"] = dict(
        cfg["reference_check"], sample=3, forced_decode_steps=4,
        gap_p99_limit=1e-4, gap_mean_limit=1e-5, forced_p75_limit=1e-4,
        forced_rms_limit=1e-5, **check)
    return cfg


def test_config_file_is_the_catalogs_row_cut_to_one_chips_share():
    cfg = toy.load("configs", CONFIG)
    kwargs, share = cfg["model"]["config_kwargs"], cfg["share"]
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert set(cfg["changed"]) == set(cfg["reduced"])
    for key, value in PUBLISHED.items():      # every width as published
        if key not in cfg["reduced"]:
            assert cfg[key] == value and type(cfg[key]) is type(value), key
    assert cfg["source"] == "https://huggingface.co/XiaomiMiMo/" \
        "MiMo-V2-Flash/blob/main/config.json"
    # the per-layer lists are the source's, whole; the net reads 7
    assert cfg["hybrid_layer_pattern"] == PATTERN and len(PATTERN) == 48
    assert cfg["moe_layer_freq"] == [0] + [1] * 47
    # inside the floors: the dense layer and the six that follow, one
    # whole period at 5 sliding : 1 full; 16 experts; an eighth of the
    # vocabulary
    assert cfg["num_hidden_layers"] == 7 and PATTERN[:7] == [0, 1, 1, 1, 1,
                                                             0, 1]
    assert PATTERN[1:7].count(1) == 5 and cfg["n_routed_experts"] == 16 >= 8
    assert cfg["vocab_size"] * share["vocabulary_ways"] == 152576
    assert kwargs["n_routed_experts"] == share["router_width"] == 256
    assert kwargs["experts_held"] == share["experts_held"] == [0, 16]
    assert 256 // share["chips_per_layer"] == 16
    assert share["chips_per_layer"] * share["pipeline_stages"] \
        == share["chips"] == 112
    assert -(-48 // share["pipeline_stages"]) == cfg["num_hidden_layers"]
    assert set(cfg["assumed"]) >= {
        "initializer_range", "router_bias_std", "sink_mean", "sink_std",
        "residual_order", "qk_norm", "value_scale_on", "rotated_dims",
        "sink", "why"}
    assert cfg["assumed"]["sink_mean"] == pytest.approx(math.log(32), 1e-6)
    assert len(cfg["departures"]) >= 4 and cfg["dtype"] == "bfloat16"
    assert "6.86 GB" in cfg["deployment"]
    assert cfg["serve"] == {"max_active": 128, "kv_blocks": 6144,
                            "block_size": 128, "max_seq_len": 14336,
                            "temperature": 0.0}
    assert kwargs["ring_block"] == 128 == cfg["sliding_window"]
    assert cfg["driver"] == DRIVER
    # the dense FFN stands beside the experts in the control; attention,
    # router, sinks and head are left as they are
    names = [n for n, _, _ in ref.leaf_shapes(cfg)]
    low = [n for n in names
           if n.endswith(tuple(cfg["reference_check"]["control_leaves"]))]
    assert len(low) == 7 * 3 and not [n for n in names if "shared" in n]
    assert [n for n in names if n.endswith("sinks")] == [
        f"blocks.{i}.attn.sinks" for i in (1, 2, 3, 4, 6)]
    rc = cfg["reference_check"]
    assert 0 < rc["gap_mean_limit"] < rc["gap_p99_limit"] < 0.2
    assert 0 < rc["forced_rms_limit"] < 0.2
    assert 0 < rc["forced_p75_limit"] < 0.2
    # the built net is the file's: the program cuts the lists to 7
    from benchmark.lib.build import load_object, model_kwargs
    built = load_object(cfg["model"]["config_class"])(**model_kwargs(cfg))
    assert built.hybrid_layer_pattern == PATTERN[:7]
    assert (built.heads("full_attention"), built.heads("sliding_attention"),
            built.head_dim, built.v_head_dim, built.sliding_window,
            built.ring_block, built.experts_held, built.n_routed_experts,
            built.attention_value_scale, built.partial_rotary_factor) \
        == ((64, 4), (64, 8), 192, 128, 128, 128, [0, 16], 256, 0.707,
            0.334)
    assert (built.rope_theta, built.swa_rope_theta,
            built.routed_scaling_factor) == (5000000, 10000, None)


def test_mix_is_the_issues_table():
    mix = toy.load("traffic", "reason_sat")
    assert mix["arrival"]["kind"] == "poisson"
    assert mix["headroom"] == 2.0
    assert mix["knee_rps"] == 2.92 and mix["arrival"]["rate"] == 6.0 \
        == round(2 * mix["headroom"] * mix["knee_rps"]) / 2   # to 0.5/s
    (tenant,) = mix["tenants"]
    assert tenant["prompt"] == {"kind": "lognormal", "median": 1024,
                                "sigma": 0.8, "lo": 256, "hi": 8192}
    assert tenant["new"] == {"kind": "lognormal", "median": 2048,
                             "sigma": 0.5, "lo": 512, "hi": 6144}
    assert mix["stratify"] == {"size": 32, "order_seed": 45}
    assert mix["seed_burst"] == {"count": 144, "new_scale": [0.05, 1.0]}
    assert (mix["lead_in_s"], mix["sample_every_s"], mix["trace_seconds"],
            mix["unfinished_is_failure"]) == (24.0, 0.1, 3.0, False)
    # every stream fits the deployment's longest; the buckets of the issue
    from benchmark.drivers.serve_open_loop import mix_buckets
    cfg = toy.load("configs", CONFIG)
    assert 8192 + 6144 == cfg["serve"]["max_seq_len"]
    assert mix_buckets(mix, cfg["serve"]["max_seq_len"] - 1) \
        == [256, 512, 1024, 2048, 4096, 8192]
    bench = runner.load_json(toy.ROOT, "BENCHMARK.json")
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "reason_sat", 1)
    for m in bench["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
        if m["name"] in ("window_cache_saved_share", "swa_decode_step_mbu",
                         "gqa_paged_attn_roofline"):
            assert CELL not in m["workloads"]     # they count Laguna's bytes


def test_copy_of_the_reference_is_the_programs():
    from paddle_tpu.text.models.reference import mimo_v2 as theirs
    for name in ("layer_shape", "rope", "rms_norm", "swiglu", "attention",
                 "route", "routed_part", "sub_weights", "block",
                 "block_weights", "forward"):
        assert inspect.getsource(getattr(ref, name)) == inspect.getsource(
            getattr(theirs, name)), name
    assert "paddle_tpu" not in re.sub(r'""".*?"""', "", inspect.getsource(ref),
                                      flags=re.S)


def test_weights_are_a_function_of_the_seed_and_the_programs_leaves():
    from benchmark.drivers import serve_open_loop_ref_state as drv
    cfg = mimo_toy()
    big = 2 ** 31 + 12345            # the driver's seeds are large
    a = dict(ref.make_weights(big, cfg))
    b = dict(ref.make_weights(big, cfg, prefix="blocks.1."))
    c = dict(ref.make_weights(big + 1, cfg))
    assert set(b) == {k for k in a if k.startswith("blocks.1.")}
    for k in b:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert not np.array_equal(np.asarray(a["head"]), np.asarray(c["head"]))
    assert abs(float(np.std(np.asarray(a["head"]))) - 0.1) < 1e-2
    bias = np.asarray(a["blocks.1.ffn.router_bias"])
    assert bias.shape == (16,) and bias.dtype == np.float32 and bias.any()
    sinks = np.concatenate([np.asarray(v) for k, v in a.items()
                            if k.endswith("sinks")])
    assert sinks.shape == (5 * 16,) and sinks.dtype == np.float32
    assert abs(sinks.mean() - math.log(4.0)) < 0.2 \
        and 0.35 < sinks.std() < 0.65
    assert np.all(np.asarray(a["blocks.0.attn_norm"]) == 1)
    net, loop = drv.build_server(cfg, big)
    params, _ = net.functional_state()
    assert set(params) == set(a)
    for k in a:
        np.testing.assert_array_equal(np.asarray(params[k]),
                                      np.asarray(a[k]))
    ids = np.random.RandomState(0).randint(1, 256, 40)
    got = np.asarray(net(ids[None])._value)[0]
    rcfg, held = ref.ref_config(cfg)
    want = np.asarray(ref.forward(a, rcfg, ids, held))
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-5
    # block by block, padded and the queries in blocks, the reference is
    # the same reference
    rows, = ref.reference_logits(big, cfg, [ids], [29], pad_to=16,
                                 q_block=16)
    assert rows.shape == (10, 256)
    assert np.abs(rows - want[29:39]).max() / np.abs(want).max() < 1e-5


def run_toy(cfg, rate=30.0):
    cell = toy.cell(CELL, cfg, toy.serve_mix_toy("reason_sat", rate,
                                                 new=(10, 24)),
                    seconds=2.0)
    return cell, runner.load_module("drivers", DRIVER).run(cell)


def test_driver_toy_is_correct_and_reports_the_cells_metrics(capsys):
    """Prompts of 5-30 tokens and 10-24 new ones over a window of 16 = one
    ring block: the served streams wrap their rings."""
    cell, obs = run_toy(mimo_toy())
    assert obs["correct"], obs["why_incorrect"]
    assert obs["failed"] == 0 and obs["attempted"] == len(obs["rows"]) > 0
    assert obs["compiles_in_window"] == 0
    compared = obs["compared"]
    assert set(compared) == {"requests_errored", "outputs_malformed",
                             "compiles_in_window", "ref_gap_p99",
                             "ref_gap_mean", "forced_logits_err_p75",
                             "forced_logits_rms"}
    assert all(value <= limit for value, limit in compared.values())
    e2e = runner.read_metrics(cell, obs, "end_to_end", "end_to_end")
    layer = runner.read_metrics(cell, obs, "per_layer", "layer_metrics")
    assert "its knee" in capsys.readouterr().out
    assert set(e2e) == {"serve_tokens_per_s", "setup_s"}
    # without a trace the device_trace and program_span readers report
    # nothing; the counters are read from the window's samples
    assert set(layer) == {"gen_late_p95_ms", "beat_ms", "kv_used_share",
                          "chat_ttft_p50_ms", "chat_tpot_p50_ms",
                          "compiles_in_window", "moe_expert_peak_over_mean"}
    assert 1.0 <= layer["moe_expert_peak_over_mean"]["value"] <= 8.0
    stats = obs["samples"][-1]
    assert stats["window_ring_bytes"] \
        == 5 * 4 * 16 * (8 * 24 + 8 * 16) * 4    # layers slots window k+v
    assert stats["attn_window_decode_tokens_read"] \
        < stats["attn_full_decode_tokens_read"] * 5 / 2


@pytest.mark.parametrize("control", [
    {"round_experts_to": "float8_e4m3fn"}, {"kv_round_to": "float8_e4m3fn"},
    "sinks_out"])
def test_driver_toy_refuses_each_control(control, monkeypatch):
    """The weights' control (benchmark/control_run.py), the cache's
    (benchmark/control_run_window.py: keys and values through float8 on
    their way into rings and pages) and the sink-dropped one
    (benchmark/control_run_sink.py: the served net's sinks at -1e30)."""
    cfg = mimo_toy()
    if control == "sinks_out":
        monkeypatch.setattr(ref_driver, "load_weights",
                            control_run_sink.without_sinks(
                                ref_driver.load_weights))
    elif "round_experts_to" in control:
        cfg["control"] = control
    else:
        cfg["model"]["config_kwargs"].update(control)
    _cell, obs = run_toy(cfg)
    assert not obs["correct"]
    for name in ("forced_logits_err_p75", "forced_logits_rms"):
        assert any(name in why for why in obs["why_incorrect"])
        value, limit = obs["compared"][name]
        assert value > 5 * limit


def test_readers_report_nothing_from_a_program_without_the_counters():
    """The parent of this cell's PR (no such net), and every configuration
    whose values are as deep as its keys: Laguna's counters are there and
    the readers still say nothing of its bytes."""
    sample = dict(steps=0, moe_decode_layer_steps=0, moe_decode_tokens=0,
                  moe_decode_experts_touched=0, kv_pool_used_blocks=9,
                  active_slots=4)
    obs = {"samples": [sample, dict(sample, steps=9)],
           "trace_modules": {0: [["jit_decode_step(1)", 0.0, 20e6]]},
           "trace_ops": {0: [["custom-call[tpu_custom_call] "
                              "_paged_grouped_call_once.1", 0.0, 1e5]]},
           "kernel_patterns": {"paged_attn": "_paged_grouped_call_once"},
           "module_patterns": {"decode": "^jit_decode_step"}}
    counted = dict(sample, attn_full_decode_tokens_read=0,
                   attn_window_decode_tokens_read=0)
    laguna = dict(obs, samples=[counted, dict(counted, steps=9)],
                  config=toy.load("configs", "laguna_s21_ep16"),
                  device_kind="TPU v5 lite", max_active=128)
    for name in NEW_READERS:
        reader = runner.load_module("layer_metrics", name)
        assert reader.read(obs) is None, name
        assert reader.read(laguna) is None, name
        assert reader.read({}) is None, name


def cell_obs(cfg):
    """A window of 100 steps of the cell as the arithmetic has it: 128
    slots of 2900 live tokens, 14 of 16 experts touched a layer."""
    seen, layer_steps = 128 * 2900, 100 * 6
    first = dict(steps=0, moe_decode_layer_steps=0,
                 moe_decode_experts_touched=0, kv_pool_used_blocks=3000,
                 active_slots=128, attn_full_decode_tokens_read=0,
                 attn_window_decode_tokens_read=0,
                 window_ring_bytes=128 * 5 * 128 * 5120)
    last = dict(first, steps=100, moe_decode_layer_steps=layer_steps,
                moe_decode_experts_touched=14 * layer_steps,
                attn_full_decode_tokens_read=100 * 2 * seen,
                attn_window_decode_tokens_read=100 * 5 * 128 * 128)
    return {"samples": [first, last], "config": cfg, "block_size": 128,
            "max_active": 128, "device_kind": "TPU v5 lite",
            "module_patterns": cfg["module_patterns"],
            "kernel_patterns": cfg["kernel_patterns"],
            "trace_modules": {0: [["jit_decode_step(1)", 0.0, 16e6],
                                  ["jit_prefill(2)", 16e6, 30e6],
                                  ["jit_decode_step(1)", 46e6, 16e6]]},
            "trace_ops": {0: [
                ["custom-call[tpu_custom_call] _paged_grouped_call_once.1",
                 0.0, 1.6e6],
                ["custom-call[tpu_custom_call] _paged_grouped_call_once.2",
                 3e6, 0.2e6],
                ["fusion fusion.7", 4e6, 1e6]]}}


def test_mimo_decode_step_mbu_reads_bytes_over_time_and_peak(capsys):
    cfg = toy.load("configs", CONFIG)
    obs = cell_obs(cfg)
    need = nbytes.decode_step_bytes(cfg, 14.0, 2 * 128 * 2900,
                                    5 * 128 * 128, 128)
    got = runner.load_module("layer_metrics",
                             "mimo_decode_step_mbu").read(obs)
    assert got == pytest.approx(100 * need / (0.016 * 819e9))
    assert 50 < got < 70
    # 2 x 371 200 tokens at 2 560 B + 5 x 16 384 at 5 120 B
    assert "14.00 experts touched a layer, 2.320 GB of cached keys and " \
        "values" in capsys.readouterr().out
    # what the algorithm needs never passes what is held
    full = nbytes.decode_step_bytes(cfg, 16, 2 * 6144 * 128, 5 * 128 * 128,
                                    128)
    assert full <= nbytes.held_params(cfg) * 2 + 6144 * 128 * 5120 \
        + 128 * nbytes.ring_bytes_per_slot(cfg)


def test_sink_roofline_reads_each_kind_of_call_at_its_own_bytes(capsys):
    cfg = toy.load("configs", CONFIG)
    obs = cell_obs(cfg)
    got = runner.load_module("layer_metrics",
                             "sink_gqa_attn_roofline").read(obs)
    full_ops, full_b = nbytes.sink_gqa_call_cost(cfg, False, 128, 128 * 2900)
    ring_ops, ring_b = nbytes.sink_gqa_call_cost(cfg, True, 128, 128 * 128)
    assert full_b == 128 * 2900 * 2560 + 128 * 64 * 320 * 2
    assert ring_b == 128 * 128 * 5120 + 128 * 64 * 320 * 2 + 64 * 4
    assert full_ops == 2 * 64 * 320 * 128 * 2900
    # bound by the bytes: 16 and 8 operations a byte against the chip's 240
    assert full_ops / 197e12 < full_b / 819e9
    assert ring_ops / 197e12 < ring_b / 819e9
    need = (2 * full_b + 5 * ring_b) / 7 / 819e9
    assert got == pytest.approx(100 * need / 0.9e-3)
    assert 0 < got < 100
    assert "2 calls of 900.0 us" in capsys.readouterr().out


def test_byte_functions_against_the_built_nets_leaves_and_the_pool():
    """The configuration file's own numbers: leaves from `leaf_shapes`
    (which the driver holds to the built net's parameters), the pool's
    allocation from the net's `paged_cache_spec` at the cell's ServeConfig,
    by shape alone."""
    import jax
    import jax.numpy as jnp
    from benchmark.lib.build import load_object, model_kwargs
    from paddle_tpu.nn.kv_pool import KVBlockPool
    cfg = toy.load("configs", CONFIG)
    leaves = {n: int(np.prod(s)) for n, s, _ in ref.leaf_shapes(cfg)}
    small = sum(v for n, v in leaves.items()
                if n.endswith(("norm", "sinks", "router_bias")))
    assert nbytes.held_params(cfg) == sum(leaves.values()) - small
    assert small < 0.07e6
    assert 6.85e9 < 2 * nbytes.held_params(cfg) < 6.87e9
    assert nbytes.layer_params(cfg, False, True, 0) == 290_455_552
    assert nbytes.layer_params(cfg, True, False, 16) == 498_073_600
    assert nbytes.layer_params(cfg, False, False, 16) == 492_830_720
    spec = type("N", (), {"config": load_object(
        cfg["model"]["config_class"])(**model_kwargs(cfg))})()
    spec = load_object(cfg["model"]["class"]).paged_cache_spec(spec)
    serve = cfg["serve"]
    arenas = jax.eval_shape(lambda: KVBlockPool(
        serve["kv_blocks"], serve["block_size"]).arenas_for(
            spec, jnp.bfloat16, slots=serve["max_active"]))
    paged = sum(int(np.prod(x.shape)) * 2 for layer, s in zip(arenas, spec)
                for x in layer[:len(s.arenas)])
    rings = sum(int(np.prod(x.shape)) * 2 for layer, s in zip(arenas, spec)
                for x in layer[len(s.arenas):])
    assert paged == (6144 + 1) * 128 * nbytes.paged_bytes_per_token(cfg)
    assert rings == 128 * nbytes.ring_bytes_per_slot(cfg) == 419_430_400
    assert 4.02e9 < paged < 4.04e9
