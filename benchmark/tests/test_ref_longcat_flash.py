"""The LongCat-Flash share through the reference-checked serving driver at
toy size on the CPU (its check passing, and refusing a run served one
precision down), the benchmark's copy of the reference against the
program's, the seeded weights, the configuration file against the
catalog's row, the byte functions against the built net's leaves and the
pool's allocation, and the two readers this cell brings."""
import inspect
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark import run as runner
from benchmark.lib import accounting
from benchmark.lib import bytes_longcat_flash as nbytes
from benchmark.lib import ref_longcat_flash as ref
from benchmark.tests import toy

CELL = "longcat_flash_assist_sat"
CONFIG = "longcat_flash_chat_ep32"
# the catalog's row (model-configs guide, architectures.jsonl), `config`
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}


@pytest.fixture(autouse=True)
def _clean():
    accounting.listen()
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    yield
    paddle.set_flags({"FLAGS_pallas_interpret": False})


def longcat_toy(**check):
    cfg = toy.load("configs", CONFIG)
    cfg.update(vocab_size=256, hidden_size=64, num_layers=2,
               num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               ffn_hidden_size=96, expert_ffn_hidden_size=32,
               n_routed_experts=8, zero_expert_num=8, moe_topk=6,
               max_position_embeddings=256, dtype="float32")
    cfg["share"].update(router_width=24, experts_held=[4, 8], zero_experts=8)
    cfg["model"]["config_kwargs"].update(num_experts=16, experts_held=[4, 8])
    cfg["assumed"]["router_bias_std"] = 0.004
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
    assert cfg["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert set(cfg["changed"]) == set(cfg["reduced"])
    for key, value in PUBLISHED.items():      # every width as published
        if key not in cfg["reduced"]:
            assert cfg[key] == value and type(cfg[key]) is type(value), key
    # inside the floors: four layers, 8 experts, an eighth of the vocabulary
    assert cfg["num_layers"] == 4 and cfg["n_routed_experts"] == 16 >= 8
    assert cfg["vocab_size"] * share["vocabulary_ways"] == 131072
    assert share["vocabulary_ways"] == 8
    assert kwargs["num_experts"] == PUBLISHED["n_routed_experts"] == 512
    assert share["router_width"] == 512 + cfg["zero_expert_num"] == 768
    assert kwargs["experts_held"] == share["experts_held"] == [0, 16]
    assert cfg["n_routed_experts"] == share["experts_held"][1]
    assert 512 // share["chips_per_layer"] == 16
    assert share["zero_experts"] == cfg["zero_expert_num"] == 256
    assert set(cfg["assumed"]) >= {"initializer_range", "router_bias_std",
                                   "norm_topk_prob", "hidden_act", "why"}
    assert len(cfg["departures"]) >= 3 and cfg["dtype"] == "bfloat16"
    assert cfg["serve"] == {"max_active": 128, "kv_blocks": 1536,
                            "block_size": 128, "max_seq_len": 3072,
                            "temperature": 0.0}
    # the two dense FFNs stand beside the experts in the control: a token
    # leaves 12 x 16 / 768 = 0.25 pairs a layer on the held experts
    assert set(cfg["reference_check"]["control_leaves"]) == {
        "experts.gate", "experts.up", "experts.down", "ffn.gate", "ffn.up",
        "ffn.down"}
    names = [n for n, _, _ in ref.leaf_shapes(cfg)]
    low = [n for n in names
           if n.endswith(tuple(cfg["reference_check"]["control_leaves"]))]
    assert len(low) == 4 * (3 + 2 * 3)       # no attention, router or head
    rc = cfg["reference_check"]
    assert 0 < rc["gap_mean_limit"] < rc["gap_p99_limit"] < 0.2
    assert 0 < rc["forced_rms_limit"] < 0.2
    assert 0 < rc["forced_p75_limit"] < 0.2


def test_mix_is_the_issues_table():
    mix = toy.load("traffic", "assist_sat")
    ten, = mix["tenants"]
    assert ten["prompt"] == {"kind": "lognormal", "median": 512,
                             "sigma": 0.6, "lo": 128, "hi": 2048}
    assert ten["new"] == {"kind": "lognormal", "median": 512, "sigma": 0.5,
                          "lo": 96, "hi": 1024}
    assert mix["stratify"] == {"size": 32, "order_seed": 39}
    assert mix["seed_burst"] == {"count": 144, "new_scale": [0.05, 1.0]}
    assert (mix["lead_in_s"], mix["sample_every_s"],
            mix["trace_seconds"]) == (6.0, 0.1, 3.0)
    assert (mix["knee_rps"], mix["headroom"], mix["arrival"]["rate"]) \
        == (6.33, 2.0, 12.5)
    assert mix["arrival"]["rate"] * 2 == round(mix["arrival"]["rate"] * 2)
    from benchmark.drivers import serve_open_loop_ref as drv
    cfg = toy.load("configs", CONFIG)
    plan = drv.plan(cfg, mix, 2 ** 31 + 77, 51.0)
    assert all(1 <= r.prompt.min() and r.prompt.max() < 16384
               for r in plan[:60])
    assert {drv.bucket_of(r.prompt.size) for r in plan} \
        == {128, 256, 512, 1024, 2048}      # 128: the ~1 % clipped to lo
    body = plan[mix["seed_burst"]["count"]:]
    assert min(r.prompt.size for r in body) == 128
    assert max(r.prompt.size for r in body) == 2048
    # the lower clip of the outputs is 3.3 sigma out (0.04 % of draws)
    # and binds in few runs; the upper one (8 %) in every run
    assert 96 <= min(r.new_tokens for r in body) < 160
    assert max(r.new_tokens for r in body) == 1024
    assert all(r.prompt.size + r.new_tokens <= 3072 for r in plan)


def test_copy_of_the_reference_is_the_programs():
    from paddle_tpu.text.models.reference import longcat_flash as theirs
    for name in ("inv_freq", "rope", "rms_norm", "swiglu", "attention",
                 "route", "expert_layer", "sub_weights", "block",
                 "block_weights", "forward"):
        assert inspect.getsource(getattr(ref, name)) == inspect.getsource(
            getattr(theirs, name)), name
    assert "paddle_tpu" not in re.sub(r'""".*?"""', "", inspect.getsource(ref),
                                      flags=re.S)


def test_weights_are_a_function_of_the_seed_and_the_programs_leaves():
    from benchmark.drivers import serve_open_loop_ref as drv
    cfg = longcat_toy()
    big = 2 ** 31 + 12345            # the driver's seeds are large
    a = dict(ref.make_weights(big, cfg))
    b = dict(ref.make_weights(big, cfg, prefix="blocks.1."))
    c = dict(ref.make_weights(big + 1, cfg))
    assert set(b) == {k for k in a if k.startswith("blocks.1.")}
    for k in b:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert not np.array_equal(np.asarray(a["head"]), np.asarray(c["head"]))
    assert abs(float(np.std(np.asarray(a["head"]))) - 0.02) < 2e-3
    bias = np.asarray(a["blocks.1.experts.router_bias"])
    assert bias.shape == (24,) and 0 < float(np.std(bias)) < 0.01
    assert np.all(np.asarray(a["blocks.0.sub.1.attn_norm"]) == 1)
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
    # block by block and padded, the reference is the same reference
    rows, = ref.reference_logits(big, cfg, [ids], [29], pad_to=16)
    assert rows.shape == (10, 256)
    assert np.abs(rows - want[29:39]).max() / np.abs(want).max() < 1e-5


def run_toy(cfg, rate=30.0):
    cell = toy.cell(CELL, cfg, toy.serve_mix_toy("assist_sat", rate),
                    seconds=2.0)
    return cell, runner.load_module("drivers", "serve_open_loop_ref").run(
        cell)


def test_driver_toy_is_correct_and_reports_the_cells_metrics(capsys):
    cell, obs = run_toy(longcat_toy())
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
    out = capsys.readouterr().out
    assert "its knee" in out
    # every decode slot was live in the teacher-forced steps, over two
    # caches a layer, and the loop's own decode step sampled what the
    # compared logits say
    said = re.search(r"positions, (\d+) slots live.*argmax at (\d+) of (\d+)",
                     out)
    assert int(said[1]) == cell.config["serve"]["max_active"]
    assert said[2] == said[3] != "0"
    assert set(e2e) == {"serve_tokens_per_s", "setup_s"}
    # without a trace the device_trace and program_span readers report
    # nothing; the expert counters are read from the window's samples
    assert set(layer) == {"gen_late_p95_ms", "beat_ms", "kv_used_share",
                          "chat_ttft_p50_ms", "chat_tpot_p50_ms",
                          "compiles_in_window", "moe_expert_peak_over_mean",
                          "moe_zero_pair_share"}
    assert 1.0 <= layer["moe_expert_peak_over_mean"]["value"] <= 8.0
    # 8 of the toy router's 24 outputs are zero-compute experts
    assert 15.0 < layer["moe_zero_pair_share"]["value"] < 55.0
    line = re.search(r"moe: decode: ([\d.]+) real and ([\d.]+) zero pairs a "
                     r"token a layer, real pairs a token sd ([\d.]+)", out)
    assert abs(float(line[1]) + float(line[2]) - 6.0) < 1e-3
    assert 0.3 < float(line[3]) < 2.5


def test_driver_toy_refuses_a_run_one_precision_down():
    cfg = longcat_toy()
    cfg["control"] = {"round_experts_to": "float8_e4m3fn"}
    _cell, obs = run_toy(cfg)
    assert not obs["correct"]
    for name in ("forced_logits_err_p75", "forced_logits_rms"):
        assert any(name in why for why in obs["why_incorrect"])
        value, limit = obs["compared"][name]
        assert value > 5 * limit      # reads 5.5e-4 and 6.5e-4


def test_readers_report_nothing_from_a_program_without_the_counters():
    """The parent of this cell's PR, and every net without zero-compute
    experts: Kimi's counters are there, the zero pairs are not."""
    sample = dict(steps=0, moe_decode_layer_steps=0, moe_decode_tokens=0,
                  moe_decode_experts_touched=0, kv_pool_used_blocks=9,
                  active_slots=4)
    obs = {"samples": [sample, dict(sample, steps=9)],
           "trace_modules": {0: [["jit_decode_step(1)", 0.0, 20e6]]},
           "module_patterns": {"decode": "^jit_decode_step"}}
    for name in ("moe_zero_pair_share", "scmoe_decode_step_mbu"):
        reader = runner.load_module("layer_metrics", name)
        assert reader.read(obs) is None
        assert reader.read({}) is None


def test_scmoe_decode_step_mbu_reads_bytes_over_time_and_peak(capsys):
    cfg = toy.load("configs", CONFIG)
    first = dict(steps=0, moe_decode_layer_steps=0, moe_decode_pairs_zero=0,
                 moe_decode_experts_touched=0, kv_pool_used_blocks=1100,
                 active_slots=128)
    last = dict(first, steps=100, moe_decode_layer_steps=400,
                moe_decode_experts_touched=5600, moe_decode_pairs_zero=1)
    obs = {"samples": [first, last], "config": cfg, "block_size": 128,
           "max_active": 128, "device_kind": "TPU v5 lite",
           "module_patterns": cfg["module_patterns"],
           "trace_modules": {0: [["jit_decode_step(1)", 0.0, 20e6],
                                 ["jit_prefill(2)", 20e6, 30e6],
                                 ["jit_decode_step(1)", 50e6, 20e6]]}}
    need = nbytes.decode_step_bytes(cfg, 14.0, 972 * 128, 128)
    got = runner.load_module("layer_metrics",
                             "scmoe_decode_step_mbu").read(obs)
    assert got == pytest.approx(100 * need / (0.020 * 819e9))
    assert 55 < got < 75
    assert "14.00 experts touched a layer, 124416 live tokens of 9216 B" \
        in capsys.readouterr().out
    # every byte the share holds, read in the least time the chip could:
    # what the algorithm needs never passes what is held
    full = nbytes.decode_step_bytes(cfg, 16, 1536 * 128, 128)
    assert full <= nbytes.held_params(cfg) * 2 + 1536 * 128 * 9216


def test_moe_zero_pair_share_reads_the_windows_counters(capsys):
    first = dict(steps=10, moe_decode_layer_steps=40, moe_decode_tokens=1000,
                 moe_decode_pairs_zero=16000, moe_decode_pairs_real=32000,
                 moe_decode_pairs_real_sq=270000)
    last = dict(steps=110, moe_decode_layer_steps=440,
                moe_decode_tokens=13800, moe_decode_pairs_zero=16000 + 204800,
                moe_decode_pairs_real=32000 + 409600,
                moe_decode_pairs_real_sq=270000 + 3411968)
    reader = runner.load_module("layer_metrics", "moe_zero_pair_share")
    got = reader.read({"samples": [first, last]})
    assert got == pytest.approx(100 / 3)
    # 12800 tokens x 4 layers: 8 real and 4 zero pairs a token a layer,
    # E[r^2] = 66.64 -> sd 1.6248
    assert "8.0000 real and 4.0000 zero pairs a token a layer, real pairs " \
        "a token sd 1.6248" in capsys.readouterr().out


def test_byte_functions_against_the_built_nets_leaves_and_the_pool():
    c = toy.load("configs", CONFIG)
    assert nbytes.attention_params(c) == (
        6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
        + 8192 * 6144) == 90_570_752
    assert nbytes.dense_ffn_params(c) == 3 * 6144 * 12288 == 226_492_416
    assert nbytes.expert_params(c) == 3 * 6144 * 2048 == 37_748_736
    assert nbytes.router_params(c) == 6144 * 768
    assert nbytes.layer_params(c, 0) == 638_844_928
    assert round(nbytes.layer_params(c, 16) * 2 / 1e9, 3) == 2.486
    assert round(nbytes.held_params(c) * 2 / 1e9, 2) == 10.35
    assert nbytes.latent_bytes_per_token(c) == 8 * 1152
    # the program's own leaves add up to the same count (norms and the
    # selection bias apart): names and shapes are the built net's, which
    # `load_weights` holds to `leaf_shapes` at toy size (test above)
    leaves = sum(int(np.prod(shape)) for _, shape, kind
                 in ref.leaf_shapes(c) if kind == "matrix")
    assert leaves == nbytes.held_params(c)
    small = sum(int(np.prod(shape)) for _, shape, kind
                in ref.leaf_shapes(c) if kind != "matrix")
    assert small == 4 * (2 * (2 * 6144 + 1536 + 512) + 768) + 6144
    # and the pool's allocation: 8 arenas of kv_blocks + 1 blocks
    from paddle_tpu.nn.kv_pool import KVBlockPool
    arena = KVBlockPool(c["serve"]["kv_blocks"],
                        c["serve"]["block_size"]).arena_shape(1, 576)
    per_block = int(np.prod(arena[1:])) * 2 * 8
    assert per_block == 128 * nbytes.latent_bytes_per_token(c)
    assert round(1536 * per_block / 1e9, 3) == 1.812
    # a step that touches 13.8 of 16 experts a layer, 120 k tokens live
    step = nbytes.decode_step_bytes(c, 13.8, 120_000, 128)
    assert 10.4e9 < step < 10.8e9
    assert step < nbytes.held_params(c) * 2 + 120_000 * 9216


def test_toy_net_is_what_the_byte_functions_count():
    """At toy size the net is built: its parameter count less the norms
    and the selection bias is `held_params` of the same configuration."""
    from benchmark.drivers import serve_open_loop_ref as drv
    cfg = longcat_toy()
    net, _loop = drv.build_server(cfg, 3)
    kinds = {name: kind for name, _, kind in ref.leaf_shapes(cfg)}
    count = sum(int(np.prod(p.shape)) for name, p in net.named_parameters()
                if kinds[name] == "matrix")
    assert count == nbytes.held_params(cfg)
    spec = net.paged_cache_spec()
    assert len(spec) == 2 * cfg["num_layers"]
    assert sum(a[0][1] for a in (layer.arenas for layer in spec)) * 4 \
        == nbytes.latent_bytes_per_token(cfg, itemsize=4)
