"""The Laguna share through the reference-checked serving driver at toy
size on the CPU (its check passing, and refusing a run served one
precision down, weights or cache), the benchmark's copy of the reference
against the program's, the seeded weights, the configuration file against
the catalog's row, the byte functions against the built net's leaves and
the pool's allocation, and the four readers this cell brings."""
import inspect
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark import run as runner
from benchmark.lib import accounting
from benchmark.lib import bytes_laguna as nbytes
from benchmark.lib import ref_laguna as ref
from benchmark.tests import toy

CELL = "laguna_agent_mixed_sat"
CONFIG = "laguna_s21_ep16"
DRIVER = "serve_open_loop_ref_state"
NEW_READERS = ("gqa_paged_attn_roofline", "swa_decode_step_mbu",
               "decode_window_attn_ms", "window_cache_saved_share")
# the catalog's row (model-configs guide, architectures.jsonl), `config`:
# its numbers and flags; the per-layer lists are held below
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1,
    "mlp_only_layers": [0], "tie_word_embeddings": False,
    "gating": "per-head", "sliding_window": 512,
    "moe_apply_router_weight_on_input": False,
    "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0}
PUBLISHED_ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1}}


@pytest.fixture(autouse=True)
def _clean():
    accounting.listen()
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    yield
    paddle.set_flags({"FLAGS_pallas_interpret": False})


def laguna_toy(**check):
    cfg = toy.load("configs", CONFIG)
    cfg.update(vocab_size=256, hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32, shared_expert_intermediate_size=32,
               num_hidden_layers=5, num_key_value_heads=2, head_dim=16,
               num_attention_heads_per_layer=[4, 6, 6, 6] * 2,
               sliding_window=16, num_experts=8, n_routed_experts=8,
               num_experts_per_tok=3,
               max_position_embeddings=256, dtype="float32")
    cfg["share"].update(router_width=16, experts_held=[4, 8])
    cfg["model"]["config_kwargs"].update(num_experts=16, experts_held=[4, 8],
                                         ring_block=8)
    cfg["assumed"]["initializer_range"] = 0.1
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
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert set(cfg["changed"]) == set(cfg["reduced"])
    for key, value in PUBLISHED.items():      # every width as published
        if key not in cfg["reduced"]:
            assert cfg[key] == value and type(cfg[key]) is type(value), key
    assert cfg["rope_parameters"] == PUBLISHED_ROPE
    # the per-layer lists are the source's, whole; the net reads 12
    assert cfg["layer_types"] == ["full_attention"] + \
        ["sliding_attention"] * 3 + cfg["layer_types"][:44]
    assert cfg["num_attention_heads_per_layer"] == [48, 72, 72, 72] * 12
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert cfg["gating_types"] == ["per_head"] * 48
    # inside the floors: the dense layer and 11 that follow, three whole
    # periods; 16 experts; an eighth of the vocabulary
    assert cfg["num_hidden_layers"] == 12 and cfg["num_experts"] == 16 >= 8
    assert cfg["vocab_size"] * share["vocabulary_ways"] == 100352
    assert kwargs["num_experts"] == share["router_width"] == 256
    assert kwargs["experts_held"] == share["experts_held"] == [0, 16]
    assert 256 // share["chips_per_layer"] == 16 == cfg["num_experts"] \
        == cfg["n_routed_experts"]      # the accepted reader's name for it
    assert share["chips_per_layer"] * share["pipeline_stages"] \
        == share["chips"] == 64
    assert 48 // share["pipeline_stages"] == cfg["num_hidden_layers"]
    assert set(cfg["assumed"]) >= {
        "initializer_range", "residual_order", "qk_norm", "gate",
        "router_scores", "router_bias", "hidden_act", "why"}
    assert len(cfg["departures"]) >= 3 and cfg["dtype"] == "bfloat16"
    assert "5.33 GB" in cfg["deployment"]
    assert cfg["serve"]["max_active"] == 128 \
        and cfg["serve"]["block_size"] == kwargs["ring_block"] == 128 \
        and cfg["serve"]["max_seq_len"] == 9216
    assert cfg["driver"] == DRIVER
    # the dense FFN stands beside the experts in the control; the shared
    # expert, the attention, the router and the head are left as they are
    names = [n for n, _, _ in ref.leaf_shapes(cfg)]
    low = [n for n in names
           if n.endswith(tuple(cfg["reference_check"]["control_leaves"]))]
    assert len(low) == 12 * 3 and not any("shared" in n for n in low)
    rc = cfg["reference_check"]
    assert 0 < rc["gap_mean_limit"] < rc["gap_p99_limit"] < 0.2
    assert 0 < rc["forced_rms_limit"] < 0.2
    assert 0 < rc["forced_p75_limit"] < 0.2
    # the built net is the file's: the program cuts the lists to 12
    from benchmark.lib.build import load_object, model_kwargs
    built = load_object(cfg["model"]["config_class"])(**model_kwargs(cfg))
    assert built.layer_types == cfg["layer_types"][:12]
    assert built.num_attention_heads_per_layer == [48, 72, 72, 72] * 3
    assert (built.sliding_window, built.ring_block, built.num_kv_heads,
            built.head_dim, built.experts_held) == (512, 128, 8, 128, [0, 16])


def test_mix_is_the_issues_table():
    mix = toy.load("traffic", "agent_mixed_sat")
    ten, = mix["tenants"]
    assert ten["prompt"] == {"kind": "lognormal", "median": 1536,
                             "sigma": 0.85, "lo": 384, "hi": 8192}
    assert ten["new"] == {"kind": "lognormal", "median": 512, "sigma": 0.5,
                          "lo": 96, "hi": 1024}
    assert mix["stratify"] == {"size": 32, "order_seed": 43}
    assert mix["seed_burst"] == {"count": 144, "new_scale": [0.05, 1.0]}
    assert (mix["lead_in_s"], mix["sample_every_s"],
            mix["trace_seconds"]) == (12.0, 0.1, 3.0)
    assert mix["headroom"] == 2.0
    assert mix["arrival"]["rate"] * 2 == round(mix["arrival"]["rate"] * 2)
    from benchmark.drivers import serve_open_loop_ref as drv
    cfg = toy.load("configs", CONFIG)
    plan = drv.plan(cfg, mix, 2 ** 31 + 77, 51.0)
    assert all(1 <= r.prompt.min() and r.prompt.max() < 12544
               for r in plan[:60])
    buckets = [drv.bucket_of(r.prompt.size) for r in plan]
    assert set(buckets) == {512, 1024, 2048, 4096, 8192}
    share = {b: buckets.count(b) / len(buckets) for b in set(buckets)}
    # about 10 / 22 / 31 / 24 / 12 % (the clip at 384 holds 5 %)
    for b, want in ((512, 0.10), (1024, 0.22), (2048, 0.31), (4096, 0.24),
                    (8192, 0.12)):
        assert abs(share[b] - want) < 0.04, (b, share[b])
    body = plan[mix["seed_burst"]["count"]:]
    assert min(r.prompt.size for r in body) == 384
    assert max(r.prompt.size for r in body) == 8192
    mean = sum(r.prompt.size for r in body) / len(body)
    assert 2000 < mean < 2300
    assert max(r.new_tokens for r in body) == 1024
    assert all(r.prompt.size + r.new_tokens <= 9216 for r in plan)


def test_copy_of_the_reference_is_the_programs():
    from paddle_tpu.text.models.reference import laguna as theirs
    for name in ("inv_freq", "rope", "rms_norm", "swiglu", "attention",
                 "route", "routed_part", "shared_part", "sub_weights",
                 "block", "block_weights", "forward"):
        assert inspect.getsource(getattr(ref, name)) == inspect.getsource(
            getattr(theirs, name)), name
    assert "paddle_tpu" not in re.sub(r'""".*?"""', "", inspect.getsource(ref),
                                      flags=re.S)


def test_weights_are_a_function_of_the_seed_and_the_programs_leaves():
    from benchmark.drivers import serve_open_loop_ref_state as drv
    cfg = laguna_toy()
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
    assert bias.shape == (16,) and not bias.any()    # no selection bias
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
    cell = toy.cell(CELL, cfg, toy.serve_mix_toy("agent_mixed_sat", rate,
                                                 new=(10, 24)),
                    seconds=2.0)
    return cell, runner.load_module("drivers", DRIVER).run(cell)


def test_driver_toy_is_correct_and_reports_the_cells_metrics(capsys):
    """Prompts of 5-30 tokens and 10-24 new ones over a window of 16: the
    served streams wrap their rings."""
    cell, obs = run_toy(laguna_toy())
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
    said = re.search(r"positions, (\d+) slots live.*argmax at (\d+) of (\d+)",
                     out)
    assert int(said[1]) == cell.config["serve"]["max_active"]
    assert said[2] == said[3] != "0"
    assert set(e2e) == {"serve_tokens_per_s", "setup_s"}
    # without a trace the device_trace and program_span readers report
    # nothing; the counters are read from the window's samples
    assert set(layer) == {"gen_late_p95_ms", "beat_ms", "kv_used_share",
                          "chat_ttft_p50_ms", "chat_tpot_p50_ms",
                          "compiles_in_window", "moe_expert_peak_over_mean",
                          "window_cache_saved_share"}
    assert 1.0 <= layer["moe_expert_peak_over_mean"]["value"] <= 8.0
    # 3 window layers of 16-token rings against 5 paged layers of streams
    # of about 30 tokens in 16-token blocks: nothing is saved at toy size
    # (a ring is a page never freed, and an idle slot's rings count too)
    assert layer["window_cache_saved_share"]["value"] < 60.0
    assert "GB of rings) where 5 paged layers would hold" in out
    stats = obs["samples"][-1]
    assert stats["window_ring_bytes"] == 3 * 2 * 4 * 16 * 2 * 16 * 4
    assert stats["attn_window_decode_tokens_read"] \
        < stats["attn_full_decode_tokens_read"] * 3 / 2


@pytest.mark.parametrize("control", [
    {"round_experts_to": "float8_e4m3fn"}, {"kv_round_to": "float8_e4m3fn"}])
def test_driver_toy_refuses_a_run_one_precision_down(control):
    """The weights' control (benchmark/control_run.py) and the cache's
    (benchmark/control_run_window.py: keys and values through float8 on
    their way into rings and pages)."""
    cfg = laguna_toy()
    if "kv_round_to" in control:
        cfg["model"]["config_kwargs"].update(control)
    else:
        cfg["control"] = control
    _cell, obs = run_toy(cfg)
    assert not obs["correct"]
    for name in ("forced_logits_err_p75", "forced_logits_rms"):
        assert any(name in why for why in obs["why_incorrect"])
        value, limit = obs["compared"][name]
        assert value > 5 * limit


def test_readers_report_nothing_from_a_program_without_the_counters():
    """The parent of this cell's PR, and every net without window layers:
    the other nets' counters are there, the window's are not."""
    sample = dict(steps=0, moe_decode_layer_steps=0, moe_decode_tokens=0,
                  moe_decode_experts_touched=0, kv_pool_used_blocks=9,
                  active_slots=4)
    obs = {"samples": [sample, dict(sample, steps=9)],
           "trace_modules": {0: [["jit_decode_step(1)", 0.0, 20e6]]},
           "trace_ops": {0: [["custom-call[tpu_custom_call] "
                              "_paged_grouped_call_once.1", 0.0, 1e5]]},
           "kernel_patterns": {"paged_attn": "_paged_grouped_call_once"},
           "module_patterns": {"decode": "^jit_decode_step"}}
    for name in NEW_READERS:
        reader = runner.load_module("layer_metrics", name)
        assert reader.read(obs) is None, name
        assert reader.read({}) is None, name


def cell_obs(cfg):
    """A window of 100 steps of the cell as the arithmetic has it: 128
    slots of 2430 live tokens, 14 of 16 experts touched a layer."""
    seen, layer_steps = 128 * 2430, 100 * 11
    first = dict(steps=0, moe_decode_layer_steps=0,
                 moe_decode_experts_touched=0, kv_pool_used_blocks=2500,
                 active_slots=128, attn_full_decode_tokens_read=0,
                 attn_window_decode_tokens_read=0,
                 window_ring_bytes=9 * 2 * 128 * 512 * 2048)
    last = dict(first, steps=100, moe_decode_layer_steps=layer_steps,
                moe_decode_experts_touched=14 * layer_steps,
                attn_full_decode_tokens_read=100 * 3 * seen,
                attn_window_decode_tokens_read=100 * 9 * 128 * 512)
    return {"samples": [first, last], "config": cfg, "block_size": 128,
            "max_active": 128, "device_kind": "TPU v5 lite",
            "module_patterns": cfg["module_patterns"],
            "kernel_patterns": cfg["kernel_patterns"],
            "trace_modules": {0: [["jit_decode_step(1)", 0.0, 25e6],
                                  ["jit_prefill(2)", 25e6, 30e6],
                                  ["jit_decode_step(1)", 55e6, 25e6]]},
            "trace_ops": {0: [
                ["custom-call[tpu_custom_call] _paged_grouped_call_once.1",
                 0.0, 2.4e6],
                ["custom-call[tpu_custom_call] _paged_grouped_call_once.2",
                 3e6, 0.6e6],
                ["fusion fusion.7", 4e6, 1e6]]}}


def test_swa_decode_step_mbu_reads_bytes_over_time_and_peak(capsys):
    cfg = toy.load("configs", CONFIG)
    obs = cell_obs(cfg)
    need = nbytes.decode_step_bytes(cfg, 14.0, 3 * 128 * 2430,
                                    9 * 128 * 512, 128)
    got = runner.load_module("layer_metrics", "swa_decode_step_mbu").read(obs)
    assert got == pytest.approx(100 * need / (0.025 * 819e9))
    assert 45 < got < 60
    assert "14.00 experts touched a layer, 6.238 GB of cached keys and " \
        "values" in capsys.readouterr().out
    # what the algorithm needs never passes what is held
    full = nbytes.decode_step_bytes(cfg, 16, 3 * 3072 * 128, 9 * 128 * 512,
                                    128)
    assert full <= nbytes.held_params(cfg) * 2 + 3072 * 128 * 12288 \
        + 128 * nbytes.ring_bytes_per_slot(cfg)


def test_gqa_roofline_reads_each_kind_of_call_at_its_own_lengths(capsys):
    cfg = toy.load("configs", CONFIG)
    obs = cell_obs(cfg)
    got = runner.load_module("layer_metrics",
                             "gqa_paged_attn_roofline").read(obs)
    full_ops, full_b = nbytes.gqa_call_cost(cfg, 48, 128, 128 * 2430)
    ring_ops, ring_b = nbytes.gqa_call_cost(cfg, 72, 128, 128 * 512)
    assert full_b == 128 * 2430 * 4096 + 2 * 128 * 48 * 128 * 2
    assert full_ops == 4 * 128 * 48 * 128 * 2430
    # bound by the bytes: 12 and 18 operations a byte against the chip's 240
    assert full_ops / 197e12 < full_b / 819e9
    assert ring_ops / 197e12 < ring_b / 819e9
    need = (3 * full_b + 9 * ring_b) / 12 / 819e9
    assert got == pytest.approx(100 * need / 1.5e-3)
    assert "2 calls of 1500.0 us" in capsys.readouterr().out


def test_window_cache_saved_share_counts_rings_whole(capsys):
    cfg = toy.load("configs", CONFIG)
    obs = cell_obs(cfg)
    got = runner.load_module("layer_metrics",
                             "window_cache_saved_share").read(obs)
    live = (2500 - 64) * 128
    held = 2500 * 128 * 12288 + 9 * 2 * 128 * 512 * 2048
    assert got == pytest.approx(100 * (1 - held / (12 * live * 4096)))
    assert 55 < got < 65
    # streams shorter than the window: a ring is a page never freed
    short = dict(obs, samples=[dict(s, kv_pool_used_blocks=300)
                               for s in obs["samples"]])
    assert runner.load_module("layer_metrics",
                              "window_cache_saved_share").read(short) < 0


def test_byte_functions_against_the_built_nets_leaves_and_the_pool():
    c = toy.load("configs", CONFIG)
    assert nbytes.attention_params(c, 48) == (
        2 * 3072 * 6144 + 2 * 3072 * 1024 + 3072 * 48) == 44_187_648
    assert nbytes.attention_params(c, 72) == (
        2 * 3072 * 9216 + 2 * 3072 * 1024 + 3072 * 72) == 63_135_744
    assert nbytes.dense_ffn_params(c) == 3 * 3072 * 12288 == 113_246_208
    assert nbytes.expert_params(c) == nbytes.shared_expert_params(c) \
        == 3 * 3072 * 1024
    assert nbytes.router_params(c) == 3072 * 256
    assert nbytes.layer_params(c, 48, True, 0) == 157_433_856
    assert round(nbytes.layer_params(c, 48, False, 16) / 1e6, 1) == 205.4
    assert round(nbytes.layer_params(c, 72, False, 16) / 1e6, 1) == 224.4
    assert round(nbytes.held_params(c) * 2 / 1e9, 2) == 5.33
    assert (nbytes.layers_of(c, nbytes.FULL),
            nbytes.layers_of(c, nbytes.SLIDING)) == (3, 9)
    assert nbytes.paged_bytes_per_token(c) == 12288
    assert nbytes.ring_bytes_per_slot(c) == 9 * 512 * 4096 == 18_874_368
    # the program's own leaves add up to the same count (norms and the
    # selection bias apart): names and shapes are the built net's, which
    # `load_weights` holds to `leaf_shapes` at toy size (test above)
    leaves = sum(int(np.prod(shape)) for _, shape, kind
                 in ref.leaf_shapes(c) if kind == "matrix")
    assert leaves == nbytes.held_params(c)
    small = sum(int(np.prod(shape)) for _, shape, kind
                in ref.leaf_shapes(c) if kind != "matrix")
    assert small == 12 * 2 * 3072 + 11 * 256 + 3072
    # and the pool's allocation: 6 arenas of kv_blocks + 1 blocks for the
    # three full layers, 18 rings of 128 slots for the nine sliding ones
    from paddle_tpu.nn.kv_pool import KVBlockPool, window_ring_shape
    blocks = c["serve"]["kv_blocks"]
    arena = KVBlockPool(blocks, 128).arena_shape(8, 128)
    per_block = int(np.prod(arena[1:])) * 2 * 6
    assert per_block == 128 * nbytes.paged_bytes_per_token(c)
    assert 4.5e9 < blocks * per_block < 5.2e9
    ring = int(np.prod(window_ring_shape(512, 128, 8, 128))) * 2 * 18
    assert ring == nbytes.ring_bytes_per_slot(c)
    assert round(128 * ring / 1e9, 2) == 2.42
    # a step that touches 14 of 16 experts a layer, 311 k tokens live
    step = nbytes.decode_step_bytes(c, 14, 3 * 311_000, 9 * 128 * 512, 128)
    assert 10.8e9 < step < 11.6e9
    assert step < nbytes.held_params(c) * 2 + 311_000 * 12288 \
        + 128 * nbytes.ring_bytes_per_slot(c)


def test_toy_net_is_what_the_byte_functions_count():
    """At toy size the net is built: its parameter count less the norms
    and the selection bias is `held_params` of the same configuration, and
    the loop's per-slot state is the rings'."""
    from benchmark.drivers import serve_open_loop_ref_state as drv
    cfg = laguna_toy()
    net, loop = drv.build_server(cfg, 3)
    kinds = {name: kind for name, _, kind in ref.leaf_shapes(cfg)}
    count = sum(int(np.prod(p.shape)) for name, p in net.named_parameters()
                if kinds[name] == "matrix")
    assert count == nbytes.held_params(cfg)
    spec = net.paged_cache_spec()
    assert len(spec) == cfg["num_hidden_layers"] == 5
    assert sum(len(layer.arenas) for layer in spec) // 2 \
        == nbytes.layers_of(cfg, nbytes.FULL) == 2
    assert loop.stats()["state_bytes"] \
        == 4 * nbytes.ring_bytes_per_slot(cfg, itemsize=4)
    arenas = sum(x.nbytes for layer, a in zip(spec, loop._arenas)
                 for x in a[:len(layer.arenas)])
    assert arenas == (48 + 1) * 16 * nbytes.paged_bytes_per_token(
        cfg, itemsize=4)
