"""tools/hlo_evidence.py tier-1 self-check: the chip-free kernel
evidence harness must run on CPU, produce the documented schema, and its
canonical configs must keep passing every kernel eligibility gate (the
framework_lint TOOL_CROSS_CHECKS registration runs the same self_check)."""
import json
import os
import sys

import pytest

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)

import hlo_evidence  # noqa: E402


def test_self_check_clean():
    assert hlo_evidence.self_check() == []


def test_registered_in_framework_lint():
    import framework_lint
    assert "hlo_evidence" in framework_lint.TOOL_CROSS_CHECKS


def test_gates_pass_for_all_bench_shapes():
    """Every bench shape must be kernel-eligible — otherwise the bench
    would silently measure fallback paths again (BENCH_r03)."""
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    fc = importlib.import_module("paddle_tpu.ops.pallas.fused_ce")
    da = importlib.import_module("paddle_tpu.ops.pallas.decode_attention")

    bert, dec, ls = (hlo_evidence.BERT_CFG, hlo_evidence.DECODE_CFG,
                     hlo_evidence.LONGSEQ_CFG)
    assert fc.supported(bert["batch"] * bert["seq"], 768, 30522)
    s = ls["seq"]
    assert fa.supported((ls["batch"], 12, s, 64), (ls["batch"], 12, s, 64),
                        (ls["batch"], 12, s, 64))
    assert da.supported((dec["batch"], 12, 1, 64),
                        (dec["batch"], 12, dec["max_seq_len"], 64))


def test_tiny_run_schema_and_assertions(tmp_path):
    """Run the tool end to end on CPU with toy configs: TPU-target
    lowering must succeed, all three kernels must appear as custom calls,
    and the default-config decode reduction must clear 2x."""
    out = tmp_path / "HLO_EVIDENCE.json"
    report = hlo_evidence.run(str(out), tiny=True)

    data = json.loads(out.read_text())
    assert data == json.loads(json.dumps(report))  # round-trips
    assert data["platform"] == "tpu" and data["tiny"] is True
    for name in ("bert_train_step", "gpt_longseq_train_step",
                 "gpt_decode_step"):
        g = data["graphs"][name]
        assert "custom_calls" in g and "cost_analysis" in g
        assert "config" in g and "pallas_counters" in g

    assert data["graphs"]["bert_train_step"]["custom_calls"].get(
        "_ce_fwd_kernel", 0) > 0
    assert data["graphs"]["gpt_longseq_train_step"]["custom_calls"].get(
        "_flash_fwd_kernel", 0) > 0
    dec = data["graphs"]["gpt_decode_step"]
    assert dec["custom_calls"].get("_decode_attn_kernel", 0) > 0
    assert dec["sdpa_custom_calls"].get("_decode_attn_kernel", 0) == 0
    # cost analysis is computable on CPU for the TPU-lowered module
    assert dec["cost_analysis"].get("flops", -1) > 0
    full = dec["attention_per_step_full_config"]
    assert full["flops_reduction_x"] >= 2.0
    assert full["bytes_reduction_x"] >= 2.0
    assert data["ok"], [a for a in data["assertions"] if not a["ok"]]


def test_decode_attention_model_math():
    m = hlo_evidence.decode_attention_model(
        {"max_seq_len": 1024, "prompt": 32, "new": 128, "batch": 8},
        heads=12, head_dim=64, layers=12, bk=128)
    # live cols never exceed the cache and never shrink below one block
    assert 128 <= m["avg_live_cols_kernel"] <= 1024
    assert m["sdpa_full_cache"]["flops"] > m["decode_kernel"]["flops"]
    assert m["flops_reduction_x"] == pytest.approx(
        1024 / m["avg_live_cols_kernel"], rel=1e-2)
