"""framework_lint in-process (ISSUE 1): the repo itself must be clean
(this test IS the tier-1 invocation of the lint), and seeded fixtures
with a registry/API.spec drift and a tracer-concretization hazard must
each produce violations."""
import json
import os
import sys
import tempfile
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import framework_lint  # noqa: E402


def test_repo_is_clean():
    problems = framework_lint.run_lint()
    assert problems == [], "\n".join(problems)
    assert framework_lint.main([]) == 0


def test_registry_spec_drift_detected():
    with tempfile.TemporaryDirectory() as tmp:
        # a spec that lost hash_bucket and carries a dead MISSING entry
        spec = os.path.join(tmp, "API.spec")
        with open(os.path.join(REPO, "API.spec")) as f:
            lines = [ln for ln in f
                     if not ln.split(" ", 1)[0].endswith(".hash_bucket")]
        lines.append("paddle_tpu.gone_op MISSING\n")
        with open(spec, "w") as f:
            f.writelines(lines)
        problems = framework_lint.check_registry_spec(
            spec, framework_lint.VERSIONS_PATH)
        assert any("hash_bucket" in p and "absent from API.spec" in p
                   for p in problems)
        assert any("MISSING" in p for p in problems)


def test_version_drift_detected():
    with tempfile.TemporaryDirectory() as tmp:
        with open(framework_lint.VERSIONS_PATH) as f:
            snap = json.load(f)
        # signature changed without a version bump
        snap["matmul"] = {"version": snap["matmul"]["version"],
                         "sig": "(x, y, old_flag=False)"}
        # and a version regression: snapshot is ahead of the live @defop
        snap["relu"] = {"version": 99, "sig": snap["relu"]["sig"]}
        # and a stale snapshot: live beam_search is v2, snapshot says v1
        snap["beam_search"] = {"version": 1, "sig": snap["beam_search"]["sig"]}
        # and a stale entry for a removed op
        snap["op_that_was_deleted"] = {"version": 1, "sig": "(x)"}
        vpath = os.path.join(tmp, "OP_VERSIONS.json")
        with open(vpath, "w") as f:
            json.dump(snap, f)
        problems = framework_lint.check_registry_spec(
            framework_lint.SPEC_PATH, vpath)
        assert any("matmul" in p and "without a version bump" in p
                   for p in problems)
        assert any("relu" in p and "regressed" in p for p in problems)
        assert any("beam_search" in p and "still records v1" in p
                   for p in problems)
        assert any("op_that_was_deleted" in p and "no longer registered"
                   in p for p in problems)


def test_concretization_hazards_detected_and_pragma_suppresses():
    src = textwrap.dedent("""
        import jax.numpy as jnp
        from paddle_tpu.ops._dispatch import defop

        @defop
        def bad_branch(x, axis=0):
            y = jnp.exp(x)
            if x > 0:                      # hazard: if on traced value
                y = y * 2
            return y

        @defop
        def bad_concretize(x):
            s = jnp.sum(x)
            n = float(x)                   # hazard: float() of traced
            return s.item() + n            # hazard: .item()

        @defop
        def fine_op(x, mode="a"):
            if mode == "a":                # static attr: fine
                return jnp.exp(x)
            if x.ndim == 2:                # metadata: fine
                return jnp.log(x)
            return jnp.sqrt(x)

        @defop
        def waived(x):
            if x > 0:  # lint: concretization-ok
                return jnp.exp(x)
            return x
    """)
    with tempfile.TemporaryDirectory() as tmp:
        with open(os.path.join(tmp, "fixture_ops.py"), "w") as f:
            f.write(src)
        hits = framework_lint.check_concretization(tmp)
    joined = "\n".join(hits)
    assert "bad_branch" in joined and "`if` on traced" in joined
    assert "bad_concretize" in joined and "`float()`" in joined
    assert ".item()" in joined
    assert "fine_op" not in joined
    assert "waived" not in joined


def test_perf_floors_clean_on_committed_evidence():
    """The committed HLO_EVIDENCE.json must clear every floor (counts and
    grid arithmetic, not chip measurements)."""
    assert framework_lint.check_perf_floors() == []


def test_perf_floor_regression_detected():
    with open(framework_lint.EVIDENCE_PATH) as f:
        evidence = json.load(f)
    evidence["graphs"]["gpt_decode_step"]["attention_per_step"][
        "flops_reduction_x"] = 1.3  # below the 2x floor
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "HLO_EVIDENCE.json")
        with open(path, "w") as f:
            json.dump(evidence, f)
        problems = framework_lint.check_perf_floors(path)
    assert len(problems) == 1
    assert "decode-attention FLOPs reduction" in problems[0]
    assert "1.3" in problems[0] and "2.0" in problems[0]


def test_perf_floor_missing_metric_detected():
    with open(framework_lint.EVIDENCE_PATH) as f:
        evidence = json.load(f)
    del evidence["graphs"]["serve_decode"]["kv_bytes_per_step"][
        "bytes_reduction_x_at_typical_fill"]
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "HLO_EVIDENCE.json")
        with open(path, "w") as f:
            json.dump(evidence, f)
        problems = framework_lint.check_perf_floors(path)
    assert len(problems) == 1
    assert "serve_decode KV-bytes reduction" in problems[0]
    assert "missing" in problems[0]


def test_perf_floor_missing_or_corrupt_file_detected():
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "HLO_EVIDENCE.json")
        problems = framework_lint.check_perf_floors(path)
        assert len(problems) == 1 and "not found" in problems[0]
        with open(path, "w") as f:
            f.write("{broken")
        problems = framework_lint.check_perf_floors(path)
        assert len(problems) == 1 and "not valid JSON" in problems[0]


def test_perf_floor_null_metric_detected():
    """Review fix: a legitimately-null JSON leaf must NOT slip through
    the missing-key guard — it is a non-numeric violation."""
    with open(framework_lint.EVIDENCE_PATH) as f:
        evidence = json.load(f)
    evidence["graphs"]["pipeline_scan_megastep"]["dispatch_model"][
        "dispatch_reduction_x"] = None
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "HLO_EVIDENCE.json")
        with open(path, "w") as f:
            json.dump(evidence, f)
        problems = framework_lint.check_perf_floors(path)
    assert len(problems) == 1
    assert "scan-fused dispatch reduction" in problems[0]
    assert "non-numeric" in problems[0]


def test_pp_schedule_report_registered_and_green():
    """ISSUE 11 satellite: the pipeline-schedule report was the only
    pipeline tool outside the lint net — its self_check now pins the
    report's mesh/microbatch constants against pipeline.py's schedule
    accounting and the stage-cut planner's objective knobs."""
    import pp_schedule_report
    assert "pp_schedule_report" in framework_lint.TOOL_CROSS_CHECKS
    assert pp_schedule_report.self_check() == []


def test_spmd_plan_pipeline_json_schema(capsys):
    """The `spmd_plan --pipeline --json` schema is CI surface: key
    drift here breaks tier-1, same pin as the Megatron rediscovery."""
    import spmd_plan
    assert spmd_plan.main(["--pipeline", "--json", "--tp", "1"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["ok"] is True
    assert set(payload) >= {
        "axis", "bubble", "cuts", "diagnostics", "evaluations",
        "expert", "frontier_bytes_per_tick", "hand", "inner", "mesh",
        "num_micro", "num_stages", "num_virtual", "objective", "ok",
        "schedule", "stages", "wire"}
    assert payload["axis"] == "pp"
    assert payload["num_stages"] == 4
    assert payload["schedule"] == "1f1b"
    assert len(payload["stages"]) == 4
    for stage in payload["stages"]:
        assert set(stage) == {"stage", "op_range", "flops", "hbm_peak",
                              "param_bytes", "diagnostics"}
        assert stage["diagnostics"] == 0
    assert set(payload["wire"]) == {"kind", "axis", "count",
                                    "bytes_per_tick", "total_bytes"}
    assert payload["wire"]["kind"] == "ppermute"
    assert payload["hand"]["objective"] >= payload["objective"]
    # a second run serializes identically (stability contract)
    assert spmd_plan.main(["--pipeline", "--json", "--tp", "1"]) == 0
    assert json.loads(capsys.readouterr().out) == payload


def test_spmd_plan_pipeline_ep_prices_all_to_all(capsys):
    """An ep-mesh MoE plan must place experts and price the all-to-all
    dispatch/combine wire in the report (golden acceptance)."""
    import spmd_plan
    assert spmd_plan.main(["--pipeline", "--json", "--tp", "1",
                           "--pp", "2", "--ep", "2"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["ok"] is True
    assert payload["expert"]["axis"] == "ep"
    assert payload["expert"]["all_to_all_count"] > 0
    assert payload["expert"]["all_to_all_bytes"] > 0
    assert any("w_up" in t for t in payload["expert"]["rules"])


def test_traffic_determinism_lint_detects_and_pragma_suppresses():
    src = textwrap.dedent("""
        import random
        import time

        import numpy as np


        def bad_clock():
            return time.time()


        def bad_stdlib():
            return random.uniform(0, 1)


        def bad_global_numpy():
            return np.random.rand(3)


        def bad_unseeded_ctor():
            return np.random.RandomState()


        def allowed():
            t = time.perf_counter()
            time.sleep(0)
            rng = np.random.RandomState(7)
            waived = np.random.rand()  # lint: traffic-determinism-ok
            return t, rng, waived
    """)
    with tempfile.TemporaryDirectory() as tmp:
        with open(os.path.join(tmp, "mod.py"), "w") as f:
            f.write(src)
        problems = framework_lint.check_traffic_determinism(tmp)
    assert any("time.time()" in p for p in problems), problems
    assert any("random.uniform" in p for p in problems), problems
    assert any("np.random.rand" in p for p in problems), problems
    assert any("np.random.RandomState" in p and "seed" in p
               for p in problems), problems
    # exactly the four violations: perf_counter/sleep/seeded-ctor are
    # allowed and the pragma'd global draw is waived
    assert len(problems) == 4, problems


def test_traffic_lab_itself_is_deterministic():
    assert framework_lint.check_traffic_determinism() == []


def test_tool_registry_completeness_detected():
    with tempfile.TemporaryDirectory() as tmp:
        with open(os.path.join(tmp, "rogue_tool.py"), "w") as f:
            f.write("def self_check():\n    return []\n")
        with open(os.path.join(tmp, "no_check_tool.py"), "w") as f:
            f.write("def main():\n    return 0\n")
        problems = framework_lint.check_tool_registry(tmp)
    assert any("rogue_tool" in p and "TOOL_CROSS_CHECKS" in p
               for p in problems), problems
    assert not any("no_check_tool" in p for p in problems), problems


def test_tool_registry_repo_is_complete():
    assert framework_lint.check_tool_registry() == []


def test_doc_command_to_a_retired_entry_point_detected():
    assert framework_lint.check_doc_commands() == []      # the repo itself
    with tempfile.TemporaryDirectory() as tmp:
        os.makedirs(os.path.join(tmp, "docs"))
        for name, text in (("README.md", "run `python3 kept.py --x`\n"),
                           ("BASELINE.md", "no command here\n"),
                           ("docs/ops.md", "then `ENV=1 python gone.py`\n"),
                           ("kept.py", "")):
            with open(os.path.join(tmp, name), "w") as f:
                f.write(text)
        problems = framework_lint.check_doc_commands(tmp)
    assert len(problems) == 1 and "docs/ops.md:1" in problems[0], problems
