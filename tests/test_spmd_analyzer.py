"""SPMD sharding analyzer (ISSUE 3 tentpole).

Golden paths: the GPT tensor-parallel config must resolve a spec for
every var with ZERO diagnostics and exactly the expected collective set
(qkv column-parallel -> out-proj row-parallel -> one all-reduce per
chain, one per MLP down-proj, one vocab-parallel embedding gather), and
the per-device HBM estimate must shrink accordingly.

Negative corpus: one deliberately broken program per diagnostic in
DIAGNOSTIC_CODES (mirroring the PR-1 verifier corpus), plus the
PADDLE_TPU_VERIFY_SPMD hook failing compilation BEFORE jit.
"""
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn, ops, static
from paddle_tpu.core import monitor
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.distributed import sharding
from paddle_tpu.static import spmd_analyzer as spmd
from paddle_tpu.static.spmd_analyzer import (DIAGNOSTIC_CODES,
                                             SpmdLintError,
                                             analyze_params,
                                             analyze_program)

MESH = {"dp": 2, "tp": 2}


@pytest.fixture()
def static_mode():
    paddle.enable_static()
    yield
    paddle.disable_static()


@pytest.fixture()
def tp_mesh():
    """A registered tp=2 mesh that IS the default for the test's
    duration (the VERIFY_SPMD hook reads the default mesh), restoring
    whatever default another test left behind."""
    with mesh_mod._lock:
        old = mesh_mod._default_name
    m = mesh_mod.init_mesh({"tp": 2}, name="_spmd_hook_test")
    mesh_mod.set_mesh(m, "_spmd_hook_test")
    yield m
    mesh_mod.reset_mesh("_spmd_hook_test")
    with mesh_mod._lock:
        if old in mesh_mod._meshes:
            mesh_mod._default_name = old


def _linear_program(in_f=8, out_f=4, batch=4):
    main = static.Program("lin")
    with static.program_guard(main):
        x = static.data("x", [batch, in_f], "float32")
        net = nn.Linear(in_f, out_f)
        y = net(x)
    main._jit_fetch_vars = [y]
    return main, net, y


# ---------------------------------------------------------------------------
# golden paths
# ---------------------------------------------------------------------------

def _gpt_program(layers=2):
    from paddle_tpu.text.models.gpt import GPT, GPTConfig
    main = static.Program("gpt")
    with static.program_guard(main):
        ids = static.data("input_ids", [2, 16], "int64")
        net = GPT(GPTConfig(vocab_size=1024, hidden_size=64,
                            num_layers=layers, num_heads=2,
                            intermediate_size=128, max_seq_len=32))
        logits = net(ids)
    main._jit_fetch_vars = [logits]
    return main, net, logits


def test_gpt_tp_golden_path(static_mode):
    layers = 2
    main, net, logits = _gpt_program(layers)
    specs = sharding.named_param_specs(net, {"tp": 2})
    rep = analyze_program(main, mesh={"tp": 2}, param_specs=specs)

    assert rep.diagnostics == [], "\n".join(str(d) for d in rep.diagnostics)
    # every var resolved a spec
    for op in main.ops:
        for oid in op.out_ids:
            assert oid in rep.specs
    ar = [c for c in rep.collectives if c.kind == "all_reduce"]
    # 1 vocab-parallel embedding gather + per block: out-proj + fc2
    assert len(ar) == 2 * layers + 1
    assert all(c.axis == "tp" for c in ar)
    assert ar[0].op_name == "embedding"
    assert all(c.op_name == "matmul" for c in ar[1:])
    # no resharding anywhere, and nothing else on the wire
    assert [c for c in rep.collectives if c.kind != "all_reduce"] == []
    # tied LM head stays column-parallel: logits sharded on vocab
    assert rep.spec_of(logits) == ((), (), ("tp",))
    # per-device HBM strictly below the replicated estimate
    assert rep.hbm["peak_bytes"] < rep.hbm_replicated["peak_bytes"]
    assert rep.hbm["param_bytes"] < rep.hbm_replicated["param_bytes"]


def test_gpt_block_qkv_column_then_rowparallel_one_allreduce(static_mode):
    """The attention chain: qkv column-parallel produces NO collective;
    the row-parallel out-proj implies exactly one all-reduce."""
    from paddle_tpu.text.models.gpt import GPTBlock, GPTConfig
    main = static.Program("blk")
    with static.program_guard(main):
        x = static.data("x", [2, 16, 64], "float32")
        blk = GPTBlock(GPTConfig.tiny())
        y = blk(x)
    main._jit_fetch_vars = [y]
    specs = sharding.named_param_specs(blk, {"tp": 2})
    rep = analyze_program(main, mesh={"tp": 2}, param_specs=specs)
    assert rep.diagnostics == [], "\n".join(str(d) for d in rep.diagnostics)
    ar = [c for c in rep.collectives if c.kind == "all_reduce"]
    assert len(ar) == 2  # attn out-proj + mlp fc2
    assert all(c.axis == "tp" and c.op_name == "matmul" for c in ar)
    # the FIRST matmul (qkv column-parallel) implied nothing: both
    # all-reduces come later in the op list
    first_mm = next(i for i, op in enumerate(main.ops)
                    if op.name == "matmul")
    assert all(c.op_index > first_mm for c in ar)
    # block output is replicated (ready for the residual stream)
    assert rep.spec_of(y) == ((), (), ())


def test_dp_batch_sharding_propagates(static_mode):
    main, net, y = _linear_program()
    rep = analyze_program(main, mesh=MESH, data_specs={"x": P("dp")})
    assert rep.diagnostics == []
    assert rep.collectives == []  # pure DP forward: no comm implied
    assert rep.spec_of(y)[0] == ("dp",)


def test_analyze_params_dygraph_gpt():
    from paddle_tpu.text.models.gpt import GPT, GPTConfig
    layers = 2
    net = GPT(GPTConfig.tiny())
    rep = analyze_params(dict(net.named_parameters()), mesh={"tp": 2},
                         tokens_per_step=2 * 16)
    assert rep.diagnostics == []
    ar = [c for c in rep.collectives if c.kind == "all_reduce"]
    assert len(ar) == 2 * layers + 1  # out_proj + fc2 per block, + wte
    assert all(c.axis == "tp" for c in ar)
    assert all(c.bytes > 0 for c in ar)
    # per-device param bytes beat full replication
    full = sum(int(np.prod(p.shape)) * 4 for _, p in net.named_parameters())
    assert rep.hbm["param_bytes"] < full


# ---------------------------------------------------------------------------
# the broken corpus: one program per diagnostic
# ---------------------------------------------------------------------------

def test_corpus_unbound_axis(static_mode):
    main, net, _ = _linear_program()
    rep = analyze_program(main, mesh=MESH, param_specs={
        net.weight.scope_name: P("mp", None)})
    assert [d.code for d in rep.diagnostics] == ["unbound-axis"]
    d = rep.diagnostics[0]
    assert d.axis == "mp" and d.var == net.weight.scope_name
    assert "mp" in d.message and "dp" in d.message


def test_corpus_duplicate_axis(static_mode):
    main, net, _ = _linear_program()
    rep = analyze_program(main, mesh=MESH, param_specs={
        net.weight.scope_name: P("tp", "tp")})
    assert "duplicate-axis" in [d.code for d in rep.diagnostics]
    d = next(x for x in rep.diagnostics if x.code == "duplicate-axis")
    assert d.axis == "tp"


def test_corpus_non_divisible(static_mode):
    main, net, _ = _linear_program(in_f=7)
    rep = analyze_program(main, mesh=MESH, param_specs={
        net.weight.scope_name: P("tp", None)})
    assert [d.code for d in rep.diagnostics] == ["non-divisible"]
    assert "7" in rep.diagnostics[0].message


def test_corpus_spec_rank(static_mode):
    main, net, _ = _linear_program()
    rep = analyze_program(main, mesh=MESH, param_specs={
        net.bias.scope_name: P(None, "tp")})
    assert [d.code for d in rep.diagnostics] == ["spec-rank"]
    assert net.bias.scope_name == rep.diagnostics[0].var


def test_corpus_reshard_one_sided_contraction(static_mode):
    """A column-parallel activation fed into a replicated weight: the
    contraction dim is sharded on one operand only — implicit all-gather,
    reported with its byte cost."""
    main, net, _ = _linear_program()
    rep = analyze_program(main, mesh=MESH, data_specs={"x": P(None, "tp")})
    assert [d.code for d in rep.diagnostics] == ["reshard"]
    ag = [c for c in rep.collectives if c.kind == "all_gather"]
    assert len(ag) == 1 and ag[0].axis == "tp"
    assert ag[0].bytes == 4 * 8 * 4  # the gathered activation, f32


def test_corpus_collective_divergence_across_cond(static_mode):
    main = static.Program("cf")
    with static.program_guard(main):
        x = static.data("x", [4, 8], "float32")
        yv = static.data("y", [4, 4], "float32")
        w = nn.Linear(8, 4, bias_attr=False)
        pred = ops.less_than(ops.sum(yv), ops.full([], 100.0, "float32"))
        out = static.nn.cond(pred, lambda: ops.matmul(x, w.weight),
                             lambda: ops.exp(yv))
    main._jit_fetch_vars = [out]
    rep = analyze_program(main, mesh=MESH,
                          param_specs={w.weight.scope_name: P("tp", None)},
                          data_specs={"x": P(None, "tp")})
    codes = [d.code for d in rep.diagnostics]
    assert "collective-divergence" in codes
    d = next(x for x in rep.diagnostics
             if x.code == "collective-divergence")
    assert d.op_name == "cond" and "all_reduce" in d.message


def test_corpus_reshard_contraction_on_different_axes(static_mode):
    """Contraction sharded on DIFFERENT axes on each operand: both sides
    must be gathered (and counted) — the output cannot be replicated for
    free."""
    main = static.Program("xx")
    with static.program_guard(main):
        x = static.data("x", [4, 8], "float32")
        w = nn.Linear(8, 4, bias_attr=False)
        y = ops.matmul(x, w.weight)
    main._jit_fetch_vars = [y]
    rep = analyze_program(main, mesh=MESH,
                          param_specs={w.weight.scope_name: P("tp", None)},
                          data_specs={"x": P(None, "dp")})
    assert [d.code for d in rep.diagnostics] == ["reshard"]
    assert "DIFFERENT axes" in rep.diagnostics[0].message
    ag = sorted(c.axis for c in rep.collectives if c.kind == "all_gather")
    assert ag == ["dp", "tp"]  # BOTH operands gathered, both counted


def test_while_loop_with_literal_carry_and_inner_collective(static_mode):
    """A plain-int loop carry must not crash propagation, and a
    row-parallel matmul inside the body is counted once with a
    path-qualified op name."""
    main = static.Program("wl")
    with static.program_guard(main):
        x = static.data("x", [4, 8], "float32")
        w = nn.Linear(8, 8, bias_attr=False)
        n = ops.full([], 3, "int32")
        _, acc = static.nn.while_loop(
            lambda i, a: ops.less_than(i, n),
            lambda i, a: (i + 1, ops.matmul(a, w.weight)),
            [ops.zeros([], "int32"), x])
    main._jit_fetch_vars = [acc]
    rep = analyze_program(main, mesh=MESH,
                          param_specs={w.weight.scope_name: P("tp", None)},
                          data_specs={"x": P(None, "tp")})
    assert rep.diagnostics == []
    ar = [c for c in rep.collectives if c.kind == "all_reduce"]
    assert len(ar) == 1 and ar[0].axis == "tp"
    assert "while_loop#" in ar[0].op_name and "body" in ar[0].op_name


def test_corpus_covers_every_diagnostic_code():
    """Meta-test: the suite above exercises the full catalogue."""
    import inspect
    import sys
    src = inspect.getsource(sys.modules[__name__])
    for code in DIAGNOSTIC_CODES:
        assert f'"{code}"' in src or f"'{code}'" in src


# ---------------------------------------------------------------------------
# the PADDLE_TPU_VERIFY_SPMD hook + monitor gauges
# ---------------------------------------------------------------------------

def test_verify_spmd_env_flag(monkeypatch):
    spmd.set_verify_spmd(None)
    monkeypatch.setenv("PADDLE_TPU_VERIFY_SPMD", "0")
    assert not spmd.verify_spmd_enabled()
    monkeypatch.setenv("PADDLE_TPU_VERIFY_SPMD", "1")
    assert spmd.verify_spmd_enabled()


def test_hook_fails_compilation_before_jit(static_mode, tp_mesh,
                                           monkeypatch):
    """An injected unbound-axis/non-divisible spec must raise at the
    Executor's compile step — before lowering — not at run time."""
    monkeypatch.setenv("PADDLE_TPU_VERIFY_SPMD", "1")
    for bad, code in ((P("mp", None), "unbound-axis"),
                      (P("tp", None), "non-divisible")):
        main, net, y = _linear_program(in_f=7)
        main.spmd_param_specs = {net.weight.scope_name: bad}
        exe = static.Executor()
        before = monitor.stat_get("executor/lowerings")
        with pytest.raises(SpmdLintError) as e:
            exe.run(main, feed={"x": np.ones((4, 7), "float32")},
                    fetch_list=[y])
        assert e.value.code == code
        # nothing was lowered: the finding preceded jit compilation
        assert monitor.stat_get("executor/lowerings") == before


def test_hook_in_apply_pass(static_mode, tp_mesh, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_VERIFY_SPMD", "1")
    main, net, _ = _linear_program()
    main.spmd_param_specs = {net.weight.scope_name: P("zz", None)}
    from paddle_tpu.static.passes import apply_pass
    with pytest.raises(SpmdLintError, match="unbound-axis"):
        apply_pass(main, "eliminate_dead_ops")


def test_hook_clean_program_passes_and_publishes_gauges(static_mode,
                                                        tp_mesh,
                                                        monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_VERIFY_SPMD", "1")
    main, net, y = _linear_program()
    main.spmd_param_specs = {
        net.weight.scope_name: P(None, "tp"),
        net.bias.scope_name: P("tp")}
    exe = static.Executor()
    (out,) = exe.run(main, feed={"x": np.ones((4, 8), "float32")},
                     fetch_list=[y])
    assert out.shape == (4, 4)
    gauges = monitor.stats("spmd.")
    assert gauges["spmd.hbm_estimate"] > 0
    assert gauges["spmd.resharding_count"] == 0


def test_gauges_reflect_collective_bytes(static_mode):
    main, net, _ = _linear_program()
    rep = analyze_program(main, mesh=MESH, param_specs={
        net.weight.scope_name: P("tp", None)},
        data_specs={"x": P(None, "tp")})  # row-parallel TP: one all-reduce
    assert rep.diagnostics == []
    rep.publish()
    assert monitor.stat_get("spmd.collective_bytes") \
        == rep.collective_bytes() > 0


# ---------------------------------------------------------------------------
# satellites: sharding._validate_divisible, MeshGuard, in_spmd_region,
# pipeline schedule accounting
# ---------------------------------------------------------------------------

def test_validate_divisible_counts_and_rejects_long_specs():
    import jax
    mesh = mesh_mod.init_mesh({"dp": 2}, name="vd_test")
    try:
        before = monitor.stat_get("sharding.nondivisible_fallback")
        spec = sharding._validate_divisible(P("dp"), (5,), mesh)
        assert tuple(spec) == (None,)  # fallback preserved...
        assert monitor.stat_get("sharding.nondivisible_fallback") \
            == before + 1  # ...but no longer silent
        # divisible dims don't count
        spec = sharding._validate_divisible(P("dp"), (6,), mesh)
        assert tuple(spec) == ("dp",)
        assert monitor.stat_get("sharding.nondivisible_fallback") \
            == before + 1
        # a spec longer than the tensor's rank used to be zip-truncated
        with pytest.raises(ValueError, match="entries"):
            sharding._validate_divisible(P(None, "dp"), (6,), mesh,
                                         name="w")
    finally:
        mesh_mod.reset_mesh("vd_test")


def test_meshguard_without_mesh_names_registry():
    mesh_mod.reset_mesh("definitely_absent")
    with pytest.raises(RuntimeError) as e:
        mesh_mod.MeshGuard(name="definitely_absent").__enter__()
    msg = str(e.value)
    assert "definitely_absent" in msg and "init_mesh" in msg


def test_meshguard_with_mesh_still_works():
    m = mesh_mod.init_mesh({"dp": 1}, name="mg_ok")
    try:
        with mesh_mod.MeshGuard(name="mg_ok") as got:
            assert got is m
    finally:
        mesh_mod.reset_mesh("mg_ok")


def _probe_spmd_region():
    import jax
    from jax.sharding import Mesh
    import jax.numpy as jnp
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    seen = {}

    def f():
        seen["dp"] = mesh_mod.in_spmd_region("dp")
        seen["zz"] = mesh_mod.in_spmd_region("zz")
        seen["any"] = mesh_mod.in_spmd_region()
        return jnp.zeros(())

    jax.jit(mesh_mod.shard_map(f, mesh=mesh, in_specs=(), out_specs=P()))()
    return seen


def test_in_spmd_region():
    assert not mesh_mod.in_spmd_region("dp")  # outside any shard_map
    seen = _probe_spmd_region()
    assert seen == {"dp": True, "zz": False, "any": True}


def test_pipeline_schedule_collectives():
    from paddle_tpu.distributed.pipeline import (schedule_collectives,
                                                 schedule_ticks)
    pc = schedule_collectives(8, 4, hidden_bytes=1024)
    assert pc["kind"] == "ppermute" and pc["axis"] == "pp"
    assert pc["count"] == schedule_ticks(8, 4) == 11
    assert pc["total_bytes"] == 11 * 1024
    pcv = schedule_collectives(8, 4, 1024, schedule="interleaved",
                               num_virtual=2)
    assert pcv["count"] == 2 * 8 + 4 - 1


# ---------------------------------------------------------------------------
# satellites (ISSUE 10): collective dtype dimension + quantized savings,
# cross-dim duplicate-axis pricing, add_tp_rule callable/rank validation
# ---------------------------------------------------------------------------

def test_collective_dtype_recorded_and_bytes_if():
    """Every collective carries its wire dtype; bytes_if re-prices the
    payload under a narrower cast (the EQuARX quantized seam)."""
    paddle.enable_static()
    try:
        main, net, _ = _linear_program()
        rep = analyze_program(main, mesh=MESH, param_specs={
            net.weight.scope_name: P("tp", None)},
            data_specs={"x": P(None, "tp")})  # row-parallel: 1 all-reduce
        assert rep.diagnostics == []
        (ar,) = [c for c in rep.collectives if c.kind == "all_reduce"]
        assert ar.dtype == "float32" and ar.is_float
        assert ar.bytes_if("int8") == ar.bytes // 4
        assert ar.bytes_if("float16") == ar.bytes // 2
        assert ar.bytes_if("float32") == ar.bytes
        # fp8 wire dtypes live in ml_dtypes, not numpy proper — the
        # EQuARX fp8 seam must price, not TypeError out of np.dtype
        assert ar.bytes_if("float8_e4m3fn") == ar.bytes // 4
        assert ar.bytes_if("float8_e5m2") == ar.bytes // 4
    finally:
        paddle.disable_static()


def test_quantized_savings_per_axis_in_render(static_mode):
    from paddle_tpu.text.models.gpt import GPT, GPTConfig
    main = static.Program("q")
    with static.program_guard(main):
        ids = static.data("input_ids", [2, 16], "int64")
        net = GPT(GPTConfig.tiny())
        logits = net(ids)
    main._jit_fetch_vars = [logits]
    specs = sharding.named_param_specs(net, {"tp": 2})
    rep = analyze_program(main, mesh={"tp": 2}, param_specs=specs)
    savings = rep.quantized_savings("int8")
    assert set(savings) == {"tp"}
    row = savings["tp"]
    assert row["bytes"] == rep.collective_bytes() > 0
    assert row["bytes_quantized"] == row["bytes"] // 4  # all-f32 wire
    assert row["saved"] == row["bytes"] - row["bytes_quantized"]
    out = rep.render()
    assert "int8/fp8 quantized collectives would save" in out
    assert f"saves {row['saved']} B" in out


def test_matmul_output_axis_collision_is_priced(static_mode):
    """dp-sharded batch meeting a dp-column-sharded weight: the axis
    cannot shard two output dims — must surface as a PRICED reshard,
    not a silently free drop (the planner would otherwise exploit it)."""
    main, net, y = _linear_program()
    rep = analyze_program(main, mesh=MESH, param_specs={
        net.weight.scope_name: P(None, "dp")},
        data_specs={"x": P("dp")})
    assert "reshard" in [d.code for d in rep.diagnostics]
    d = next(x for x in rep.diagnostics if x.code == "reshard")
    assert "cannot shard two" in d.message and d.axis == "dp"
    ag = [c for c in rep.collectives if c.kind == "all_gather"]
    assert len(ag) == 1 and ag[0].axis == "dp" and ag[0].bytes > 0
    # batch keeps dp; the weight's column sharding lost
    assert rep.spec_of(y) == (("dp",), ())


def test_embedding_vocab_axis_colliding_with_ids_is_priced(static_mode):
    main = static.Program("emb")
    with static.program_guard(main):
        ids = static.data("ids", [4, 8], "int64")
        emb = nn.Embedding(16, 6)
        out = emb(ids)
    main._jit_fetch_vars = [out]
    rep = analyze_program(main, mesh=MESH, param_specs={
        emb.weight.scope_name: P("dp", None)},
        data_specs={"ids": P("dp")})
    codes = [d.code for d in rep.diagnostics]
    assert codes == ["reshard"]
    assert "vocab-sharded" in rep.diagnostics[0].message
    ag = [c for c in rep.collectives if c.kind == "all_gather"]
    assert len(ag) == 1 and ag[0].axis == "dp"
    assert [c for c in rep.collectives if c.kind == "all_reduce"] == []


def test_add_tp_rule_accepts_callable_and_validates_rank():
    meshlike = sharding.mesh_like({"tp": 2})
    # a callable rule serves multiple ranks from one template
    sharding.add_tp_rule(r"my_head\.weight$",
                         lambda ndim: P(*([None] * (ndim - 1) + ["tp"])))
    try:
        assert sharding.param_spec_for("my_head.weight", 2, meshlike) \
            == P(None, "tp")
        assert sharding.param_spec_for("my_head.weight", 3, meshlike) \
            == P(None, None, "tp")
    finally:
        assert sharding.remove_tp_rule(r"my_head\.weight$") == 1
    # a fixed over-rank spec fails AT MATCH TIME, naming the rule —
    # not as a spec-rank crash downstream
    sharding.add_tp_rule(r"tiny\.bias$", P("tp", None))
    try:
        with pytest.raises(ValueError, match="rank-1 param 'tiny.bias'"):
            sharding.param_spec_for("tiny.bias", 1, meshlike)
        # matching rank still works
        assert sharding.param_spec_for("tiny.bias", 2, meshlike) \
            == P("tp", None)
    finally:
        assert sharding.remove_tp_rule(r"tiny\.bias$") == 1


# ---------------------------------------------------------------------------
# two-tier topology: per-tier pricing + the cross-tier diagnostic
# ---------------------------------------------------------------------------

TIERED_MESH = {"pod": {"size": 2, "tier": "dcn"}, "dp": 2, "tp": 2}


def _tiered_gpt(batch=4):
    from paddle_tpu.text.models.gpt import GPT, GPTConfig
    main = static.Program("gpt_tiered")
    with static.program_guard(main):
        ids = static.data("input_ids", [batch, 16], "int64")
        net = GPT(GPTConfig(vocab_size=1024, hidden_size=64,
                            num_layers=2, num_heads=2,
                            intermediate_size=128, max_seq_len=32))
        logits = net(ids)
    main._jit_fetch_vars = [logits]
    return main, net, logits


def test_tiered_mesh_prices_collectives_per_link(static_mode):
    """Declaring link tiers adds tier/cost_us to every collective and a
    per-tier wire-bytes rollup; the good layout (tp intra-pod, batch
    DCN-major on (pod, dp)) carries ZERO diagnostics — the loss-free
    pure-dp crossing is exempt from cross-tier by design."""
    main, net, _ = _tiered_gpt()
    specs = sharding.named_param_specs(net, TIERED_MESH)
    rep = spmd.analyze_program(main, mesh=TIERED_MESH, param_specs=specs,
                               data_specs={"input_ids": P(("pod", "dp"))})
    assert rep.diagnostics == []
    assert rep.mesh_tiers["pod"]["tier"] == "dcn"
    assert rep.mesh_tiers["tp"]["tier"] == "ici"
    ars = [c for c in rep.collectives if c.kind == "all_reduce"]
    assert ars and all(c.tier == "ici" for c in ars)  # tp stays intra-pod
    assert all(c.cost_us > 0 for c in ars)
    tiers = rep.tier_bytes()
    assert tiers.get("ici", 0) == sum(c.bytes for c in rep.collectives
                                      if c.tier == "ici")
    assert "link tiers: pod=dcn" in rep.render()


def test_cross_tier_diagnostic_for_model_parallel_on_dcn(static_mode):
    """A persistable sharded over the slow axis (model parallelism
    crossing pods) raises cross-tier, naming op/var/axis; the same
    layout on a flat mesh does not."""
    main, net, _ = _tiered_gpt()
    specs = sharding.named_param_specs(net, TIERED_MESH)
    specs[net.wte.weight.scope_name] = P("pod", None)  # vocab over DCN
    rep = spmd.analyze_program(main, mesh=TIERED_MESH, param_specs=specs,
                               data_specs={"input_ids": P("dp")})
    xt = [d for d in rep.diagnostics if d.code == "cross-tier"]
    assert xt and xt[0].axis == "pod" and xt[0].var
    assert "slow-tier" in xt[0].message
    # flat mesh, same shapes: no tiers -> no cross-tier, identical render
    flat = {"pod": 2, "dp": 2, "tp": 2}
    rep2 = spmd.analyze_program(main, mesh=flat, param_specs=specs,
                                data_specs={"input_ids": P("dp")})
    assert rep2.mesh_tiers == {}
    assert [d for d in rep2.diagnostics if d.code == "cross-tier"] == []
    assert "link tiers" not in rep2.render()


def test_hierarchical_sync_wire_model(static_mode):
    """The dp gradient-sync pricing: hierarchical ships exactly 1/n of
    the flat inter-pod bytes (n = intra-pod dp size); localsgd divides
    the whole sync by k; the recommendation follows the cost ratio."""
    main, net, _ = _tiered_gpt()
    specs = sharding.named_param_specs(net, TIERED_MESH)
    rep = spmd.analyze_program(main, mesh=TIERED_MESH, param_specs=specs,
                               data_specs={"input_ids": P(("pod", "dp"))})
    B = 4096
    gs = rep.hierarchical_sync(grad_bytes=B)
    assert gs["inner"] == {"axes": ["dp"], "size": 2}
    assert gs["outer"] == {"axes": ["pod"], "size": 2}
    ring = lambda b, s: int(2 * b * (s - 1) // s)  # noqa: E731
    sch = gs["schemes"]
    assert sch["flat"]["wire_bytes"] == {"ici": ring(B, 2),
                                         "dcn": ring(B, 2)}
    assert sch["hierarchical"]["wire_bytes"] == {"ici": ring(B, 2),
                                                 "dcn": ring(B // 2, 2)}
    assert sch["localsgd"]["wire_bytes"]["dcn"] == ring(B, 2) // 4
    assert gs["inter_pod_reduction_x"] == 2.0
    assert gs["recommendation"] == "hierarchical"
    # per-step DCN cost dominates ICI by the bandwidth gap / shard ratio
    assert sch["flat"]["cost_us"]["dcn"] > sch["flat"]["cost_us"]["ici"]
    # flat mesh: nothing to decompose
    rep2 = spmd.analyze_program(main, mesh={"dp": 2, "tp": 2},
                                param_specs=sharding.named_param_specs(
                                    net, {"dp": 2, "tp": 2}),
                                data_specs={"input_ids": P("dp")})
    assert rep2.hierarchical_sync() is None
