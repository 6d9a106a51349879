"""chip_smoke.py rehearsed on the CPU: its phase functions at toy size
(Pallas kernels interpreted), its refusal to run off-TPU, and the two
no-fallback guarantees its chip run leans on — autotune never measures
under a trace, and run_guarded never swallows a kernel's error."""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import monitor

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402  (repo root)

SIZES = chip_smoke.Sizes.toy()


@pytest.fixture(autouse=True)
def _no_leaked_mesh():
    # an earlier test's default mesh would turn the one-chip train phase
    # into a sharded one
    from paddle_tpu.distributed import mesh as mesh_mod
    mesh_mod.reset_mesh()
    yield
    mesh_mod.reset_mesh()


@pytest.fixture
def interpret():
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    yield
    paddle.set_flags({"FLAGS_pallas_interpret": False})


def _run(name, phase):
    result = chip_smoke.run_phase(name, phase, SIZES)
    assert result["ok"], result["failures"]
    return result


def test_train_phase_toy():
    result = _run("train", chip_smoke.train_phase)
    assert result["loss_end"] < result["loss_start"]
    assert result["param_dtypes"] == ["bfloat16"]
    assert result["compiles_after_step1"] == 0


def test_serve_phase_toy(interpret):
    result = _run("serve", chip_smoke.serve_phase)
    assert result["completed"] == SIZES.serve_requests == 6
    assert len(result["prefill_buckets"]) >= 2     # both length bands
    # a prefill's chunk through the kernel, a decode step through the
    # kernel that also writes its token (PR 47)
    assert monitor.stat_get("pallas.hit.paged_decode_attention") > 0
    assert monitor.stat_get("pallas.hit.paged_write_attend") > 0
    assert all(e <= chip_smoke.LOGITS_TOL
               for e in result["forced_logits_err"].values())
    # the toy hybrid (one period): served, and with the interpreted
    # kernels on the same logits as on their jnp forms
    hybrid = result["hybrid"]
    assert hybrid["completed"] == 3 and hybrid["state_bytes"] > 0
    assert all(hybrid["hits"].get(k) for k in chip_smoke.HYBRID_KERNELS)
    # one kernel that writes and attends, or the writer and the kernel
    # apart (the toy's blocks of less than 128 lanes; at the published
    # widths the head tile decides: the v5e compile below)
    assert chip_smoke.paged_step_engaged(hybrid["hits"])
    assert not chip_smoke.paged_step_engaged({"paged_decode_attention": 1})
    assert hybrid["logits_err"] <= chip_smoke.LOGITS_TOL
    # both latent nets' programs are reported; the toy's bucket of 32 is
    # less than a tile and runs whole (the full size's: the v5e compiles)
    latent = result["programs_latent"]
    assert set(latent) == {"decode", "prefill32", "scmoe_decode",
                           "scmoe_prefill32"}
    assert latent["prefill32"]["tiles"] == latent["scmoe_prefill32"][
        "tiles"] == 0
    assert (latent["decode"]["arenas"], latent["scmoe_decode"]["arenas"]) \
        == (2, 2)


def test_kernels_phase_toy(interpret):
    result = _run("kernels", chip_smoke.kernels_phase)
    assert result["interpreted"]
    assert set(result["errors_vs_jnp_reference"]) >= {
        "flash_causal", "flash_padding_bias", "fused_ce", "decode",
        "paged_decode_s1_blockpicked", "paged_write_attend",
        "paged_work_list", "latent_paged_decode", "grouped_expert_ffn", "grouped_paged_decode",
        "sink_paged_decode"}
    # the fused write against the writer and the kernel apart: bit for bit
    assert result["errors_vs_jnp_reference"]["paged_write_attend"] \
        == {"out": 0.0, "arenas": 0.0}
    # the list the multi-head kernel's grid ends with: every live pair
    # counted, the kernel over it against the jnp form, and the writing
    # form against the pair bit for bit, an idle slot and a full table in
    errs = result["errors_vs_jnp_reference"]["paged_work_list"]
    assert (errs["items"], errs["write_out"], errs["write_arenas"]) \
        == (0.0, 0.0, 0.0) and errs["out"] <= chip_smoke.KERNEL_TOL
    # the grouped-query form's two call sites: the pool's pages, the rings
    # (and again with keys deeper than values and sinks in the rings)
    for name in ("grouped_paged_decode", "sink_paged_decode"):
        assert set(result["errors_vs_jnp_reference"][name]) \
            == {"full", "ring"}
    # both nets' expert layers, a decode step and a bucket each
    assert set(result["errors_vs_jnp_reference"]["grouped_expert_ffn"]) \
        == {"share_t2", "share_t32", "scmoe_t2", "scmoe_t32"}
    # the toy's latent net through the interpreted kernel and without it
    assert set(result["errors_vs_jnp_reference"]["latent_paged_decode"]) \
        == {"out", "logits", "block_size"}


@pytest.mark.slow
def test_multichip_phase_toy():
    result = _run("multichip", chip_smoke.multichip_phase)
    assert result["dp2_tp2_sharded_params"] > 0
    assert result["dp4_dropout.local_draw"] == 1 + 4 * SIZES.multichip_layers
    assert result["dp4_dropout_step_variants"] == 1


def test_multichip_phase_toy_reports_the_local_draws():
    """The slow toy run above carries these keys too; here the dropout-on
    fit alone, fast enough for tier-1."""
    import dataclasses
    from paddle_tpu.distributed import mesh as mesh_mod
    mesh_mod.init_mesh({"dp": 4})
    cfg = dataclasses.replace(SIZES.bert, num_hidden_layers=1)
    report, failures = chip_smoke.dp4_dropout_fit(SIZES, cfg, 3)
    assert not failures, failures
    assert report["dp4_dropout.local_draw"] == 1 + 4      # sites, 1 layer
    assert report["dp4_dropout.local_draw_fallback.manual"] == 0
    assert report["dp4_dropout.local_draw_fallback.indivisible"] == 0
    assert report["dp4_dropout_step_variants"] == 1
    assert np.isfinite(report["dp4_dropout_loss"])


def test_dropout_fit_without_a_mesh_fails_the_report():
    import dataclasses
    cfg = dataclasses.replace(SIZES.bert, num_hidden_layers=1)
    report, failures = chip_smoke.dp4_dropout_fit(SIZES, cfg, 2)
    assert report["dp4_dropout.local_draw"] == 0
    assert len(failures) == 1 and "no dropout site" in failures[0]


def test_multichip_phase_states_its_skip(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a: [object()])
    result = chip_smoke.multichip_phase(SIZES)
    assert result == {"failures": [], "skip": "SKIP (1 device)"}


def test_failed_phase_is_reported_not_raised(capsys):
    def broken(sizes):
        raise RuntimeError("boom")

    result = chip_smoke.run_phase("broken", broken, SIZES)
    assert not result["ok"]
    assert "PHASE broken FAIL" in capsys.readouterr().out


def test_main_refuses_non_tpu(capsys):
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert "platform 'cpu'" in err
    for line in out.splitlines():       # no result line off-TPU
        assert not line.startswith("{") or "ok" not in json.loads(line)


def _compile_kernels_for_v5e():
    """Child-process body of the test below: libtpu compiles ahead of time
    for a v5e topology with no chip present."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops.pallas.decode_attention import (
        decode_attention, latent_paged_cut, latent_paged_decode_attention,
        paged_cut, paged_decode_attention, paged_write_attend,
        paged_write_attend_cut, paged_write_token)
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.fused_ce import fused_linear_cross_entropy
    from paddle_tpu.ops.pallas.grouped_ffn import grouped_ffn_cut
    from paddle_tpu.nn.layer import experts
    try:
        device = topologies.get_topology_desc("v5e:2x2", "tpu").devices[0]
    except Exception as e:  # environment without a usable libtpu
        print(f"NO-TOPOLOGY {type(e).__name__}: {e}")
        return
    sharding = SingleDeviceSharding(device)

    def compile_for_v5e(fn, *specs):
        specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
                 for shape, dtype in specs]
        jax.jit(fn).trace(*specs).lower(
            lowering_platforms=("tpu",)).compile()

    def flash_grads(causal):
        def loss(q, k, v, bias):
            out = flash_attention(q, k, v, causal=causal,
                                  bias=None if causal else bias)
            return out.astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))

    def ce_grads(h, w, b, y):
        return jax.grad(lambda *a: fused_linear_cross_entropy(*a, y).sum(),
                        argnums=(0, 1, 2))(h, w, b)

    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    paddle.set_flags({"FLAGS_pallas_force_compile": True})
    q = ((2, 4, 1024, 64), bf16)
    compile_for_v5e(flash_grads(True), q, q, q, ((2, 1024), f32))
    q = ((2, 4, 1000, 64), bf16)   # ragged keys under a padding bias
    compile_for_v5e(flash_grads(False), q, q, q, ((2, 1000), f32))
    compile_for_v5e(ce_grads, ((512, 256), bf16), ((1000, 256), bf16),
                    ((1000,), bf16), ((512,), i32))
    cache = ((2, 4, 512, 64), bf16)
    compile_for_v5e(decode_attention, ((2, 4, 1, 64), bf16), cache, cache,
                    ((2,), i32))
    for block in (128, 16):  # the serving size; a tests-only size
        arena = ((9, 4, 64, block), bf16)
        compile_for_v5e(paged_decode_attention, ((2, 4, 8, 64), bf16),
                        arena, arena, ((2, 4), i32), ((2,), i32))
        compile_for_v5e(paged_write_token, arena, ((2,), i32), ((2,), i32),
                        ((4, 64, 2), bf16))
        # one token a slot, written by the kernel that attends (PR 47):
        # whole 128-lane tiles only, which its gate holds it to
        cut = paged_write_attend_cut((2, 4, 1, 64), arena[0], arena[0], 4, 2)
        assert (cut is not None) == (block == 128), (block, cut)
        if cut:
            compile_for_v5e(
                paged_write_attend, ((2, 4, 1, 64), bf16), arena, arena,
                ((2, 4), i32), ((2,), i32), ((4, 64, 2), bf16),
                ((4, 64, 2), bf16))
    # GPT-2 XL's pool: a block's 25 heads, [1, 25, 64, 128], in one grid
    # step of the decode step; 5 head tiles under a 256-row prefill
    arena = ((225, 25, 64, 128), bf16)
    for (b, s), heads_per_step in (((32, 1), 25), ((1, 256), 5)):
        compile_for_v5e(paged_decode_attention, ((b, 25, s, 64), bf16),
                        arena, arena, ((b, 8), i32), ((b,), i32))
        cut = paged_cut((b, 25, s, 64), arena[0], 8, 2)
        assert cut["heads_per_step"] == heads_per_step, (b, s, cut)
    # the decode step that writes its 32 tokens: still 25 heads a step
    compile_for_v5e(
        paged_write_attend,
        ((32, 25, 1, 64), bf16), arena, arena, ((32, 8), i32), ((32,), i32),
        ((25, 64, 32), bf16), ((25, 64, 32), bf16))
    assert paged_write_attend_cut((32, 25, 1, 64), arena[0], arena[0], 8,
                                  2)["heads_per_step"] == 25
    # the writer picks its slot's lane out of the dense tokens [h, d, b]:
    # the cells' decode steps, 32 slots over GPT-2 XL's pool and 64 over
    # the Kimi share's one-head latent arena
    for b, arena in ((32, arena[0]), (64, (1025, 1, 576, 128))):
        compile_for_v5e(paged_write_token, (arena, bf16), ((b,), i32),
                        ((b,), i32), ((*arena[1:3], b), bf16))
    # the Kimi share's decode step over that arena: 64 heads the rows of
    # one product a block (a 576-row contraction, then p against the
    # first 512 rows with the lanes contracted), the 24-block table in
    # one grid step; and a table of 3 blocks
    for table in (24, 3):
        compile_for_v5e(
            lambda q, a, t, n: latent_paged_decode_attention(
                q, a, t, n, 0.1, 512),
            ((64, 64, 1, 576), bf16), (arena, bf16), ((64, table), i32),
            ((64,), i32))
    assert latent_paged_cut((64, 64, 1, 576), arena, 24, 2, 512) == {
        "blocks_per_step": 24, "grid_steps": 64, "live_bytes": 147456}
    # the two shares' expert layers, the plan with the kernel: the decode
    # step and the longest bucket the gate admits; a 1024-token prefill
    # is the gate's to reject (`tokens`: the loop wins there on the chip)
    for H, n, K, sizes in ((7168, 12, 8, (64, 512)),
                           (6144, 16, 12, (128, 512))):
        for T in sizes:
            compile_for_v5e(
                lambda *a: experts._grouped_expert_ffn(*a, 0),
                ((T, H), bf16), ((T, K), i32), ((T, K), f32),
                ((T,), jnp.bool_), ((n, H, 2048), bf16),
                ((n, H, 2048), bf16), ((n, 2048, H), bf16))
            cut = grouped_ffn_cut(T, K, n, H, 2048, experts.block_rows(T), 2)
            assert cut["tile_bytes"] == 3 * H * 256 * 2, (T, H, cut)
        assert not experts._grouped_kernel_eligible(
            jax.ShapeDtypeStruct((1024, H), bf16),
            jax.ShapeDtypeStruct((n, H, 2048), bf16))
    print("MOSAIC-OK")


def _run_in_cpu_child(body, ok):
    """Run this module's `body()` in a CPU child: libtpu's threads must
    not live in this process, which later forks DataLoader workers."""
    here = os.path.dirname(os.path.abspath(__file__))
    code = (f"import sys; sys.path[:0] = [{here!r}, {os.path.dirname(here)!r}]"
            f"; import test_chip_smoke as t; t.{body}()")
    r = subprocess.run([sys.executable, "-c", code],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    if "NO-TOPOLOGY" in r.stdout:
        pytest.skip(r.stdout.strip())
    assert ok in r.stdout, r.stdout + r.stderr[-3000:]
    return r.stdout


def _instruction_count(text):
    """Every instruction of every computation of a compiled program's
    text: what a PR that means to leave a program alone leaves alone."""
    return len(re.findall(r"^\s+(?:ROOT )?%?[\w.\-]+ = \S+ [\w\-]+\(",
                          text, re.M))


def _paged_list_calls(text):
    """Of a compiled program's `_paged_call_once` kernels (the multi-head
    paged kernel over its work list): how many there are, how many take a
    scalar before their scalar-prefetch operands (a DYNAMIC grid bound:
    the list's live count, read on the device), and the distinct
    (bound, lengths, slot, blk, phys) operand tuples: one, where the
    layers of a program share ONE computation of the list."""
    calls = re.findall(
        r"%_paged_call_once[.\d]* = [^\n]*? custom-call\(([^)]*)\)[^\n]*?"
        r"operand_layout_constraints=\{(s32\[\], )?", text)
    lists = {tuple(re.sub(r"/\*.*?\*/", "", operands).split(", ")[:5])
             for operands, _ in calls}
    return {"calls": len(calls),
            "dynamic_grid": sum(bool(scalar) for _, scalar in calls),
            "lists": len(lists)}


def _scope_summary(text):
    """What `core/program_map` makes of a program compiled for v5e: of the
    entry computation's `fusion` / `while` / Pallas custom-call
    instructions (the events that carry a decode step's device time), how
    many lie under each vocabulary word ("unscoped": under none), and the
    words each kernel's calls lie under."""
    from paddle_tpu.core import program_map
    ops = program_map.parse(text)["ops"]
    entry = re.search(r"^ENTRY .*?\{\n(.*?)^\}", text, re.S | re.M).group(1)
    words, kernels = {}, {}
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?(\S+) = .*? (fusion|while|custom-call)\(",
                     line)
        if not m or (m.group(2) == "custom-call"
                     and "tpu_custom_call" not in line):
            continue
        word = program_map.scope_of(ops.get(m.group(1))) or "unscoped"
        words[word] = words.get(word, 0) + 1
        if m.group(2) == "custom-call":
            kernel = re.sub(r"[.\d]+$", "", m.group(1))
            kernels[kernel] = sorted({word, *kernels.get(kernel, ())})
    return {"words": words, "kernels": kernels}


def _assert_scoped(step, must, kernels):
    """A decode step compiled for v5e names its work: at least 90 % of its
    top-level fusions, loops and kernels under a vocabulary word, the
    words its net must show, each kernel's calls under its word alone."""
    words = step["scopes"]["words"]
    total = sum(words.values())
    assert total - words.get("unscoped", 0) >= 0.9 * total, words
    assert must <= set(words), (must, words)
    assert step["scopes"]["kernels"] == kernels, step["scopes"]


def test_kernels_compile_under_mosaic_for_v5e():
    """What interpret mode cannot see is Mosaic itself — 64-bit index
    maps, layouts, block shapes, VMEM. Results still need the chip."""
    _run_in_cpu_child("_compile_kernels_for_v5e", "MOSAIC-OK")


def _compile_paged_steps_for_v5e():
    """Child-process body of the test below: two layers of write-and-
    attend (`paged_write_attend`, as `MultiHeadAttention` calls it) over
    donated arenas of the benchmark's pool shape, as a decode step and as
    a prefill, compiled for v5e; one JSON line a step says what the
    compiled program does to a buffer of arena shape."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.nn.kv_pool import KVBlockPool, paged_write_attend
    try:
        device = topologies.get_topology_desc("v5e:2x2", "tpu").devices[0]
    except Exception as e:  # environment without a usable libtpu
        print(f"NO-TOPOLOGY {type(e).__name__}: {e}")
        return
    sharding = SingleDeviceSharding(device)
    heads, d, table, bf16 = 25, 64, 8, jnp.bfloat16
    arena = KVBlockPool(224, 128).arena_shape(heads, d)

    def step(arenas, tables, lengths, q, k, v):
        out = []
        for ka, va in arenas:
            q, ka, va = paged_write_attend(q, ka, va, tables, lengths, k, v,
                                           d ** -0.5)
            out.append((ka, va))
        return out, q

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    paddle.set_flags({"FLAGS_pallas_force_compile": True})
    for b, s in ((32, 1), (1, 256)):
        monitor.reset(prefix="pallas.")
        kv = spec((b, s, heads, d), bf16)
        compiled = jax.jit(step, donate_argnums=(0,)).trace(
            [(spec(arena, bf16),) * 2] * 2, spec((b, table), jnp.int32),
            spec((b,), jnp.int32), spec((b, heads, s, d), bf16), kv, kv,
        ).lower(lowering_platforms=("tpu",)).compile()
        text = compiled.as_text()
        hits = monitor.stats("pallas.hit.")
        print("STEP " + json.dumps({
            "s": s,
            "arena_in_hlo": "[%s]" % ",".join(map(str, arena)) in text,
            "relayouts": chip_smoke.arena_relayouts(text, arena),
            "lane_padded": chip_smoke.lane_padded_results(text),
            "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
            "arena_bytes": int(np.prod(arena)) * 2,
            "attn": hits.get("pallas.hit.paged_decode_attention", 0),
            "writer": hits.get("pallas.hit.paged_write_token", 0),
            "write_attend": hits.get("pallas.hit.paged_write_attend", 0),
            "rejects": monitor.stats("pallas.gate_reject."),
            "kernels": sorted(set(re.findall(
                r"%(_paged_\w+?)[.\d]* = [^\n]*custom-call\(", text))),
            **{name: monitor.stat_get(
                f"pallas.{kernel}.{name}.b{b}s{s}")
               for kernel in (("paged_write_attend",) if s == 1
                              else ("paged_decode_attention",))
               for name in ("heads_per_step", "grid_steps", "write_bytes")},
            "paged_calls": _paged_list_calls(text),
            "instructions": _instruction_count(text)}))
    print("PAGED-STEPS-DONE")


def test_paged_serve_steps_hold_no_arena_copy_for_v5e():
    """The paged arena has ONE device layout: the pool's default layout,
    write_kv's in-place updates and the paged kernel's operand constraint
    agree, so a compiled decode step and a compiled prefill hold no copy
    or transpose of arena shape and no temp of arena size (PR 26: with
    [n, h, block, d] arenas each program copied each arena three times,
    76 % of a decode beat). A property of the compiled program, so it is
    held here, ahead of time for v5e, not sampled at run time."""
    out = _run_in_cpu_child("_compile_paged_steps_for_v5e",
                            "PAGED-STEPS-DONE")
    decode, prefill = (json.loads(line[5:]) for line in out.splitlines()
                       if line.startswith("STEP "))
    for step in (decode, prefill):
        assert step["arena_in_hlo"] and step["relayouts"] == [], step
        assert step["temp_bytes"] < step["arena_bytes"] // 10, step
        assert step["rejects"] == {}, step
        # nor an array one element a 128-lane row: the tokens go in dense
        # (the [32,25,64,1] row-major copy before each of the writer's
        # calls was 13 MB for 102 KB, 3.6 ms of a 15.7 ms step; PR 34)
        assert step["lane_padded"] == [], step
    # the decode step: ONE kernel a layer writes the tokens and attends
    # (PR 47): no call of the token writer is left in the program
    assert (decode["s"], decode["write_attend"], decode["attn"],
            decode["writer"]) == (1, 2, 0, 0), decode
    assert decode["kernels"] == ["_paged_call_once"], decode
    # the prefill: `write_kv`'s XLA loop, then the kernel, as ever
    assert (prefill["s"], prefill["write_attend"], prefill["attn"],
            prefill["writer"]) == (256, 0, 2, 0), prefill
    assert prefill["kernels"] == ["_paged_call_once"], prefill
    # a block's 25 heads in one grid step of the decode step, with the
    # write's output blocks and tokens in VMEM too, 26.2 MB of blocks
    # stored a call; a 256-row prefill fits 5 heads a step. The grid is
    # head tiles x the LIVE items of a work list (PR 48): 256 and 5 x 8
    # are its bounds (the pool's 224 blocks and a step a slot do not
    # bound 32 x 8 further), and in both programs each kernel's grid ends
    # at a scalar read on the device, and the two layers share ONE list
    assert (decode["heads_per_step"], decode["grid_steps"],
            decode["write_bytes"]) == (25, 256, 32 * 25 * 128 * 128 * 2)
    assert (prefill["heads_per_step"], prefill["grid_steps"]) == (5, 40)
    for step in (decode, prefill):
        assert step["paged_calls"] == {"calls": 2, "dynamic_grid": 2,
                                       "lists": 1}, step
    # counted. The decode step: 150 instructions with the writer's four
    # calls and their operands (PR 45), 40 with the writing kernel over
    # the table-wide grid (PR 47), 138 with the list: five fusions of
    # comparisons and sums, once for both layers, and still no byte of
    # temporaries (the same list by a search and lookups was 997 and
    # 2.6 MB). The prefill: 468 and 290 816 B before the list
    assert (decode["instructions"], decode["temp_bytes"]) == (138, 0), decode
    assert (prefill["instructions"], prefill["temp_bytes"]) \
        == (537, 323_072), prefill


def _compile_latent_steps_for_v5e(model="KimiK2", config="latent",
                                  serve="latent_serve", layers=None):
    """Child-process body of the tests below: ServeLoop's own decode step
    and bucket-2048 prefill of a latent-cache share at its published
    widths, compiled for v5e over the benchmark's pool of one-head latent
    arenas; one JSON line a program. By default the Kimi-K2 share
    (`chip_smoke.Sizes.full().latent`: one dense and one expert layer, 12
    of 384 experts held)."""
    import dataclasses
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.inference.serving import (_build_prefill,
                                              build_decode_step)
    from paddle_tpu.nn import initializer
    from paddle_tpu.nn.kv_pool import KVBlockPool
    from paddle_tpu.text import models
    try:
        device = topologies.get_topology_desc("v5e:2x2", "tpu").devices[0]
    except Exception as e:  # environment without a usable libtpu
        print(f"NO-TOPOLOGY {type(e).__name__}: {e}")
        return
    sharding = SingleDeviceSharding(device)
    # only shapes are compiled: billions of parameters need not be drawn
    initializer.Normal.__call__ = \
        lambda self, shape, dtype="float32": jnp.zeros(tuple(shape), dtype)
    sizes = chip_smoke.Sizes.full()
    slots, blocks, block, max_seq, bucket = getattr(sizes, serve)
    config = getattr(sizes, config)
    if layers:
        config = dataclasses.replace(config, num_layers=layers)
    net = getattr(models, model)(config)
    net.eval()
    params, buffers = net.functional_state()
    pool, width = KVBlockPool(blocks, block), max_seq // block
    (arena,) = net.paged_cache_spec()[0].arenas
    arena = pool.arena_shape(*arena)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def like(tree):
        return jax.tree.map(lambda x: spec(x.shape, x.dtype), tree)

    i32, u32 = jnp.int32, jnp.uint32
    state = (like(params), like(buffers), like(jax.eval_shape(
        lambda: pool.arenas_for(net.paged_cache_spec(), jnp.bfloat16))))
    programs = {
        1: (build_decode_step(net),
            (spec((slots, width), i32), spec((slots,), i32),
             spec((slots,), i32), spec((slots, 2), u32))),
        bucket: (_build_prefill(net, 0.0, None),
                 (spec((slots,), i32), spec((1, width), i32),
                  spec((1, bucket), i32), spec((), i32), spec((2,), u32),
                  spec((), i32)))}
    paddle.set_flags({"FLAGS_pallas_force_compile": True})
    for s, (fn, rest) in programs.items():
        monitor.reset(prefix="pallas.")
        compiled = jax.jit(fn, donate_argnums=(2,)).trace(
            *state, *rest).lower(lowering_platforms=("tpu",)).compile()
        net.load_functional_state(params, buffers)
        text, mem = compiled.as_text(), compiled.memory_analysis()
        print("STEP " + json.dumps({
            "s": s,
            "arena_in_hlo": "[%s]" % ",".join(map(str, arena)) in text,
            "relayouts": chip_smoke.arena_relayouts(text, arena),
            "lane_padded": chip_smoke.lane_padded_results(text),
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "argument_bytes": mem.argument_size_in_bytes,
            "arena_bytes": int(np.prod(arena)) * 2,
            "arenas": len(net.paged_cache_spec()),
            "writer": monitor.stats("pallas.hit.").get(
                "pallas.hit.paged_write_token", 0),
            "latent_attn": monitor.stats("pallas.hit.").get(
                "pallas.hit.latent_paged_attention", 0),
            "rejects": monitor.stats(
                "pallas.gate_reject.latent_paged_attention."),
            "cut": monitor.stats("pallas.latent_paged_attention."),
            "experts": monitor.stats("pallas.hit.").get(
                "pallas.hit.grouped_expert_ffn", 0),
            "experts_rejects": monitor.stats(
                "pallas.gate_reject.grouped_expert_ffn."),
            "experts_cut": monitor.stats("pallas.grouped_expert_ffn."),
            "instructions": _instruction_count(text),
            "conditionals": len(re.findall(r" conditional\(", text)),
            "scopes": _scope_summary(text)}))
    print("LATENT-STEPS-DONE")


def _compile_scmoe_steps_for_v5e():
    """The same for the LongCat-Flash share as the benchmark serves it
    (`chip_smoke.Sizes.full().scmoe` at FOUR shortcut-connected layers:
    8 arenas, 128 slots, 1536 blocks of 128)."""
    _compile_latent_steps_for_v5e("LongCatFlash", "scmoe", "scmoe_serve",
                                  layers=4)


def test_latent_serve_steps_hold_no_arena_copy_for_v5e():
    """The pool's one layout holds for a one-head latent arena
    [blocks+1, 1, 576, 128] too: the Kimi-K2 share's decode step (the
    Pallas token writer and the Pallas latent-attention kernel, each
    once a layer) and its bucket-2048 prefill (the in-place block loop,
    attention within the chunk: no kernel of the pool's) hold no copy or
    transpose of arena shape, and the donated arenas come back aliased.
    Unlike GPT's steps these are whole model programs, so their temps
    are activations. The decode step's: 3.2 MB (the plain-XLA latent
    attention gathered 24 blocks for each of 64 slots and scored them in
    float32: 475 MB before the kernel, PR 38). The prefill works tile by
    tile over the tiles that hold a token (`decoder._live_rows`) and a
    tile of queries reads the keys up to its own tile: seven conditionals
    a layer at 2048 rows in tiles of 256, and 484 MB of temporaries where
    whole rows against all the keys held 562 MB (PR 42). The decode step
    knows nothing of tiles: its instructions and its temporaries are
    counted, and a PR that means to change it changes the counts."""
    out = _run_in_cpu_child("_compile_latent_steps_for_v5e",
                            "LATENT-STEPS-DONE")
    decode, prefill = (json.loads(line[5:]) for line in out.splitlines()
                       if line.startswith("STEP "))
    for step in (decode, prefill):
        assert step["arena_in_hlo"] and step["relayouts"] == [], step
        assert step["alias_bytes"] >= 2 * step["arena_bytes"], step
        assert step["lane_padded"] == [], step   # 64 dense latents a call
        assert step["rejects"] == {}, step
    assert (decode["temp_bytes"], decode["instructions"],
            decode["conditionals"]) == (3_162_624, 1694, 0), decode
    assert prefill["temp_bytes"] < 5.5e8, prefill
    assert prefill["conditionals"] == 2 * (2048 // 256 - 1), prefill
    # the Pallas writer and the latent kernel, one a layer (two layers)
    assert (decode["s"], decode["writer"], decode["latent_attn"]) == (1, 2, 2)
    assert decode["cut"] == {
        "pallas.latent_paged_attention.blocks_per_step.b64": 24,
        "pallas.latent_paged_attention.grid_steps.b64": 64,
        "pallas.latent_paged_attention.live_bytes.b64": 147456}
    # the XLA block loop; attention within the chunk hits nothing new
    assert (prefill["s"], prefill["writer"], prefill["latent_attn"]) \
        == (2048, 0, 0)
    # the expert layer (one of the two layers): the grouped kernel in the
    # decode step, 20 blocks of 64 rows by 8 tiles of 256 columns; the
    # 2048-token prefill is past the tokens it wins at and keeps the loop
    assert (decode["experts"], decode["experts_rejects"]) == (1, {})
    assert decode["experts_cut"] == {
        "pallas.grouped_expert_ffn.rows_per_block.t64": 64,
        "pallas.grouped_expert_ffn.tile_bytes.t64": 3 * 7168 * 256 * 2,
        "pallas.grouped_expert_ffn.grid_steps.t64": 160}
    assert (prefill["experts"], prefill["experts_rejects"]) == (
        0, {"pallas.gate_reject.grouped_expert_ffn.tokens": 1})
    # the work's own name on the optimized instructions (PR 41): what a
    # device trace's `fusion.123` is laid against (core/program_map.py)
    _assert_scoped(decode, {"attn", "ffn", "router", "experts", "head"},
                   {"_paged_write_once": ["attn"],
                    "_latent_paged_call_once": ["attn"],
                    "_grouped_ffn_call": ["experts"]})


def test_scmoe_serve_steps_hold_no_arena_copy_for_v5e():
    """The LongCat-Flash share at the benchmark's size: four layers of
    two latent attentions each over EIGHT arenas [1537, 1, 576, 128], 128
    decode slots. The decode step (the Pallas token writer and the Pallas
    latent kernel, each once a sublayer: 8 a trace) and the bucket-2048
    prefill hold no copy or transpose of arena shape, every donated
    arena comes back aliased, nothing is rejected. The decode step's
    temporaries are 52 MB (its activations at 128 rows; one gather of
    the tables' blocks would be 128 x 24 x 147 KB = 453 MB), counted with
    its instructions as the Kimi share's are. What the chip must hold:
    12.16 GB of arguments (10.35 GB of weights, 1.81 GB of arenas) and the
    prefill's 0.68 GB of temporaries (1.02 GB before the tiles, PR 42; 56
    conditionals: seven a latent attention): 12.8 GB of 16."""
    out = _run_in_cpu_child("_compile_scmoe_steps_for_v5e",
                            "LATENT-STEPS-DONE")
    decode, prefill = (json.loads(line[5:]) for line in out.splitlines()
                       if line.startswith("STEP "))
    for step in (decode, prefill):
        assert step["arenas"] == 8 and step["arena_bytes"] == 226_639_872
        assert step["arena_in_hlo"] and step["relayouts"] == [], step
        assert step["alias_bytes"] >= 8 * step["arena_bytes"], step
        assert step["lane_padded"] == [], step
        assert step["rejects"] == {}, step
        assert step["argument_bytes"] + step["temp_bytes"] < 14e9, step
    assert (decode["temp_bytes"], decode["instructions"],
            decode["conditionals"]) == (52_076_544, 5908, 0), decode
    assert prefill["temp_bytes"] < 8e8, prefill
    assert prefill["conditionals"] == 8 * (2048 // 256 - 1), prefill
    assert (decode["s"], decode["writer"], decode["latent_attn"]) == (1, 8, 8)
    assert decode["cut"] == {
        "pallas.latent_paged_attention.blocks_per_step.b128": 24,
        "pallas.latent_paged_attention.grid_steps.b128": 128,
        "pallas.latent_paged_attention.live_bytes.b128": 147456}
    assert (prefill["s"], prefill["writer"], prefill["latent_attn"]) \
        == (2048, 0, 0)
    # the four expert layers: the grouped kernel in the decode step, 28
    # blocks of 128 rows by 8 tiles; the 2048-token prefill keeps the loop
    assert (decode["experts"], decode["experts_rejects"]) == (4, {})
    assert decode["experts_cut"] == {
        "pallas.grouped_expert_ffn.rows_per_block.t128": 128,
        "pallas.grouped_expert_ffn.tile_bytes.t128": 3 * 6144 * 256 * 2,
        "pallas.grouped_expert_ffn.grid_steps.t128": 224}
    assert (prefill["experts"], prefill["experts_rejects"]) == (
        0, {"pallas.gate_reject.grouped_expert_ffn.tokens": 4})
    _assert_scoped(decode, {"attn", "ffn", "router", "experts", "head"},
                   {"_paged_write_once": ["attn"],
                    "_latent_paged_call_once": ["attn"],
                    "_grouped_ffn_call": ["experts"]})


def _compile_hybrid_steps_for_v5e():
    """Child-process body of the test below: ServeLoop's own decode step
    and bucket-1024 prefill of one period of the hybrid stack at its
    published widths (`chip_smoke.Sizes.full().hybrid`), compiled for v5e
    over the benchmark's pool (32 slots, 576 blocks of 128): paged keys
    and values and one float32 state a slot from one spec; one JSON line
    a program."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.inference.serving import (_build_prefill,
                                              build_decode_step)
    from paddle_tpu.nn import initializer
    from paddle_tpu.nn.kv_pool import KVBlockPool
    from paddle_tpu.text.models import OlmoHybrid
    try:
        device = topologies.get_topology_desc("v5e:2x2", "tpu").devices[0]
    except Exception as e:  # environment without a usable libtpu
        print(f"NO-TOPOLOGY {type(e).__name__}: {e}")
        return
    sharding = SingleDeviceSharding(device)
    # only shapes are compiled: 0.9 B parameters need not be drawn
    initializer.Normal.__call__ = \
        lambda self, shape, dtype="float32": jnp.zeros(tuple(shape), dtype)
    net = OlmoHybrid(chip_smoke.Sizes.full().hybrid)
    net.eval()
    params, buffers = net.functional_state()
    slots, blocks, block, max_seq, bucket = 32, 576, 128, 4736, 1024
    pool, width = KVBlockPool(blocks, block), -(-max_seq // block)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def like(tree):
        return jax.tree.map(lambda x: spec(x.shape, x.dtype), tree)

    i32, u32 = jnp.int32, jnp.uint32
    arenas = jax.eval_shape(lambda: pool.arenas_for(
        net.paged_cache_spec(), jnp.bfloat16, slots=slots))
    state = (like(params), like(buffers), like(arenas))
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for layer in arenas for x in layer)
    programs = {
        1: (build_decode_step(net),
            (spec((slots, width), i32), spec((slots,), i32),
             spec((slots,), i32), spec((slots, 2), u32))),
        bucket: (_build_prefill(net, 0.0, None),
                 (spec((slots,), i32), spec((1, width), i32),
                  spec((1, bucket), i32), spec((), i32), spec((2,), u32),
                  spec((), i32)))}
    paddle.set_flags({"FLAGS_pallas_force_compile": True})
    for s, (fn, rest) in programs.items():
        monitor.reset(prefix="pallas.")
        compiled = jax.jit(fn, donate_argnums=(2,)).trace(
            *state, *rest).lower(lowering_platforms=("tpu",)).compile()
        net.load_functional_state(params, buffers)
        text, mem = compiled.as_text(), compiled.memory_analysis()
        hits = monitor.stats("pallas.hit.")
        print("STEP " + json.dumps({
            "s": s,
            "state_in_hlo": "f32[32,96,5760]" in text,
            "state_copies": len(re.findall(
                r"= f32\[32,96,5760\]\S* (?:copy|transpose)\(", text)),
            "relayouts": chip_smoke.arena_relayouts(
                text, pool.arena_shape(30, 128)),
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes, "held_bytes": held,
            "hits": {k.rsplit(".", 1)[1]: int(v) for k, v in hits.items()},
            "rejects": monitor.stats("pallas.gate_reject."),
            "paged_calls": _paged_list_calls(text),
            "instructions": _instruction_count(text),
            "scopes": _scope_summary(text)}))
    print("HYBRID-STEPS-DONE")


def test_hybrid_serve_steps_update_state_and_arenas_in_place_for_v5e():
    """One pool, two kinds of state: the decode step of one period of the
    hybrid stack (the state-update kernel three times, the Pallas paged
    pair once) and its bucket-1024 prefill (the chunked-scan kernel three
    times, a slot's row put into each state) compile under Mosaic for v5e
    at the published widths, hold no copy or transpose of the state's
    [32, 96, 5760] float32 (its lanes are whole tiles: 5760 = 45 x 128)
    nor of an arena, and give every donated byte back aliased."""
    out = _run_in_cpu_child("_compile_hybrid_steps_for_v5e",
                            "HYBRID-STEPS-DONE")
    decode, prefill = (json.loads(line[5:]) for line in out.splitlines()
                       if line.startswith("STEP "))
    for step in (decode, prefill):
        assert step["state_in_hlo"] and step["state_copies"] == 0, step
        assert step["relayouts"] == [], step
        assert step["alias_bytes"] >= step["held_bytes"], step
    # 30 heads of 128 with the write's blocks and tokens in VMEM would be
    # 15 a grid step: the fused form's gate leaves this step on the
    # writer and the kernel apart (PR 47), counted
    assert decode["hits"] == {"gdn_step": 3, "paged_write_token": 2,
                              "paged_decode_attention": 1,
                              "paged_work_list": 1}
    # whose grid ends at the live items of its work list (PR 48: ~460 of
    # the 32 x 37 table entries), a bound read on the device
    assert decode["paged_calls"] == {"calls": 1, "dynamic_grid": 1,
                                     "lists": 1}, decode
    assert decode["rejects"] == {
        "pallas.gate_reject.paged_write_attend.shape": 1}, decode
    assert prefill["rejects"] == {}, prefill
    assert decode["temp_bytes"] < 64e6, decode
    assert prefill["hits"] == {"gdn_chunk_scan": 3}
    assert prefill["temp_bytes"] < 1.0e9, prefill
    # counted: the decode step was 1885 at PR 45's parent, unchanged by it
    # (`_paged_call_once` with a value width that is the key's) and by PR
    # 47 (the pair); 1975 with the work list of its one full layer (PR 48).
    # The prefill meets no paged kernel and is the parent's program
    assert (decode["instructions"], prefill["instructions"],
            prefill["temp_bytes"]) == (1975, 4974, 170_335_232), (decode,
                                                                  prefill)
    _assert_scoped(decode, {"linear_attn", "attn", "ffn", "head"},
                   {"_gdn_step_call": ["linear_attn"],
                    "_paged_write_once": ["attn"],
                    "_paged_call_once": ["attn"]})


def _compile_window_steps_for_v5e(model="Laguna", field="window",
                                  bucket=1024):
    """Child-process body of the tests below: ServeLoop's own decode step
    and bucket-`bucket` prefill of a net with window layers, a full layer
    and one sliding layer at their published widths
    (`chip_smoke.Sizes.full()`'s `field`), compiled for v5e over the
    benchmark's 128 slots: a full layer's pages and a sliding layer's
    rings from one spec; one JSON line a program."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.inference.serving import (_build_prefill,
                                              build_decode_step)
    from paddle_tpu.nn import initializer
    from paddle_tpu.nn.kv_pool import KVBlockPool
    from paddle_tpu.text import models
    try:
        device = topologies.get_topology_desc("v5e:2x2", "tpu").devices[0]
    except Exception as e:  # environment without a usable libtpu
        print(f"NO-TOPOLOGY {type(e).__name__}: {e}")
        return
    sharding = SingleDeviceSharding(device)
    # only shapes are compiled: half a billion parameters need not be drawn
    initializer.Normal.__call__ = \
        lambda self, shape, dtype="float32": jnp.zeros(tuple(shape), dtype)
    sizes = chip_smoke.Sizes.full()
    net = getattr(models, model)(getattr(sizes, field))
    net.eval()
    params, buffers = net.functional_state()
    slots, blocks, block, max_seq = getattr(sizes, field + "_serve")
    pool, width = KVBlockPool(blocks, block), -(-max_seq // block)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def like(tree):
        return jax.tree.map(lambda x: spec(x.shape, x.dtype), tree)

    i32, u32 = jnp.int32, jnp.uint32
    cache_spec = net.paged_cache_spec()
    arenas = jax.eval_shape(lambda: pool.arenas_for(
        cache_spec, jnp.bfloat16, slots=slots))
    state = (like(params), like(buffers), like(arenas))
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for layer in arenas for x in layer)
    # each ring [slots, ring_blocks, h, d, block] as the program holds it,
    # and as the kernels read it ([slots * ring_blocks, h, d, block])
    rings = sorted({x.shape for layer, a in zip(cache_spec, arenas)
                    for x in a[len(layer.arenas):]})
    paged = sorted({x.shape for layer, a in zip(cache_spec, arenas)
                    for x in a[:len(layer.arenas)]})

    def text_of(shape):
        return "bf16[" + ",".join(str(n) for n in shape) + "]"

    ring_forms = [text_of(form)[:-1] for r in rings
                  for form in (r, (r[0] * r[1],) + r[2:])]
    programs = {
        1: (build_decode_step(net),
            (spec((slots, width), i32), spec((slots,), i32),
             spec((slots,), i32), spec((slots, 2), u32))),
        bucket: (_build_prefill(net, 0.0, None),
                 (spec((slots,), i32), spec((1, width), i32),
                  spec((1, bucket), i32), spec((), i32), spec((2,), u32),
                  spec((), i32)))}
    paddle.set_flags({"FLAGS_pallas_force_compile": True})
    for s, (fn, rest) in programs.items():
        monitor.reset(prefix="pallas.")
        compiled = jax.jit(fn, donate_argnums=(2,)).trace(
            *state, *rest).lower(lowering_platforms=("tpu",)).compile()
        net.load_functional_state(params, buffers)
        text, mem = compiled.as_text(), compiled.memory_analysis()
        hits = monitor.stats("pallas.hit.")
        print("STEP " + json.dumps({
            "s": s,
            "ring_in_hlo": all(text_of(r) in text for r in rings),
            "ring_copies": sum(len(re.findall(
                "= " + re.escape(form) + r"\]\S* (?:copy|transpose)\(", text))
                for form in ring_forms),
            "relayouts": [x for shape in paged
                          for x in chip_smoke.arena_relayouts(text, shape)],
            "lane_padded": chip_smoke.lane_padded_results(text),
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes, "held_bytes": held,
            "ring_bytes": sum(int(np.prod(x.shape)) * 2
                              for layer, a in zip(cache_spec, arenas)
                              for x in a[len(layer.arenas):]),
            "hits": {k.rsplit(".", 1)[1]: int(v) for k, v in hits.items()},
            "rejects": monitor.stats("pallas.gate_reject."),
            "cut": monitor.stats("pallas.paged_decode_attention."),
            "instructions": _instruction_count(text),
            "scopes": _scope_summary(text)}))
    print("WINDOW-STEPS-DONE")


def _compile_sink_steps_for_v5e():
    """The same for the MiMo-V2-Flash share's leading full layer and one
    sliding expert layer (`chip_smoke.Sizes.full().sink`), and the
    benchmark's largest prefill bucket."""
    _compile_window_steps_for_v5e("MiMoV2Flash", "sink", 8192)


def test_window_serve_steps_hand_rings_and_arenas_to_the_kernel_for_v5e():
    """One kernel, two call sites: the decode step of the Laguna share's
    leading full layer (48 query heads over 8 key-value heads, the pool's
    pages) and of one sliding layer (72 over 8, a ring of 512 tokens a
    slot) compiles under Mosaic for v5e at the published widths and 128
    slots, hands the arenas AND the rings ([128, 4, 8, 128, 128] read as
    [512, 8, 128, 128]: the leading dimensions merged, no copy) to the
    grouped-query paged kernel and to the token writer as they are, and
    gives every donated byte back aliased; its temporaries are far under
    one ring. The bucket-1024 prefill writes a slot's ring row in place."""
    out = _run_in_cpu_child("_compile_window_steps_for_v5e",
                            "WINDOW-STEPS-DONE")
    decode, prefill = (json.loads(line[5:]) for line in out.splitlines()
                       if line.startswith("STEP "))
    for step in (decode, prefill):
        assert step["ring_in_hlo"] and step["ring_copies"] == 0, step
        assert step["relayouts"] == [], step
        assert step["alias_bytes"] >= step["held_bytes"], step
        assert step["rejects"] in (
            {}, {"pallas.gate_reject.grouped_expert_ffn.tokens": 1}), step
    assert decode["lane_padded"] == [], decode
    assert decode["hits"] == {"paged_write_token": 4,
                              "paged_decode_attention": 2,
                              "grouped_expert_ffn": 1}
    assert decode["temp_bytes"] < decode["ring_bytes"] // 8, decode
    # a key-value head's 6 or 9 query heads are the rows of one product:
    # all 8 key-value heads of a block in one grid step; a work list of
    # the live blocks, at most the pool's 1024 and a step a slot in the
    # full layer (not 128 x 72 table entries), the rings' 4 a slot in the
    # sliding layer
    assert decode["cut"] == {
        "pallas.paged_decode_attention.heads_per_step.b128s1g6": 8,
        "pallas.paged_decode_attention.grid_steps.b128s1g6": 1024 + 128,
        "pallas.paged_decode_attention.heads_per_step.b128s1g9": 8,
        "pallas.paged_decode_attention.grid_steps.b128s1g9": 128 * 4}
    assert prefill["hits"] == {} and prefill["temp_bytes"] < 1.0e9, prefill
    _assert_scoped(decode, {"attn", "window_attn", "ffn", "experts", "head"},
                   {"_paged_write_once": ["attn", "window_attn"],
                    "_paged_grouped_call_once": ["attn", "window_attn"],
                    "_grouped_ffn_call": ["experts"]})
    # the programs are counted: a kernel that learns a new operand for
    # another net (sinks, values narrower than keys: PR 45) leaves these as
    # they were, instruction for instruction and byte for byte of temporaries
    assert (decode["instructions"], decode["temp_bytes"]) \
        == (2031, 2_872_832), decode
    assert (prefill["instructions"], prefill["temp_bytes"]) \
        == (2428, 83_691_008), prefill


def test_sink_serve_steps_hand_two_widths_and_one_block_rings_to_the_kernel():
    """The MiMo-V2-Flash share's leading full layer (64 query heads over 4
    key-value heads, a K arena 192 deep beside a V arena 128 deep, G = 16)
    and one sliding expert layer (64 over 8, a ring of ONE 128-token block
    a slot, a sink logit a head, G = 8) at the published widths and 128
    slots: the decode step compiles under Mosaic for v5e with the 192-deep
    contraction as laid out (no K arena padded to 256), hands arenas and
    rings to the kernel and to the token writer as they are (no copy or
    transpose of arena or ring shape), gives every donated byte back
    aliased, and names its work; so does the benchmark's largest prefill,
    the 8192 bucket, whose temporaries (0.71 GB at two layers; a layer's
    are reused by the next) size the pool: 11.3 GB of arguments at seven
    layers and 6144 blocks + 0.7 GB = 12.0 of 16 GB."""
    out = _run_in_cpu_child("_compile_sink_steps_for_v5e",
                            "WINDOW-STEPS-DONE")
    decode, prefill = (json.loads(line[5:]) for line in out.splitlines()
                       if line.startswith("STEP "))
    for step in (decode, prefill):
        assert step["ring_in_hlo"] and step["ring_copies"] == 0, step
        assert step["relayouts"] == [], step
        assert step["alias_bytes"] >= step["held_bytes"], step
        assert step["rejects"] in (
            {}, {"pallas.gate_reject.grouped_expert_ffn.tokens": 1}), step
        # two layers' weights, 2048 blocks of pages, 128 slots of rings
        assert 2.6e9 < step["argument_bytes"] < 2.7e9, step
    assert decode["lane_padded"] == [], decode
    assert decode["hits"] == {"paged_write_token": 4,
                              "paged_decode_attention": 2,
                              "grouped_expert_ffn": 1}
    assert decode["temp_bytes"] < 4e6, decode
    # all of a block's key-value heads in one grid step; the full layer's
    # work list is the pool's 2048 blocks and a step a slot, the sliding
    # layer's one block a slot: 128 steps
    assert decode["cut"] == {
        "pallas.paged_decode_attention.heads_per_step.b128s1g16": 4,
        "pallas.paged_decode_attention.grid_steps.b128s1g16": 2048 + 128,
        "pallas.paged_decode_attention.value_dim.b128s1g16": 128,
        "pallas.paged_decode_attention.sinks.b128s1g16": 0,
        "pallas.paged_decode_attention.heads_per_step.b128s1g8": 8,
        "pallas.paged_decode_attention.grid_steps.b128s1g8": 128,
        "pallas.paged_decode_attention.value_dim.b128s1g8": 128,
        "pallas.paged_decode_attention.sinks.b128s1g8": 1}
    assert prefill["hits"] == {} and prefill["temp_bytes"] < 0.8e9, prefill
    _assert_scoped(decode, {"attn", "window_attn", "ffn", "router",
                            "experts", "head"},
                   {"_paged_write_once": ["attn", "window_attn"],
                    "_paged_grouped_call_once": ["attn", "window_attn"],
                    "_grouped_ffn_call": ["experts"]})


def test_autotune_lookup_never_measures_under_trace():
    from paddle_tpu.ops.pallas import autotune
    calls = []

    def measure(params):
        calls.append(params)
        return 1.0

    def lookup():
        return autotune.lookup("smoke_probe", (8,), "float32",
                               [(1,), (2,)], measure, (2,))

    paddle.set_flags({"FLAGS_pallas_autotune_force": True})
    try:
        autotune.clear()
        picked = []
        jax.jit(lambda x: picked.append(lookup()) or x)(jnp.zeros(()))
        assert picked == [(2,)] and not calls  # traced: heuristic default
        assert lookup() in ((1,), (2,)) and calls  # eager: measured
    finally:
        paddle.set_flags({"FLAGS_pallas_autotune_force": False})
        autotune.clear()


def test_run_guarded_propagates_kernel_errors():
    from paddle_tpu.ops.pallas import run_guarded
    monitor.reset(prefix="pallas.")

    def thunk():
        raise ValueError("Mosaic said no")

    with pytest.raises(ValueError, match="Mosaic said no"):
        run_guarded("probe", thunk)
    assert monitor.stat_get("pallas.hit.probe") == 0
    assert run_guarded("probe", lambda: 7) == 7
    assert monitor.stat_get("pallas.hit.probe") == 1
