"""Pipeline-schedule quality report (VERDICT r04 item 7 'Done' criterion):
compare gpipe vs 1f1b-remat vs interleaved on step-time and compiled
memory on the virtual 8-CPU mesh, verifying grads match the non-pipelined
reference for every schedule. Writes docs/pp_schedules.md.

Run: JAX_PLATFORMS=cpu \
     XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     python tools/pp_schedule_report.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax                                    # noqa: E402
import jax.numpy as jnp                       # noqa: E402
import numpy as np                            # noqa: E402
from jax.sharding import PartitionSpec as P   # noqa: E402

from paddle_tpu.distributed import mesh as mesh_mod          # noqa: E402
from paddle_tpu.distributed.pipeline import (bubble_fraction,  # noqa: E402
                                             micro_batch, pipeline_loss,
                                             schedule_ticks)

N_STAGES = 4
N_VIRTUAL = 2
N_MICRO = 16
D = 256
MB = 8       # microbatch size
CHUNK_DEPTH = 3  # applications of the chunk matmul: intra-chunk
# activations dominate the stash, so 1f1b's rematerialization is visible


def _apply_chunk(h, w):
    for _ in range(CHUNK_DEPTH):
        h = jnp.tanh(h @ w)
    return h


def build(schedule):
    mesh = mesh_mod.init_mesh({"pp": N_STAGES}, name="default")
    rng = np.random.RandomState(0)
    n_global = N_STAGES * N_VIRTUAL
    ws = (rng.randn(n_global, D, D) * (1.0 / np.sqrt(D))).astype("float32")
    x = rng.randn(N_MICRO * MB, D).astype("float32")
    y = rng.randn(N_MICRO * MB, D).astype("float32")
    xm = micro_batch(jnp.asarray(x), N_MICRO)
    ym = micro_batch(jnp.asarray(y), N_MICRO)

    if schedule == "interleaved":
        # chunk c on rank r = global stage c*n + r
        ws_by_rank = np.stack(
            [np.stack([ws[c * N_STAGES + r] for c in range(N_VIRTUAL)])
             for r in range(N_STAGES)])
        arg = jnp.asarray(ws_by_rank)          # [n, v, D, D]

        def spmd(wr, xm_l, ym_l):
            chunks = [lambda h, c=c: _apply_chunk(h, wr[0, c])
                      for c in range(N_VIRTUAL)]
            return pipeline_loss(chunks, lambda h, t: jnp.mean((h - t) ** 2),
                                 xm_l, ym_l, axis="pp",
                                 schedule="interleaved")
    else:
        # each rank runs its v chunks back-to-back as one deep stage:
        # contiguous layer blocks (global layer r*v + c), unlike the
        # interleaved round-robin assignment (c*n + r)
        ws_by_rank = np.stack(
            [np.stack([ws[r * N_VIRTUAL + c] for c in range(N_VIRTUAL)])
             for r in range(N_STAGES)])
        arg = jnp.asarray(ws_by_rank)

        def spmd(wr, xm_l, ym_l):
            def stage(h):
                for c in range(N_VIRTUAL):
                    h = _apply_chunk(h, wr[0, c])
                return h
            return pipeline_loss(stage, lambda h, t: jnp.mean((h - t) ** 2),
                                 xm_l, ym_l, axis="pp", schedule=schedule)

    def outer(a):
        return mesh_mod.shard_map(spmd, mesh=mesh,
                                  in_specs=(P("pp"), P(), P()),
                                  out_specs=P())(a, xm, ym).mean()

    fn = jax.jit(jax.value_and_grad(outer))
    return fn, arg, ws, x, y


def reference(ws, x, y):
    def loss_fn(ws_all):
        h = jnp.asarray(x)
        for s in range(ws.shape[0]):
            h = _apply_chunk(h, ws_all[s])
        return jnp.mean((h - jnp.asarray(y)) ** 2)
    l, g = jax.value_and_grad(loss_fn)(jnp.asarray(ws))
    return float(l), np.asarray(g)


def grads_to_global(schedule, g):
    out = np.zeros((N_STAGES * N_VIRTUAL, D, D), "float32")
    for r in range(N_STAGES):
        for c in range(N_VIRTUAL):
            s = (c * N_STAGES + r if schedule == "interleaved"
                 else r * N_VIRTUAL + c)
            out[s] = g[r, c]
    return out


def stage_program_estimate():
    """Program-level liveness estimate of ONE stage's activation
    footprint (static/shape_infer.py analyze_memory) — the build-time
    number to sanity-check XLA's measured temp buffers against: the
    estimator never sees fusion/remat, so it upper-bounds a single
    chunk's stash."""
    import paddle_tpu as paddle
    from paddle_tpu import ops, static

    paddle.enable_static()
    try:
        main_prog = static.Program("pp_stage")
        with static.program_guard(main_prog):
            h = static.data("h", [MB, D], "float32")
            w = static.data("w", [D, D], "float32")
            for _ in range(CHUNK_DEPTH):
                h = ops.tanh(ops.matmul(h, w))
        main_prog._jit_fetch_vars = [h]
        est = static.analyze_memory(main_prog)
        return est
    finally:
        paddle.disable_static()


def self_check():
    """Violation strings for framework_lint's TOOL_CROSS_CHECKS: pins
    this report's mesh/microbatch constants against pipeline.py's
    schedule accounting and the stage-cut planner's objective knobs, so
    the three can't drift apart silently (this was the only pipeline
    tool outside the lint net)."""
    problems = []
    from paddle_tpu.core.flags import flag
    from paddle_tpu.distributed.pipeline import (bubble_fraction,
                                                 schedule_collectives,
                                                 schedule_ticks)

    # the report's schedule set is exactly what schedule_ticks accounts
    # (all three rows pin the v=N_VIRTUAL formulae the report prints)
    for schedule in ("gpipe", "1f1b", "interleaved"):
        ticks = schedule_ticks(N_MICRO, N_STAGES, schedule, N_VIRTUAL)
        want = (N_VIRTUAL * N_MICRO + N_STAGES - 1
                if schedule == "interleaved"
                else N_VIRTUAL * (N_MICRO + N_STAGES - 1))
        if ticks != want:
            problems.append(
                f"pp_schedule_report: schedule_ticks({schedule}) = "
                f"{ticks}, report math expects {want} — the report's "
                "tick column no longer matches pipeline.py")
        bub = bubble_fraction(N_MICRO, N_STAGES, schedule, N_VIRTUAL)
        if not (0.0 <= bub < 1.0):
            problems.append(
                f"pp_schedule_report: bubble_fraction({schedule}) = "
                f"{bub} out of [0, 1)")
    # degenerate shapes must price sanely (the cost model feeds the
    # planner: a crash here is a crash in plan_pipeline)
    if bubble_fraction(N_MICRO, 1) != 0.0:
        problems.append("pp_schedule_report: single-stage bubble != 0")
    if schedule_collectives(N_MICRO, 1, 1024)["total_bytes"] != 0:
        problems.append(
            "pp_schedule_report: single-stage pipeline prices nonzero "
            "ppermute wire")
    if schedule_ticks(2, N_STAGES) != 2 + N_STAGES - 1:
        problems.append(
            "pp_schedule_report: num_micro < num_stages must still "
            "price M+n-1 ticks")
    # the planner's pp objective knobs this report's numbers anchor
    for name, want in (("FLAGS_spmd_plan_pp_micro", 8),
                       ("FLAGS_spmd_plan_pp_beam", 8),
                       ("FLAGS_spmd_plan_pp_flops_weight", 1.0),
                       ("FLAGS_spmd_plan_pp_wire_weight", 1.0),
                       ("FLAGS_spmd_plan_pp_hbm_weight", 1.0),
                       ("FLAGS_spmd_plan_pp_bubble_weight", 1.0)):
        try:
            got = flag(name)
        except Exception as e:  # noqa: BLE001
            problems.append(
                f"pp_schedule_report: planner knob {name} missing ({e})")
            continue
        if got != want:
            problems.append(
                f"pp_schedule_report: planner knob {name} default "
                f"changed to {got!r} (docs/spmd_planner.md flag table "
                f"says {want!r}) — update the doc and this pin together")
    if N_MICRO % N_STAGES != 0:
        problems.append(
            "pp_schedule_report: N_MICRO must stay divisible by "
            "N_STAGES (the interleaved schedule's injection-group "
            "contract)")
    return problems


def main():
    rows = []
    ref_cache = None
    for schedule in ("gpipe", "1f1b", "interleaved"):
        fn, arg, ws, x, y = build(schedule)
        if ref_cache is None:
            ref_cache = reference(ws, x, y)
        ref_loss, ref_g = ref_cache
        lowered = fn.lower(arg)
        compiled = lowered.compile()
        try:
            ma = compiled.memory_analysis()
            temp_mb = ma.temp_size_in_bytes / 1e6
        except Exception:
            temp_mb = float("nan")
        loss, g = fn(arg)
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        reps = 5
        for _ in range(reps):
            loss, g = fn(arg)
        jax.block_until_ready(loss)
        dt = (time.perf_counter() - t0) / reps * 1000
        gg = grads_to_global(schedule, np.asarray(g))
        err = float(np.max(np.abs(gg - ref_g)))
        match = err < 1e-4 and abs(float(loss) - ref_loss) < 1e-5
        ticks = schedule_ticks(N_MICRO, N_STAGES, schedule, N_VIRTUAL)
        rows.append((schedule, ticks, temp_mb, dt, float(loss), match))
        print(f"{schedule:12s} ticks={ticks:3d} tempMB={temp_mb:8.1f} "
              f"step={dt:7.2f}ms loss={float(loss):.6f} "
              f"grads_match={match}")

    est = stage_program_estimate()
    est_mb = est["peak_bytes"] / 1e6
    print(f"stage-program liveness estimate: peak {est_mb:.2f} MB "
          f"(activations {est['activation_peak_bytes'] / 1e6:.2f} MB)")

    doc = [
        "# Pipeline schedule comparison",
        "",
        f"Measured on the virtual 8-CPU mesh (pp={N_STAGES}, "
        f"v={N_VIRTUAL} chunks/rank, M={N_MICRO} microbatches of {MB}, "
        f"hidden={D}); fwd+bwd step via `tools/pp_schedule_report.py`. "
        "Chunk-time ticks are the schedule-intrinsic cost "
        "(`schedule_ticks`); XLA temp memory is the compiled buffer "
        "footprint (activation stash shows up here); every schedule's "
        "grads are verified against the non-pipelined 8-layer reference.",
        "",
        "| schedule | chunk-ticks | bubble | XLA temp MB | step ms "
        "(8-CPU) | grads match |",
        "|---|---|---|---|---|---|",
    ]
    for schedule, ticks, temp_mb, dt, _loss, match in rows:
        bub = (bubble_fraction(N_MICRO, N_STAGES)
               if schedule != "interleaved"
               else (N_STAGES - 1) / (N_VIRTUAL * N_MICRO + N_STAGES - 1))
        doc.append(f"| {schedule} | {ticks} | {bub:.3f} | {temp_mb:.1f} | "
                   f"{dt:.2f} | {'yes' if match else 'NO'} |")
    doc += [
        "",
        f"Per-chunk build-time estimate (liveness over the stage's "
        f"static Program, `paddle_tpu.static.analyze_memory`): peak "
        f"{est_mb:.2f} MB, activations "
        f"{est['activation_peak_bytes'] / 1e6:.2f} MB — the pre-XLA "
        "upper bound one microbatch stashes per chunk; multiply by the "
        "schedule's in-flight microbatch count to anticipate the stash "
        "before compiling.",
        "",
        "Reading: `1f1b` = gpipe tick order + per-tick rematerialization "
        "(bounds the activation stash to tick-boundary hiddens; on this "
        "small CPU config XLA's own scheduling already bounds gpipe's "
        "stash, so the two measure alike — the bound matters at model "
        "scale, where the stash would otherwise grow with M); "
        "`interleaved` "
        "= virtual-stage schedule — bubble (n-1)/(vM+n-1) vs "
        "(n-1)/(M+n-1) and the finer chunk granularity is what actually "
        "cuts the compiled temp footprint here — at one extra ppermute "
        "per chunk. CPU step-ms is indicative only (no real ICI); the "
        "tick/bubble/memory columns are the architecture-true comparison.",
    ]
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "pp_schedules.md")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        f.write("\n".join(doc) + "\n")
    print(f"wrote {out}")
    if not all(r[5] for r in rows):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
