"""Driver `serve_open_loop_ref`: `serve_open_loop`'s open-loop cell for a
configuration that is one chip's share of a model (README_share.md), its
weights drawn by the benchmark and `correct` decided against the
benchmark's plain float32 reference. Nothing here names a model: the
configuration does (`model.class`, `model.config_class`,
`reference_check.module`, `reference_check.control_leaves`).

The window, the samples and the log lines are `serve_open_loop`'s own
(`measure`, `warm_up`, `knee_line`, `load_line`), so is what
`benchmark/sweep.py` asks of a driver. What differs:

Set-up. The net is constructed in the served dtype (`model.config_kwargs`
carries `dtype`; `lib/build.build_net` + `amp.decorate` would hold the
parameters in float32 first: 19.4 GB of Kimi-K2.7-Code's share on a 16 GB
chip), then every leaf is replaced, one at a time, by the reference
module's `make_weights(seed, config)`. A checkout whose program lacks the
model ends there, in seconds, with a plain message and exit code 1.

The schedule. `plan` is `serve_open_loop`'s; a mix with `stratify` has its
lengths redrawn in strata (lib/stratify.py).

`correct`: no request error, every finished request has its
`max_new_tokens` ids inside the vocabulary slice, no compile inside the
window, and, at the published widths:
- `ref_gap_*`, of what the timed path produced: for a seed-drawn sample of
  `reference_check.sample` requests that finished in the window, the
  reference's full forward over prompt plus served tokens; per served
  token the gap by which its reference logit lies under the reference's
  best, over the largest reference logit of that position in magnitude
  (the mean and the 99th percentile are held to a limit; the widest is
  printed: one routing decision that the served precision flips near the
  eighth score moves a single token by as much as a lower precision
  moves many). A greedy server working in the stated precision picks the
  reference's best or a near tie;
- `forced_logits_*`, through the loop's own programs at the timed fill:
  after the window the stopped loop's `_prefill_jit` (the programs the
  window ran, one call a slot, each prompt padded to its bucket) fills
  EVERY decode slot with one of the sampled requests' prompts; then
  `forced_decode_steps` teacher-forced steps of all slots at once. The
  loop's programs return sampled tokens, not logits, so each step runs
  twice over the same arenas: `net._forward_paged` jitted here to return
  the logits (what `build_decode_step` traces, less the sampler), then
  the loop's `_step_jit`, whose write of the step's latents is the one
  that stays. Logits against the reference's on the same ids, over every
  position of the sampled requests: `_rms` the median of |difference|_2 /
  |reference|_2, `_err_p75` the upper quartile of the normalised max
  error (a flipped routing decision moves one position by much; lower
  precision moves every position). Printed beside them: the worst
  position, and the share of positions where the token `_step_jit`
  sampled is the argmax of the compared logits.
The program's part runs first; then the net, the loop and its arenas are
dropped, so that the reference (one block's float32 weights at a time)
peaks below what serving peaked at and `memory_peak_bytes` stays the
server's. `config["control"] = {"round_experts_to": <dtype>}` (set by
benchmark/control_run.py, never by a cell) rounds every leaf named by
`reference_check.control_leaves` one precision down before serving: the
run that the limits must refuse.
"""
from __future__ import annotations

import gc
import importlib

import numpy as np

from benchmark.drivers import serve_open_loop as open_loop
from benchmark.drivers.serve_open_loop import (COUNTERS, bucket_of, knee_line,
                                               load_line, measure,
                                               mix_buckets, wait_idle,
                                               warm_up)
from benchmark.lib import stratify
from benchmark.lib.build import load_object, model_kwargs
from benchmark.lib.forced_check import rel_err
from benchmark.lib.stats import samples_beyond

__all__ = ["COUNTERS", "build_server", "check", "measure", "mix_buckets",
           "plan", "run", "warm_up"]


def reference_of(config):
    """The configuration's reference module: `leaf_shapes(config)`,
    `make_weights(seed, config)`, `reference_logits(seed, config,
    sequences, first)` (README_share.md)."""
    return importlib.import_module(config["reference_check"]["module"])


def plan(config, mix, seed, seconds):
    """`serve_open_loop.plan`; a mix with `stratify` has its lengths
    redrawn in strata (lib/stratify.py)."""
    schedule = open_loop.plan(config, mix, seed, seconds)
    if "stratify" in mix:
        schedule = stratify.restratify(
            schedule, mix, seed, int(config["vocab_size"]),
            int(config["serve"]["max_seq_len"]))
    return schedule


def round_down(leaf, dtype):
    """`leaf` through `dtype` and back, scaled per tensor so that its
    largest entry is the largest `dtype` holds (how a deployment one
    precision down would store it)."""
    import jax.numpy as jnp
    floating = jnp.issubdtype(jnp.dtype(dtype), jnp.floating)
    top = float((jnp.finfo if floating else jnp.iinfo)(dtype).max)
    scale = jnp.max(jnp.abs(leaf)).astype(jnp.float32) / top
    low = leaf.astype(jnp.float32) / scale
    if not floating:
        low = jnp.round(low)
    return (low.astype(dtype).astype(jnp.float32) * scale).astype(leaf.dtype)


def load_weights(net, config, seed):
    """Replace every parameter of `net` by the benchmark's leaf of the
    same name, one at a time (the old leaf is freed as the new one is
    seated). The names and shapes must be the reference's."""
    ref = reference_of(config)
    params = dict(net.named_parameters())
    want = {name: shape for name, shape, _ in ref.leaf_shapes(config)}
    got = {name: tuple(p.shape) for name, p in params.items()}
    if got != want:
        odd = sorted(set(got.items()) ^ set(want.items()))[:6]
        raise SystemExit("serve_open_loop_ref: the net's parameters are "
                         f"not the reference's leaves, first: {odd}")
    low = (config.get("control") or {}).get("round_experts_to")
    leaves = tuple(config["reference_check"]["control_leaves"])
    for name, leaf in ref.make_weights(seed, config):
        if low and name.endswith(leaves):
            leaf = round_down(leaf, low)
        if leaf.dtype != params[name]._value.dtype:
            raise SystemExit(f"serve_open_loop_ref: {name} is "
                             f"{params[name]._value.dtype} in the net, "
                             f"{leaf.dtype} in the benchmark")
        params[name]._value = leaf


def build_server(config, seed):
    """(net, loop): the decoder born in its served dtype, the benchmark's
    weights in it, eval, behind a ServeLoop with the deployment's
    ServeConfig."""
    try:  # first thing: on a checkout without the model the run ends here
        model = load_object(config["model"]["class"])
        model_config = load_object(config["model"]["config_class"])
    except (ImportError, AttributeError) as e:
        raise SystemExit("serve_open_loop_ref: this checkout's program has "
                         f"no {config['model']['class']} to serve ({e})")
    import paddle_tpu as paddle
    from paddle_tpu.inference import ServeConfig, ServeLoop

    paddle.seed(seed)
    net = model(model_config(**model_kwargs(config)))
    net.eval()
    load_weights(net, config, seed)
    return net, ServeLoop(net, ServeConfig(**config["serve"]))


def forced_logits(net, loop, sample, steps):
    """Teacher-forced logits of the program's paged path at full fill,
    over a STOPPED, idle loop's own arenas and programs. Every decode slot
    is filled, through `loop._prefill_jit`, with the prompt of one of
    `sample` [(row, ids)] (slot i holds sample i mod len(sample); as many
    slots as the pool has blocks for); then up to `steps` decode steps of
    all slots at once, slot i fed ids[prompt_len + j - 1] at step j. Each
    step runs `net._forward_paged` jitted here to return the logits, then
    the loop's `_step_jit` over the same arenas (module docstring).
    -> ([logits [steps_k, vocab] float32 per sample k: row j - 1 predicts
    ids[prompt_len + j]], live slots, positions where `_step_jit`'s token
    is the logits' argmax, positions). The loop serves nothing after."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core import tape
    from paddle_tpu.nn.kv_pool import cache_arenas, paged_caches

    slots, width, pool = loop._A, loop._MB, loop._pool
    params, buffers = loop._params, loop._buffers
    arenas, carry = loop._arenas, loop._tokens
    loop._arenas = loop._tokens = None        # donated below
    spec = net.paged_cache_spec()
    key = np.asarray(jax.random.PRNGKey(0), np.uint32)
    table = np.zeros((slots, width), np.int32)
    lengths = np.zeros((slots,), np.int32)
    todo = [min(steps, len(ids) - int(r["prompt_len"]) - 1)
            for r, ids in sample]
    live = 0
    for i in range(slots):
        r, ids = sample[i % len(sample)]
        n = int(r["prompt_len"])
        blocks = pool.alloc(pool.blocks_for(n + steps + 1))
        if blocks is None:
            break
        table[i, :len(blocks)] = blocks
        padded = np.zeros((1, bucket_of(n)), np.int32)
        padded[0, :n] = ids[:n]
        (arenas, carry), *_ = loop._call_traced(
            loop._prefill_jit, ("prefill", padded.shape[1]), params, buffers,
            arenas, carry, jnp.asarray(table[i:i + 1]), jnp.asarray(padded),
            jnp.int32(n), jnp.asarray(key), jnp.int32(i))
        lengths[i] = n
        live += 1

    def with_logits(params, arenas, table, lengths, tokens):
        with tape.no_grad():
            net.load_functional_state(params, buffers)
            logits, caches, *_ = net._forward_paged(
                tokens[:, None], paged_caches(spec, arenas, table, lengths))
        return logits, cache_arenas(caches)

    with_logits = jax.jit(with_logits, donate_argnums=(1,))
    table_d = jnp.asarray(table)
    keys_d = jnp.asarray(np.tile(key, (slots, 1)))
    out = [[] for _ in sample]
    agree = positions = 0
    try:
        for j in range(1, max(todo) + 1):
            tokens = np.zeros((slots,), np.int32)
            for i in range(live):
                r, ids = sample[i % len(sample)]
                if j <= todo[i % len(sample)]:
                    tokens[i] = ids[int(r["prompt_len"]) + j - 1]
            at = jnp.asarray(np.where(lengths > 0, lengths + (j - 1), 0)
                             .astype(np.int32))
            logits, arenas = with_logits(params, arenas, table_d, at,
                                         jnp.asarray(tokens))
            arenas, sampled, *_ = loop._call_traced(
                loop._step_jit, ("decode",), params, buffers, arenas,
                table_d, at, jnp.asarray(tokens), keys_d)
            logits, sampled = np.asarray(logits, np.float32), np.asarray(
                sampled)
            for k in range(min(live, len(sample))):
                if j <= todo[k]:
                    out[k].append(logits[k])
                    agree += int(sampled[k] == logits[k].argmax())
                    positions += 1
    finally:
        net.load_functional_state(params, buffers)
    vocab = int(net.config.vocab_size)
    return ([np.stack(rows) if rows else np.zeros((0, vocab), np.float32)
             for rows in out], live, agree, positions)


def served_sample(rows, n, seed, prompts):
    """`n` of the requests that finished in the window, drawn from the
    seed: [(row, ids = prompt + served tokens)]."""
    done = [r for r in rows if r["finished"] and not r["error"] and r["out"]]
    rng = np.random.RandomState(seed % (2 ** 32))
    picked = rng.choice(len(done), size=min(n, len(done)), replace=False)
    return [(done[i], np.concatenate([prompts[done[i]["index"]],
                                      done[i]["out"]]))
            for i in sorted(picked)]


def reference_gaps(logits, served):
    """Per served token, how far its reference logit lies under the
    reference's best, over the largest |logit| of its position."""
    logits = np.asarray(logits, np.float64)
    own = np.take_along_axis(logits, np.asarray(served)[:, None], 1)[:, 0]
    return (logits.max(axis=1) - own) / np.abs(logits).max(axis=1)


def basic_checks(m, vocab):
    """What every serving cell checks of its window: (why, compared)."""
    why = []
    errors = [r for r in m["rows"] if r["error"]]
    if errors:
        why.append(f"{len(errors)} requests failed, first: "
                   f"{errors[0]['error']}")
    malformed = [r for r in m["rows"] if r["finished"] and not r["error"]
                 and (len(r["out"]) != r["n_out_wanted"]
                      or not all(0 <= t < vocab for t in r["out"]))]
    if malformed:
        r = malformed[0]
        why.append(f"request {r['index']}: {len(r['out'])} tokens, wanted "
                   f"{r['n_out_wanted']}, or ids outside the vocabulary")
    if m["compiles_in_window"]:
        why.append(f"{m['compiles_in_window']} compiles inside the window")
    return why, {"requests_errored": [len(errors), 0],
                 "outputs_malformed": [len(malformed), 0],
                 "compiles_in_window": [m["compiles_in_window"], 0]}


def check(config, seed, m, sample, forced):
    """(why the run is not correct: an empty list when it is; every number
    compared beside its limit). `sample` is `served_sample`'s, `forced`
    the program's `forced_logits` over it, taken before the program was
    dropped."""
    why, compared = basic_checks(m, int(config["vocab_size"]))
    rc = config["reference_check"]
    if not sample:
        why.append("no request finished in the window: nothing to compare "
                   "with the reference")
        return why, compared
    logits = reference_of(config).reference_logits(
        seed, config, [ids for _, ids in sample],
        [r["prompt_len"] - 1 for r, _ in sample])
    gaps = np.concatenate([reference_gaps(lg, r["out"])
                           for lg, (r, _) in zip(logits, sample)])
    rows, live, agree, positions = forced
    # row j - 1 of the program's predicts what row j of the reference's does
    pairs = [(got[j], want[j + 1]) for got, want in zip(rows, logits)
             for j in range(len(got))]
    if not pairs:
        why.append("no teacher-forced position to compare")
        return why, compared
    worst = [rel_err(got, want) for got, want in pairs]
    rms = [float(np.linalg.norm(got - want) / np.linalg.norm(want))
           for got, want in pairs]
    compared.update({
        "ref_gap_mean": [float(gaps.mean()), float(rc["gap_mean_limit"])],
        "ref_gap_p99": [float(np.percentile(gaps, 99)),
                        float(rc["gap_p99_limit"])],
        "forced_logits_rms": [float(np.median(rms)),
                              float(rc["forced_rms_limit"])],
        "forced_logits_err_p75": [float(np.percentile(worst, 75)),
                                  float(rc["forced_p75_limit"])]})
    print(f"serve_open_loop_ref: reference over {len(sample)} requests "
          f"{[r['index'] for r, _ in sample]}, {gaps.size} served tokens: "
          f"gap mean {gaps.mean():.5f} p50 {np.percentile(gaps, 50):.5f} "
          f"p99 {np.percentile(gaps, 99):.5f} widest {gaps.max():.5f}, "
          f"{int((gaps == 0).sum())} at the reference's best; forced "
          f"logits ({len(pairs)} positions, {live} slots live): max error "
          f"a position, quartiles {np.percentile(worst, 25):.5f} "
          f"{np.percentile(worst, 50):.5f} {np.percentile(worst, 75):.5f} "
          f"worst {max(worst):.5f}; rms error a position, quartiles "
          f"{np.percentile(rms, 25):.5f} {np.percentile(rms, 50):.5f} "
          f"{np.percentile(rms, 75):.5f} worst {max(rms):.5f}; the loop's "
          f"decode step sampled the logits' argmax at {agree} of "
          f"{positions}", flush=True)
    for name in ("ref_gap_mean", "ref_gap_p99", "forced_logits_rms",
                 "forced_logits_err_p75"):
        value, limit = compared[name]
        if not value <= limit:
            why.append(f"{name} {value:.4g} > {limit}: the served tokens "
                       "are not what the reference computes in "
                       f"{config['dtype']}")
    return why, compared


def device_peak_gb():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 1e9


def run(cell):
    import jax
    from paddle_tpu.core import monitor
    config, mix = cell.config, cell.traffic
    if cell.chips != 1:
        raise SystemExit("serve_open_loop_ref: one server on one chip")
    monitor.reset(prefix="serve.")
    net, loop = build_server(config, cell.seed)
    vocab, cap = int(config["vocab_size"]), int(config["serve"]["max_seq_len"])
    schedule = plan(config, mix, cell.seed, cell.seconds)
    buckets = mix_buckets(mix, cap - 1)
    loop.start()
    try:
        warm_up(loop, buckets, vocab, cap)
        wait_idle(loop, 60)
        m = measure(loop, schedule, mix, cell.seconds,
                    cell.trace_dir if cell.trace else None)
    finally:
        loop.stop(timeout=120)
    stats = loop.stats()
    served_peak = device_peak_gb()
    rc = config["reference_check"]
    sample = served_sample(m["rows"], int(rc["sample"]), cell.seed,
                           {r.index: r.prompt for r in schedule})
    forced = sample and forced_logits(net, loop, sample,
                                      int(rc["forced_decode_steps"]))
    # the reference needs the room, and the peak is to stay the server's
    del net, loop
    gc.collect()
    why, compared = check(config, cell.seed, m, sample, forced)
    rows = m["rows"]
    failed = sum(bool(r["error"]) or (
        bool(mix.get("unfinished_is_failure")) and not r["finished"])
        for r in rows)
    delta = {k: m["close"]["counters"][k] - m["open"]["counters"][k]
             for k in COUNTERS}
    print(f"serve_open_loop_ref: buckets {buckets}, block_size "
          f"{stats['block_size']}, {len(rows)} due in {m['window_s']:.3f} s, "
          f"{sum(r['finished'] for r in rows)} finished, window counters "
          f"{delta}, compared {compared}; device peak {served_peak:.3f} GB "
          f"after serving, {device_peak_gb():.3f} GB after the reference",
          flush=True)
    line = knee_line(mix, m["samples"], stats["max_active"])
    if line:
        print(line, flush=True)
    print(load_line(m), flush=True)
    late = [(r["t_submit"] - r["t_due"]) * 1e3 for r in rows if r["t_submit"]]
    print(f"serve_open_loop_ref: {len(late)} samples of late_ms "
          f"({samples_beyond(len(late), 95)} beyond p95), p50 "
          f"{np.percentile(late or [0], 50):.2f} p95 "
          f"{np.percentile(late or [0], 95):.2f} max "
          f"{max(late, default=0.0):.2f}", flush=True)
    return {
        "correct": not why and not failed, "why_incorrect": why,
        "compared": compared, "attempted": len(rows), "failed": failed,
        "setup_s": m["open"]["t"] - cell.t_process_start,
        "window_s": m["window_s"], "chips": 1, "rows": rows,
        "counters": delta,
        "steps": m["close"]["steps"] - m["open"]["steps"],
        "samples": m["samples"], "max_active": stats["max_active"],
        "kv_blocks": int(config["serve"]["kv_blocks"]),
        "block_size": stats["block_size"],
        "compiles_in_window": m["compiles_in_window"],
        "device_kind": jax.devices()[0].device_kind,
        "config": config,
        "kernel_patterns": config.get("kernel_patterns", {}),
        "module_patterns": config.get("module_patterns", {}),
    }
