"""Driver `serve_open_loop_ref_state`: `serve_open_loop_ref`'s cell for a
net that keeps STATE beside its paged cache: a recurrent (linear-attention)
layer holds one matrix a decode slot, and a state is advanced by every
program that writes it (benchmark/README_state.md). Everything is
`serve_open_loop_ref`'s own, by import (the schedule, the window, the
weights, `check` and its limits, what `benchmark/sweep.py` asks of a
driver), but for two things.

The teacher-forced check. `serve_open_loop_ref.forced_logits` runs each
position twice over the same arenas: a program traced there that returns
the logits and the written arenas, then the loop's `_step_jit`, with the
same token at the same position. Keys and values written twice at one
position are the same keys and values; a recurrent state updated twice is
a different state. Here the program that reads the logits gives back the
arenas it wrote the position's keys and values into (the loop's
`_step_jit` then writes the same values again) and every per-slot array
(`CacheSpec.slots`: the states) AS IT WAS GIVEN: what it computed for a
state is dropped, and only the loop's own `_step_jit` (and, for the
prompts, its `_prefill_jit`, called with the argument list the loop
uses) advances a state. Every logit is therefore read from the state the
timed programs left. (The arenas are donated to that program, as in the
older driver: one that keeps them has to copy every arena it writes a
token into, 5.02 GB beside 13.6 GB held at the published widths, and the
chip's compiler refused it: 17.70 of 15.75 GiB, PR 37's first chip run.)

The control. `config["control"]` may hold, beside `round_experts_to`
(`serve_open_loop_ref.load_weights`), `state_dtype`: the pool then keeps
every per-slot array that the net's `paged_cache_spec()` declares
float32 in that dtype, so the state is rounded to it after every update.
Set by benchmark/control_run_state.py, never by a cell: the run that the
limits must refuse.
"""
from __future__ import annotations

import gc

import numpy as np

from benchmark.drivers import serve_open_loop_ref as ref_driver
from benchmark.drivers.serve_open_loop_ref import (COUNTERS, bucket_of,
                                                   check, device_peak_gb,
                                                   knee_line, load_line,
                                                   measure, mix_buckets,
                                                   plan, served_sample,
                                                   wait_idle, warm_up)
from benchmark.lib.stats import samples_beyond

__all__ = ["COUNTERS", "build_server", "check", "measure", "mix_buckets",
           "plan", "run", "warm_up"]


def state_in(net, dtype):
    """Make `net.paged_cache_spec()` name `dtype` for every per-slot array
    it declares float32 (the control: a state one precision down)."""
    spec = [layer._replace(slots=tuple(
        (shape, dtype if own == "float32" else own)
        for shape, own in layer.slots)) for layer in net.paged_cache_spec()]
    net.paged_cache_spec = lambda: spec


def build_server(config, seed):
    """`serve_open_loop_ref.build_server`, the control's state dtype
    applied before the loop lays out its pool."""
    from paddle_tpu.inference import ServeConfig, ServeLoop
    net, loop = ref_driver.build_server(config, seed)
    low = (config.get("control") or {}).get("state_dtype")
    if low:
        del loop
        state_in(net, low)
        loop = ServeLoop(net, ServeConfig(**config["serve"]))
    return net, loop


def forced_logits(net, loop, sample, steps):
    """`serve_open_loop_ref.forced_logits` with every state advanced once
    a position (module docstring): the same slots filled through
    `loop._prefill_jit`, the same teacher-forced steps of all slots at
    once; each step reads its logits through a program that returns no
    cache, then runs the loop's `_step_jit`, the only writer.
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
        # the written arenas, and each per-slot array as it came in
        return logits, [new[:len(layer.arenas)] + old[len(layer.arenas):]
                        for layer, old, new in zip(spec, arenas,
                                                   cache_arenas(caches))]

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
            tokens_d = jnp.asarray(tokens)
            logits, arenas = with_logits(params, arenas, table_d, at,
                                         tokens_d)
            logits = np.asarray(logits, np.float32)
            arenas, sampled, *_ = loop._call_traced(
                loop._step_jit, ("decode",), params, buffers, arenas,
                table_d, at, tokens_d, keys_d)
            sampled = np.asarray(sampled)
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


def run(cell):
    """`serve_open_loop_ref.run` over this module's `build_server` and
    `forced_logits`."""
    import jax
    from paddle_tpu.core import monitor
    config, mix = cell.config, cell.traffic
    if cell.chips != 1:
        raise SystemExit("serve_open_loop_ref_state: one server on one chip")
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
    print(f"serve_open_loop_ref_state: buckets {buckets}, block_size "
          f"{stats['block_size']}, {len(rows)} due in {m['window_s']:.3f} s, "
          f"{sum(r['finished'] for r in rows)} finished, window counters "
          f"{delta}, compared {compared}; state {stats['state_bytes']} B in "
          f"{stats['max_active']} slots; device peak {served_peak:.3f} GB "
          f"after serving, {device_peak_gb():.3f} GB after the reference",
          flush=True)
    line = knee_line(mix, m["samples"], stats["max_active"])
    if line:
        print(line, flush=True)
    print(load_line(m), flush=True)
    late = [(r["t_submit"] - r["t_due"]) * 1e3 for r in rows if r["t_submit"]]
    print(f"serve_open_loop_ref_state: {len(late)} samples of late_ms "
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
