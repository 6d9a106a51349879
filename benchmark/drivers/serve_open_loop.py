"""Driver `serve_open_loop`: one serving cell through `ServeLoop.start()` /
`submit()`, open loop: requests are submitted when the schedule says they
are due, whatever the server is doing, and every latency starts at the due
time (same perf_counter clock as the server's stamps).

Set-up: seeded network in the served dtype, the pool, one warm-up request
per prefill bucket the mix can draw (plus the decode beat), the schedule
(made before the clock starts, so the generator only sleeps and submits),
then `lead_in_s` of traffic that is discarded: a seeding burst fills the
slots with staggered streams where the mix has one. The window is the
next `seconds` seconds. After it the generator keeps offering load until
every request due in the window is back or `deadline_s` has passed, then
every request still open is cut short (`max_new_tokens = 1`, a public
field the scheduler reads at each token) so that `stop()` returns at once.

Traffic keys: name, arrival, tenants, seed_burst, lead_in_s, deadline_s,
unfinished_is_failure, sample_every_s, trace_seconds (with --trace 1 the
profiler covers that many seconds right after the window, load still on),
and for a saturating mix knee_rps and headroom (`knee_line`).
`plan()` / `measure()` are what benchmark/sweep.py repeats per rate.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from benchmark.lib import accounting, profiler
from benchmark.lib.build import build_net
from benchmark.lib.forced_check import forced_logits
from benchmark.lib.stats import longest_still_s, samples_beyond, slot_fill
from benchmark.lib.workload import build_schedule

COUNTERS = ("serve.tokens_generated", "serve.requests_completed",
            "serve.requests_errored", "serve.preempted",
            "serve.backpressure_waits")


def bucket_of(n):
    """The prefill bucket ServeLoop pads a prompt of n tokens to."""
    b = 8
    while b < n:
        b *= 2
    return b


def mix_buckets(mix, cap):
    """Every prefill bucket the mix's prompt lengths can land in."""
    out = set()
    for tenant in mix["tenants"]:
        dist = tenant["prompt"]
        if dist.get("kind") == "fixed":
            lo = hi = int(dist["value"])
        else:
            lo, hi = int(dist.get("lo", 1)), min(int(dist.get("hi", cap)), cap)
        b = bucket_of(lo)
        while True:
            out.add(b)
            if b >= hi:
                break
            b *= 2
    return sorted(out)


def build_server(config, seed):
    """(net, loop): the seeded decoder in its served dtype, eval, behind a
    ServeLoop with the deployment's ServeConfig (block size fixed there)."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import ServeConfig, ServeLoop

    net = build_net(config, seed)
    net.eval()
    paddle.amp.decorate(net, level="O2", dtype=config["dtype"])
    return net, ServeLoop(net, ServeConfig(**config["serve"]))


def warm_up(loop, buckets, vocab, cap):
    """One request per bucket, two tokens each: compiles (or loads) every
    prefill program the mix can reach, and the decode beat."""
    for b in buckets:
        n = min(b, cap - 2)
        prompt = 1 + (np.arange(n, dtype=np.int64) % (vocab - 1))
        loop.submit(prompt, max_new_tokens=2).result(timeout=1500)


def cut_short(requests):
    for req in requests:
        if req is not None and not req.done:
            req.max_new_tokens = 1


def wait_idle(loop, timeout):
    end = time.perf_counter() + timeout
    while time.perf_counter() < end:
        s = loop.stats()
        if not s["queue_depth"] and not s["active_slots"]:
            return True
        time.sleep(0.01)
    return False


def _counters():
    from paddle_tpu.core import monitor
    return {k: float(monitor.stat_get(k)) for k in COUNTERS}


def measure(loop, schedule, mix, seconds, trace_dir=None):
    """Offer `schedule` to a started loop and observe the window
    [lead_in_s, lead_in_s + seconds). Leaves the loop idle."""
    lead_in = float(mix.get("lead_in_s", 0.0))
    deadline_s = float(mix.get("deadline_s", 0.0))
    every = float(mix.get("sample_every_s", 0.1))
    reqs = [None] * len(schedule)
    t_submit = [None] * len(schedule)
    stop = threading.Event()
    died = []                     # the generator's exception, if any
    t0 = time.perf_counter() + 0.05

    def generator():
        try:
            for i, r in enumerate(schedule):
                wait = t0 + r.t_due - time.perf_counter()
                if (wait > 0 and stop.wait(wait)) or stop.is_set():
                    return
                reqs[i] = loop.submit(r.prompt, max_new_tokens=r.new_tokens)
                t_submit[i] = time.perf_counter()
        except Exception as e:  # reported by the measuring thread below
            died.append(e)

    thread = threading.Thread(target=generator, name="load-generator")
    thread.start()
    try:
        time.sleep(max(0.0, t0 + lead_in - time.perf_counter()))
        open_ = {"t": time.perf_counter(), "counters": _counters(),
                 "steps": loop.stats()["steps"],
                 "acct": accounting.snapshot()}
        samples = []
        t_end = open_["t"] + seconds
        while time.perf_counter() < t_end:
            samples.append(dict(loop.stats(), t=time.perf_counter()))
            time.sleep(max(0.0, min(every, t_end - time.perf_counter())))
        close = {"t": time.perf_counter(), "counters": _counters(),
                 "steps": loop.stats()["steps"],
                 "acct": accounting.snapshot()}
        if trace_dir is not None:
            # the profiler runs right after the window, under the same
            # load, so that its start and stop (seconds of stalled host)
            # are in no counter the readers use
            profiler.start(trace_dir)
            time.sleep(float(mix.get("trace_seconds", 3.0)))
            profiler.stop()
        due = [i for i, r in enumerate(schedule)
               if lead_in <= r.t_due < lead_in + seconds]
        give_up = close["t"] + deadline_s
        while time.perf_counter() < give_up and not all(
                reqs[i] is not None and reqs[i].done for i in due):
            time.sleep(0.01)
    finally:
        stop.set()
        thread.join()
        finished = [req is not None and req.done for req in reqs]
        cut_short(reqs)
    if died:
        raise RuntimeError("the load generator died") from died[0]
    if not wait_idle(loop, 120):
        raise RuntimeError(f"serve loop not idle 120 s after the cut: "
                           f"{loop.stats()}")
    rows = []
    for i in due:
        r, req = schedule[i], reqs[i]
        rows.append({
            "index": i, "t_due": t0 + r.t_due, "t_submit": t_submit[i],
            "n_out_wanted": r.new_tokens, "prompt_len": int(r.prompt.size),
            "finished": bool(finished[i]),
            "error": None if req is None or req.error is None
            else f"{type(req.error).__name__}: {req.error}",
            "t_first": req.t_first if req is not None else None,
            "t_done": req.t_done if finished[i] else None,
            "out": list(req.out) if finished[i] else None,
        })
    return {"open": open_, "close": close, "rows": rows, "samples": samples,
            "window_s": close["t"] - open_["t"],
            "compiles_in_window": close["acct"]["backend_compiles"]
            - open_["acct"]["backend_compiles"]}


def plan(config, mix, seed, seconds):
    """The schedule of one run: lead-in, window, and the tail that keeps
    load on while the window's last requests finish."""
    horizon = (float(mix.get("lead_in_s", 0.0)) + seconds
               + float(mix.get("deadline_s", 0.0))
               + float(mix.get("trace_seconds", 3.0)) + 2.0)
    kwargs = config["serve"]
    return build_schedule(mix, seed, int(config["vocab_size"]),
                          int(kwargs["max_seq_len"]), horizon)


def knee_line(mix, samples, max_active):
    """For a mix that declares `headroom` (offered at that many times its
    knee, so that completed tokens/s is capacity): the log line that says
    whether the window ran above the knee, from the share of decode slots
    that produced a token. Under 90 % the server drained what was offered,
    the cell reads offered load, and the next issue re-rates the mix
    (benchmark/README.md). It decides nothing: a program that got faster
    must not fail its own cell. None for a mix without `headroom`, or
    when the program's `stats()` does not count decode tokens."""
    fill = slot_fill(samples, max_active)
    if "headroom" not in mix or fill is None:
        return None
    verdict = ("above its knee" if fill >= 0.9 else
               "below its knee: re-rate the mix (benchmark/README.md)")
    return (f"serve_open_loop: mix {mix['name']} declares {mix['headroom']} "
            f"x its knee of {mix.get('knee_rps')}/s: decode_tokens / (steps "
            f"x max_active) = {100.0 * fill:.2f} % over the window, {verdict}")


def load_line(m):
    """The log line that tells a stalled run from a slow program: the
    window's beats, the longest time the scheduler's `steps` stood still
    and the longest gap between two of the driver's samples (one or two
    sampling intervals in a sound run, seconds where the serve loop or the
    whole process was held), the generator's worst lateness over the
    requests due in the window and how many it submitted over 100 ms late.
    It decides nothing."""
    late = [(r["t_submit"] - r["t_due"]) * 1e3 for r in m["rows"]
            if r["t_submit"]]
    still, gap = longest_still_s(m["samples"]) or (float("nan"),) * 2
    return (f"serve_open_loop: load: {m['close']['steps'] - m['open']['steps']}"
            f" beats in {m['window_s']:.3f} s, longest without a beat "
            f"{still:.3f} s, longest gap between samples {gap:.3f} s, "
            f"generator latest {max(late, default=0.0):.1f} ms with "
            f"{sum(x > 100.0 for x in late)} over 100 ms")


def check(config, net, loop, m, vocab):
    """(why the run is not correct: an empty list when it is; every number
    compared beside its limit, {name: [value, limit]})."""
    from paddle_tpu.core import monitor
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
    fc = config["forced_check"]
    import jax.numpy as jnp
    errs = forced_logits(net, fc["prompt_lens"], int(fc["bucket"]),
                         loop.stats()["block_size"],
                         jnp.dtype(config["dtype"]), seed=0)
    if not monitor.stat_get(
            "pallas.gate_reject.paged_decode_attention.flag_off"):
        why.append("teacher-forced check never traced paged_attention_ref")
    for beat, err in errs.items():
        if not err <= float(fc["tol"]):
            why.append(f"teacher-forced {beat} logits: kernel vs "
                       f"paged_attention_ref err {err:.3g} > {fc['tol']}")
    compared = {"requests_errored": [len(errors), 0],
                "outputs_malformed": [len(malformed), 0],
                "compiles_in_window": [m["compiles_in_window"], 0]}
    compared.update({f"forced_{beat}_logits_err": [err, float(fc["tol"])]
                     for beat, err in errs.items()})
    return why, compared


def run(cell):
    import jax
    from paddle_tpu.core import monitor
    config, mix = cell.config, cell.traffic
    if cell.chips != 1:
        raise SystemExit("serve_open_loop: one server on one chip")
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
    why, compared = check(config, net, loop, m, vocab)
    rows = m["rows"]
    failed = sum(bool(r["error"]) or (
        bool(mix.get("unfinished_is_failure")) and not r["finished"])
        for r in rows)
    delta = {k: m["close"]["counters"][k] - m["open"]["counters"][k]
             for k in COUNTERS}
    print(f"serve_open_loop: buckets {buckets}, block_size "
          f"{stats['block_size']}, {len(rows)} due in {m['window_s']:.3f} s, "
          f"{sum(r['finished'] for r in rows)} finished, window counters "
          f"{delta}, compared {compared}", flush=True)
    line = knee_line(mix, m["samples"], stats["max_active"])
    if line:
        print(line, flush=True)
    print(load_line(m), flush=True)
    for what, xs in (("ttft_ms", [(r["t_first"] - r["t_due"]) * 1e3
                                  for r in rows if r["t_first"]]),
                     ("late_ms", [(r["t_submit"] - r["t_due"]) * 1e3
                                  for r in rows if r["t_submit"]])):
        print(f"serve_open_loop: {len(xs)} samples of {what} "
              f"({samples_beyond(len(xs), 95)} beyond p95), sorted: "
              f"{[round(x, 1) for x in sorted(xs)]}", flush=True)
    return {
        "correct": not why and not failed, "why_incorrect": why,
        "compared": compared, "attempted": len(rows), "failed": failed,
        "setup_s": m["open"]["t"] - cell.t_process_start,
        "window_s": m["window_s"], "chips": 1, "rows": rows,
        "counters": delta,
        "steps": m["close"]["steps"] - m["open"]["steps"],
        "samples": m["samples"], "max_active": stats["max_active"],
        "kv_blocks": int(config["serve"]["kv_blocks"]),
        "compiles_in_window": m["compiles_in_window"],
        "device_kind": jax.devices()[0].device_kind,
        "kernel_patterns": config.get("kernel_patterns", {}),
        "module_patterns": config.get("module_patterns", {}),
    }
