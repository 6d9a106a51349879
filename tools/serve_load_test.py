"""Continuous-batching serve-tier load test (the ROADMAP "hundreds of
concurrent generate streams" proof).

Spins up a ServeLoop (inference/serving.py) over a tiny GPT and drives
SERVE_LOAD_STREAMS concurrent generate streams from SERVE_LOAD_CLIENTS
client threads with jittered arrivals — far more streams than decode
slots, so the run exercises admission scheduling, pool backpressure and
retire-then-admit churn, not just the fused decode step. Reports
tokens/s, p50/p99 time-to-first-token and p50/p99 per-token latency, the
serve.* gauge snapshot, and FAILS (exit 1) on any request error. With
SERVE_LOAD_VERIFY=N, N randomly chosen streams are cross-checked
token-for-token against per-request sequential `GPT.generate` — the
continuous-batching correctness oracle running inside the load test
itself.

Run: JAX_PLATFORMS=cpu python tools/serve_load_test.py

Env knobs (defaults are the CPU-valid tier-1 shape):
  SERVE_LOAD_STREAMS=256   concurrent generate streams
  SERVE_LOAD_CLIENTS=32    client threads submitting them
  SERVE_LOAD_PROMPT=12     max prompt length (ragged 4..PROMPT)
  SERVE_LOAD_NEW=16        tokens generated per stream
  SERVE_LOAD_SLOTS=64      decode slots (ServeConfig.max_active)
  SERVE_LOAD_BLOCKS=160    KV pool blocks
  SERVE_LOAD_BLOCK_SIZE=16 tokens per pool block
  SERVE_LOAD_VERIFY=4      streams cross-checked vs sequential generate

framework_lint TOOL_CROSS_CHECKS runs self_check() here: the
FLAGS_serve_* defaults, tools/hlo_evidence.py's SERVE_CFG, and
docs/serving.md must agree.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

STREAMS = int(os.environ.get("SERVE_LOAD_STREAMS", 256))
CLIENTS = int(os.environ.get("SERVE_LOAD_CLIENTS", 32))
PROMPT = int(os.environ.get("SERVE_LOAD_PROMPT", 12))
NEW = int(os.environ.get("SERVE_LOAD_NEW", 16))
SLOTS = int(os.environ.get("SERVE_LOAD_SLOTS", 64))
BLOCKS = int(os.environ.get("SERVE_LOAD_BLOCKS", 160))
BLOCK_SIZE = int(os.environ.get("SERVE_LOAD_BLOCK_SIZE", 16))
VERIFY = int(os.environ.get("SERVE_LOAD_VERIFY", 4))

# flag defaults this tool (and docs/serving.md's flag table) are written
# against; drift means the doc + this header need an update
SERVE_FLAG_DEFAULTS = {
    "FLAGS_use_paged_attention": True,
    "FLAGS_serve_block_size": 0,
    "FLAGS_serve_kv_blocks": 512,
    "FLAGS_serve_max_active": 64,
}


def run():
    import paddle_tpu as paddle
    from paddle_tpu.core import monitor
    from paddle_tpu.inference import ServeConfig, ServeLoop
    from paddle_tpu.text.models.gpt import GPT, GPTConfig
    from paddle_tpu.traffic import harness

    paddle.seed(0)
    cfg = GPTConfig.tiny()
    net = GPT(cfg)
    net.eval()
    cap = min(cfg.max_seq_len, PROMPT + NEW + BLOCK_SIZE)
    loop = ServeLoop(net, ServeConfig(max_active=SLOTS, kv_blocks=BLOCKS,
                                      block_size=BLOCK_SIZE,
                                      max_seq_len=cap))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size,
                           (int(rng.randint(4, PROMPT + 1)),))
               .astype(np.int64) for _ in range(STREAMS)]

    # compile outside the timed window: the decode step plus ONE prefill
    # per bucket the ragged prompts can land in (a cold bucket would put
    # an XLA compile inside the timed p99)
    buckets = {}
    for p in prompts:
        b = 8
        while b < p.size:
            b *= 2
        buckets.setdefault(b, p)
    for p in buckets.values():
        loop.serve([p], max_new_tokens=2)
    monitor.reset(prefix="serve.")
    loop.start()

    # same jitter stream the hand-rolled client loop drew: client `cid`
    # takes the stride cid, cid+CLIENTS, ... and sleeps a fresh
    # RandomState(1000+cid).uniform(0, 2ms) before each submit — the
    # harness honors per-submission delays in that exact stride order
    delays = [0.0] * STREAMS
    for cid in range(CLIENTS):
        crng = np.random.RandomState(1000 + cid)
        for i in range(cid, STREAMS, CLIENTS):
            delays[i] = float(crng.uniform(0, 0.002))
    stats = harness.drive_serve(
        loop, harness.submissions_from_prompts(prompts, NEW, delays),
        clients=CLIENTS, wait="result", result_timeout_s=600.0)
    loop.stop()
    outs = stats.outs
    toks = stats.tokens
    ttfts, per_tok = stats.ttfts_ms, stats.token_ms
    errors = stats.errors
    dt = stats.wall_s

    verified = 0
    if VERIFY:
        idxs = np.random.RandomState(7).choice(
            STREAMS, size=min(VERIFY, STREAMS), replace=False)
        for i in sorted(int(x) for x in idxs):
            if outs[i] is None:
                continue
            ref = np.asarray(net.generate(
                paddle.to_tensor(prompts[i][None]), max_new_tokens=NEW,
                temperature=0, use_cache=True).numpy())[0,
                                                        prompts[i].size:]
            if not np.array_equal(outs[i], ref):
                errors.append(
                    f"verify[{i}]: serve tokens != sequential generate "
                    f"({outs[i].tolist()} vs {ref.tolist()})")
            else:
                verified += 1

    # ONE percentile estimator across serve_load_test / ps_load_test /
    # online_drill (core/slo.py) — the numbers in the three reports are
    # comparable because they share the implementation
    from paddle_tpu.core.slo import percentile

    def pct(xs, p):
        return percentile(xs, p, ndigits=3)

    snap = {k: v for k, v in monitor.stats("serve.").items()}
    report = {
        "tool": "tools/serve_load_test.py",
        "streams": STREAMS,
        "clients": CLIENTS,
        "slots": SLOTS,
        "kv_blocks": BLOCKS,
        "block_size": BLOCK_SIZE,
        "tokens": toks,
        "tokens_per_s": round(toks / dt, 2),
        "wall_s": round(dt, 3),
        "ttft_ms": {"p50": pct(ttfts, 50), "p99": pct(ttfts, 99)},
        "token_ms": {"p50": pct(per_tok, 50), "p99": pct(per_tok, 99)},
        "preempted": int(snap.get("serve.preempted", 0)),
        "completed": int(snap.get("serve.requests_completed", 0)),
        "verified_vs_generate": verified,
        "request_errors": len(errors),
    }
    print(json.dumps(report, indent=1))
    for e in errors[:10]:
        print("ERROR:", e, file=sys.stderr)
    return 1 if errors else 0


# --------------------------------------------------------------------------
# framework_lint cross-check (TOOL_CROSS_CHECKS)
# --------------------------------------------------------------------------

def self_check():
    """Serve knobs <-> flag defaults <-> hlo_evidence serve_decode
    config <-> docs. Returns violations."""
    problems = []
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        from paddle_tpu.core import flags as _flags
    except Exception as e:  # pragma: no cover
        return [f"serve_load_test: paddle_tpu import failed: {e!r}"]
    for name, want in SERVE_FLAG_DEFAULTS.items():
        defn = _flags._DEFS.get(name)
        if defn is None:
            problems.append(f"serve_load_test: flag {name} is no longer "
                            "defined in core/flags.py")
        elif defn[1] != want:
            problems.append(
                f"serve_load_test: {name} default drifted "
                f"({defn[1]!r} != {want!r}) — update SERVE_FLAG_DEFAULTS "
                "and docs/serving.md")
    # hlo_evidence's serve_decode config
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import hlo_evidence
        scfg = hlo_evidence.SERVE_CFG
        if scfg["slots"] != SERVE_FLAG_DEFAULTS["FLAGS_serve_max_active"]:
            problems.append(
                "serve_load_test: hlo_evidence SERVE_CFG slots "
                f"{scfg['slots']} != FLAGS_serve_max_active default")
        if scfg["blocks"] != SERVE_FLAG_DEFAULTS["FLAGS_serve_kv_blocks"]:
            problems.append(
                "serve_load_test: hlo_evidence SERVE_CFG blocks "
                f"{scfg['blocks']} != FLAGS_serve_kv_blocks default")
    except Exception as e:  # pragma: no cover
        problems.append(
            f"serve_load_test: cannot cross-check hlo_evidence: {e!r}")
    # docs
    doc_path = os.path.join(repo, "docs", "serving.md")
    try:
        with open(doc_path) as f:
            doc = f.read()
    except OSError as e:
        return problems + [f"serve_load_test: cannot read {doc_path}: {e}"]
    for name in SERVE_FLAG_DEFAULTS:
        if name not in doc:
            problems.append(f"serve_load_test: flag {name} is not "
                            "documented in docs/serving.md")
    if "serve_load_test" not in doc:
        problems.append("serve_load_test: docs/serving.md omits the drill")
    # the p50/p99 lines must come from the shared estimator, and it must
    # round-trip the exact values this report's pins were written against
    try:
        from paddle_tpu.core.slo import percentile
        if percentile([1.0, 2.0, 3.0, 4.0], 50, ndigits=3) != 2.5:
            problems.append("serve_load_test: core.slo.percentile no "
                            "longer matches np.percentile semantics")
        if percentile([], 99, ndigits=3) is not None:
            problems.append("serve_load_test: core.slo.percentile([]) "
                            "must be None (empty stream)")
    except Exception as e:
        problems.append(
            f"serve_load_test: shared percentile estimator gone: {e!r}")
    with open(os.path.abspath(__file__)) as f:
        self_src = f.read()
    if "from paddle_tpu.core.slo import percentile" not in self_src:
        problems.append("serve_load_test: report percentiles must come "
                        "from core.slo.percentile (shared estimator)")
    if "harness.drive_serve" not in self_src:
        problems.append("serve_load_test: the client submit loop must be "
                        "the shared paddle_tpu.traffic.harness.drive_serve")
    return problems


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--self-check" in argv or "--self_check" in argv:
        problems = self_check()
        for p in problems:
            print(p)
        print("serve_load_test self-check:",
              "clean" if not problems else f"{len(problems)} problem(s)")
        return 1 if problems else 0
    return run()


if __name__ == "__main__":
    sys.exit(main())
