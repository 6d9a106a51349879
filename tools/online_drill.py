"""Online-learning loop drill (the ROADMAP "close the serve->train
loop" proof, runnable as an operator tool).

Drives the full closed loop from docs/online_learning.md end to end:
a ServeLoop over a tiny GPT emits completion records at retire; a
dataset/streaming.StreamingDataset turns the deliberately-duplicated
record feed into exactly-once training batches; the continuous Downpour
trainer (ps_config mode="online") pushes replay-keyed deltas into a
3-server replicated geo_sparse cluster; EmbeddingSnapshotPublisher cuts
versioned snapshots and ServeLoop.publish_weights hot-swaps them
between decode beats. The whole run executes under seeded RESET+DROP
transport chaos, and (with >=2 rounds) a shard primary is killed
PERMANENTLY mid-drill — the trainer rides the failover re-route and the
publisher fetches through the promoted backup.

FAILS (exit 1) unless all of:
  - zero serve requests dropped or errored across every hot-swap
  - stream accounting exact: every record accepted once, every
    duplicate rejected, every batch delivered once
  - exactly-once delta accounting: per-server `table.applied` matches
    the flush schedule replayed against the membership timeline
  - the served model measurably moved toward the traffic: the versioned
    eval metric strictly decreases across the published snapshots

Run: JAX_PLATFORMS=cpu python tools/online_drill.py

Env knobs (defaults are the CPU-valid tier-1 shape):
  ONLINE_DRILL_ROUNDS=3     serve->train->publish rounds (>=2 kills a
                            shard primary after round 1's train)
  ONLINE_DRILL_REQS=6       serve requests per round
  ONLINE_DRILL_NEW=6        tokens generated per request
  ONLINE_DRILL_BATCH=3      records per training batch (divides REQS)
  ONLINE_DRILL_SEED=11      chaos seed
  ONLINE_DRILL_CHAOS_PCT=2  per-event %% probability of RESET and DROP

framework_lint TOOL_CROSS_CHECKS runs self_check() here: the
PADDLE_STREAM_* / PADDLE_ONLINE_* flag defaults and
docs/online_learning.md must agree.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

ROUNDS = int(os.environ.get("ONLINE_DRILL_ROUNDS", 3))
REQS = int(os.environ.get("ONLINE_DRILL_REQS", 6))
NEW = int(os.environ.get("ONLINE_DRILL_NEW", 6))
BATCH = int(os.environ.get("ONLINE_DRILL_BATCH", 3))
SEED = int(os.environ.get("ONLINE_DRILL_SEED", 11))
CHAOS_PCT = float(os.environ.get("ONLINE_DRILL_CHAOS_PCT", 2))

# flag defaults this tool (and docs/online_learning.md's flag table)
# are written against; drift means the doc + this header need an update
ONLINE_FLAG_DEFAULTS = {
    "PADDLE_STREAM_QUEUE_CAP": 1024,
    "PADDLE_STREAM_DEDUPE_WINDOW": 4096,
    "PADDLE_ONLINE_SYNC_EVERY": 1,
    "PADDLE_ONLINE_STALENESS_BATCHES": 4,
}

FAST = dict(timeout=2.0, max_retries=2, backoff_base=0.01,
            backoff_max=0.05, connect_retry_s=5.0)
HB = dict(heartbeat_s=0.1, heartbeat_timeout_s=0.7)


def run():
    import paddle_tpu as paddle
    from paddle_tpu import static
    from paddle_tpu.core import monitor
    from paddle_tpu.dataset import StreamingDataset
    from paddle_tpu.distributed.ps import (EmbeddingPrefetcher,
                                           EmbeddingSnapshotPublisher,
                                           HeterPSCache, PSClient,
                                           PSServer, ShardMap)
    from paddle_tpu import nn, optimizer
    from paddle_tpu.inference import ServeConfig, ServeLoop
    from paddle_tpu.testing import faults
    from paddle_tpu.text.models.gpt import GPT, GPTConfig
    from paddle_tpu.traffic import harness

    if REQS % BATCH:
        print(f"ONLINE_DRILL_REQS={REQS} must be a multiple of "
              f"ONLINE_DRILL_BATCH={BATCH}", file=sys.stderr)
        return 2
    violations = []

    paddle.seed(0)
    cfg = GPTConfig.tiny()
    gpt = GPT(cfg)
    gpt.eval()
    vocab, dim = cfg.vocab_size, cfg.hidden_size
    target = np.random.RandomState(77).uniform(
        -0.5, 0.5, (vocab, dim)).astype(np.float32)

    n_srv = 3
    specs = {"wte": {"type": "geo_sparse", "dim": dim, "init": "zeros"}}
    servers = [PSServer("127.0.0.1:0", specs) for _ in range(n_srv)]
    eps = [s.start() for s in servers]
    smap = ShardMap.create(eps, n_backups=1)
    for s in servers:
        s.enable_replication(shard_map=smap, peers=eps, n_backups=1,
                             rpc_opts=dict(FAST), **HB)

    trained_ids = set()

    def _collate(recs):
        ids = np.concatenate([np.asarray(r["prompt"] + r["tokens"],
                                         np.int64) for r in recs])
        trained_ids.update(int(t) for t in ids)
        return {"ids": ids, "target": target[ids]}

    ds = StreamingDataset(batch_size=BATCH, collate=_collate,
                          name="online_drill")

    def _on_complete(rec):   # at-least-once transport: every record twice
        ds.offer(rec)
        ds.offer(rec)

    loop = ServeLoop(gpt, ServeConfig(max_active=4, kv_blocks=16,
                                      block_size=16, max_seq_len=64),
                     on_complete=_on_complete)
    wte_key = next(k for k, v in loop._params.items()
                   if tuple(v.shape) == (vocab, dim))
    wte0 = np.asarray(loop._params[wte_key]).copy()

    paddle.enable_static()
    main_prog = static.Program("online_drill")
    with static.program_guard(main_prog):
        ids_v = static.data("ids", [-1], "int64")
        tgt_v = static.data("target", [-1, dim], "float32")
        emb = nn.Embedding(vocab, dim)
        diff = emb(ids_v) - tgt_v
        # mean over tokens, sum over dim: per-occurrence row movement is
        # 2*lr*n/N <= 2*lr — a contraction toward the target for lr<0.5
        loss = paddle.ops.mean(paddle.ops.sum(diff * diff, axis=-1))
        optimizer.SGD(learning_rate=0.25).minimize(loss)
    emb_name = emb.weight.scope_name
    exe = static.Executor()

    client_t = PSClient(eps, **FAST)
    client_p = PSClient(eps, **FAST)
    cache = HeterPSCache(client_p, "wte", dim, capacity=256, host_rows=0)
    pub = EmbeddingSnapshotPublisher(client_p, "wte", cache=cache)
    prefetchers = []
    window = harness.Window(ds)
    holder = {}
    all_reqs = []
    snaps = []
    state = None

    def serve_phase(k):
        rng = np.random.RandomState(1000 + k)
        prompts = [rng.randint(0, 48, 4).astype(np.int64)
                   for _ in range(REQS)]
        stats = harness.drive_serve(
            loop, harness.submissions_from_prompts(prompts, NEW),
            wait="idle")
        for e in stats.errors:
            violations.append(f"serve phase {k}: {e}")
        all_reqs.extend(r for r in stats.requests if r is not None)

    def train_phase(n_batches):
        pf = EmbeddingPrefetcher(client_t, table="wte")
        prefetchers.append(pf)
        ps_cfg = {"client": client_t, "mode": "online", "sync_every": 1,
                  "trainer_id": 7,
                  "sparse": [{"param": emb_name, "slot": "ids",
                              "table": "wte", "prefetcher": pf}],
                  "on_batch": lambda d: holder.update(drv=d)}
        if state is not None:
            ps_cfg["state"] = state["online"]
        exe.train_from_dataset(
            program=main_prog, dataset=window.take(n_batches),
            ps_config=ps_cfg,
            start_batch=ds.stats()["delivered_batches"])
        drv = holder["drv"]
        if any(f is not None for f in drv._frozen):
            violations.append("a flush payload was still frozen "
                              "(un-acked) at end of a train phase")
        return {"online": drv.online_state(), "ds": ds.state_dict()}

    def publish_and_swap():
        version, _ = pub.publish()
        snap = pub.materialize(np.asarray(loop._params[wte_key]))
        loop.publish_weights(version, {wte_key: snap})
        loop.run_until_idle()               # applies between beats
        if loop.model_version != version:
            violations.append(
                f"hot-swap did not land: model_version "
                f"{loop.model_version} != published {version}")
        snaps.append(snap)

    kill_round = 1 if ROUNDS >= 2 else None
    k_kill = None
    before = monitor.stats("serve.")
    t0 = time.perf_counter()
    p = CHAOS_PCT / 100.0
    try:
        with faults.inject(seed=SEED, p={faults.RESET: p,
                                         faults.DROP: p}) as inj:
            for k in range(ROUNDS):
                serve_phase(k)
                state = train_phase(REQS // BATCH)
                if k == kill_round:
                    # a shard primary dies PERMANENTLY; the trainer and
                    # publisher ride the failover to the promoted backup
                    k_kill = len(holder["drv"].flush_log)
                    servers[0].shutdown()
                    deadline = time.perf_counter() + 15.0
                    while time.perf_counter() < deadline:
                        try:
                            client_t.refresh_shard_map()
                        except Exception:
                            pass
                        if eps[0] not in client_t.shard_map.servers:
                            break
                        time.sleep(0.1)
                    else:
                        violations.append(
                            f"no promotion after killing {eps[0]}")
                publish_and_swap()
            chaos_fired = {"reset": inj.fired(faults.RESET),
                           "drop": inj.fired(faults.DROP)}
    finally:
        for c in (client_t, client_p, *prefetchers):
            try:
                c.close()
            except Exception:
                pass
        for j, s in enumerate(servers):
            if kill_round is not None and j == 0:
                continue
            s.shutdown()
        paddle.disable_static()

    # ---- zero dropped serve requests across the hot-swaps ----
    want_reqs = ROUNDS * REQS
    done = sum(1 for r in all_reqs
               if r.done and len(r.result(timeout=0)) == NEW)
    if done != want_reqs:
        violations.append(f"{want_reqs - done} of {want_reqs} serve "
                          "requests dropped or truncated")
    errored = int(monitor.stat_get("serve.requests_errored")
                  - before.get("serve.requests_errored", 0))
    if errored:
        violations.append(f"{errored} serve requests errored")
    swaps = int(monitor.stat_get("serve.hot_swaps")
                - before.get("serve.hot_swaps", 0))
    if swaps != ROUNDS:
        violations.append(f"{swaps} hot-swaps landed, wanted {ROUNDS}")

    # ---- exactly-once stream accounting ----
    st = ds.stats()
    if not (st["accepted"] == want_reqs
            and st["duplicates"] == want_reqs
            and st["delivered_records"] == want_reqs
            and st["backlog"] == 0):
        violations.append(f"stream accounting off: {st}")

    # ---- exactly-once delta accounting: replay the flush schedule
    # against the membership timeline ----
    log = holder["drv"].flush_log
    if [seq for _, seq, _ in log] != list(range(len(log))):
        violations.append(f"flush seqs not contiguous: "
                          f"{[s for _, s, _ in log]}")
    expected = {ep: 0 for ep in eps}
    for _, seq, idlist in log:
        for s in sorted({int(i) % n_srv for i in idlist}):
            for ep in (eps[s], eps[(s + 1) % n_srv]):
                if k_kill is not None and seq >= k_kill and ep == eps[0]:
                    continue
                expected[ep] += 1
    applied = {}
    for j, s in enumerate(servers):
        if kill_round is not None and j == 0:
            continue
        applied[eps[j]] = s.table("wte").applied
        if applied[eps[j]] != expected[eps[j]]:
            violations.append(
                f"server {j} applied {applied[eps[j]]} deltas, schedule "
                f"replay expects {expected[eps[j]]} — exactly-once "
                "accounting broken")

    # ---- the served model measurably shifted toward the traffic ----
    ev = np.fromiter(sorted(trained_ids), np.int64)
    metric = [round(float(np.square(w[ev] - target[ev]).mean()), 6)
              for w in [wte0] + snaps]
    if any(b >= a for a, b in zip(metric, metric[1:])):
        violations.append(f"eval metric not strictly decreasing across "
                          f"snapshot versions: {metric}")

    # serving-tier latency through the SHARED estimator (core/slo.py) —
    # comparable with serve_load_test's p50/p99 because the
    # implementation is the same
    from paddle_tpu.core.slo import percentile
    ttfts = [r.ttft_s * 1e3 for r in all_reqs
             if getattr(r, "ttft_s", None) is not None]
    report = {
        "tool": "tools/online_drill.py",
        "rounds": ROUNDS,
        "requests": want_reqs,
        "completed": done,
        "ttft_ms": {"p50": percentile(ttfts, 50, ndigits=3),
                    "p99": percentile(ttfts, 99, ndigits=3)},
        "hot_swaps": swaps,
        "model_version": loop.model_version,
        "chaos_fired": chaos_fired,
        "primary_killed": kill_round is not None,
        "stream": {k: st[k] for k in ("accepted", "duplicates",
                                      "delivered_records",
                                      "delivered_batches", "backlog")},
        "flushes": len(log),
        "applied_per_server": {ep: int(n) for ep, n in applied.items()},
        "eval_metric_by_version": metric,
        "wall_s": round(time.perf_counter() - t0, 3),
        "violations": len(violations),
    }
    print(json.dumps(report, indent=1))
    for v in violations[:10]:
        print("VIOLATION:", v, file=sys.stderr)
    return 1 if violations else 0


# --------------------------------------------------------------------------
# framework_lint cross-check (TOOL_CROSS_CHECKS)
# --------------------------------------------------------------------------

def self_check():
    """Online-loop knobs <-> flag defaults <-> docs. Returns violations."""
    problems = []
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        from paddle_tpu.core import flags as _flags
    except Exception as e:  # pragma: no cover
        return [f"online_drill: paddle_tpu import failed: {e!r}"]
    for name, want in ONLINE_FLAG_DEFAULTS.items():
        defn = _flags._DEFS.get(name)
        if defn is None:
            problems.append(f"online_drill: flag {name} is no longer "
                            "defined in core/flags.py")
        elif defn[1] != want:
            problems.append(
                f"online_drill: {name} default drifted "
                f"({defn[1]!r} != {want!r}) — update ONLINE_FLAG_DEFAULTS "
                "and docs/online_learning.md")
    # docs
    doc_path = os.path.join(repo, "docs", "online_learning.md")
    try:
        with open(doc_path) as f:
            doc = f.read()
    except OSError as e:
        return problems + [f"online_drill: cannot read {doc_path}: {e}"]
    for name in ONLINE_FLAG_DEFAULTS:
        if name not in doc:
            problems.append(f"online_drill: flag {name} is not "
                            "documented in docs/online_learning.md")
    if "online_drill" not in doc:
        problems.append("online_drill: docs/online_learning.md omits it")
    # ttft percentiles must come from the shared core/slo.py estimator
    with open(os.path.abspath(__file__)) as f:
        self_src = f.read()
    if "from paddle_tpu.core.slo import percentile" not in self_src:
        problems.append("online_drill: report ttft percentiles must "
                        "come from core.slo.percentile")
    for token in ("harness.drive_serve", "harness.Window"):
        if token not in self_src:
            problems.append(f"online_drill: the serve/window plumbing "
                            f"must come from paddle_tpu.traffic.harness "
                            f"(`{token}` missing)")
    return problems


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--self-check" in argv or "--self_check" in argv:
        problems = self_check()
        for p in problems:
            print(p)
        print("online_drill self-check:",
              "clean" if not problems else f"{len(problems)} problem(s)")
        return 1 if problems else 0
    return run()


if __name__ == "__main__":
    sys.exit(main())
