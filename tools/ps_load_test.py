"""PS transport throughput measurement (VERDICT r04 item 9).

N worker threads x M rounds of pull_sparse + push_sparse_grad of
realistic batches against a local PSServer; reports rows/sec per op and
aggregate. Reference design point: distributed/communicator.cc (brpc,
millions of sparse rows/sec across a cluster); this measures our
pickle-frames-over-TCP transport on one host and records the number
in docs/ps_throughput.md so regressions are visible.

Run: JAX_PLATFORMS=cpu python tools/ps_load_test.py

Modes (env):
  PS_LOAD_CHAOS=<seed>  throughput UNDER seeded resets + dropped replies
                        (the retry/replay path's overhead).
  PS_LOAD_FAILOVER=1    replicated-storage failover drill: a 3-server /
                        1-backup cluster under worker load, one primary
                        killed mid-run; reports promotion latency, the
                        ps.replica.* counters, and rows/sec through the
                        outage. Workers must finish with zero errors —
                        the live proof behind docs/fault_tolerance.md's
                        storage-tier section.
  PS_LOAD_SHARDED=1     sharded-embedding drill: workers train through
                        the FULL engine — batched deduped cross-shard
                        lookups, the tiered HeterPS LRU cache, and the
                        async prefetch stage — against a 3-shard-server
                        / 1-backup cluster, with one shard primary
                        killed mid-run. Reports per-shard rows/s, cache
                        hit rate, prefetch overlap ratio, and promotion
                        latency; zero worker errors required.

framework_lint TOOL_CROSS_CHECKS runs self_check() here: the
PADDLE_PS_REPLICA_*/PADDLE_PS_HEARTBEAT_*/PADDLE_PS_FAILOVER_* +
PADDLE_PS_{FANOUT,PREFETCH,HETER}* flag defaults, this tool's
failover/sharded-mode knobs, and docs/fault_tolerance.md must agree.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

from paddle_tpu.distributed.ps import PSClient, PSServer  # noqa: E402

VOCAB = 200_000
DIM = int(os.environ.get("PS_LOAD_DIM", 16))
WORKERS = int(os.environ.get("PS_LOAD_WORKERS", 4))
ROUNDS = int(os.environ.get("PS_LOAD_ROUNDS", 50))
BATCH_IDS = int(os.environ.get("PS_LOAD_BATCH", 2048))

# failover-drill knobs (PS_LOAD_FAILOVER mode); the heartbeat pair is
# deliberately faster than the PADDLE_PS_HEARTBEAT_* prod defaults —
# self_check() pins BOTH against docs/fault_tolerance.md
FAILOVER_SERVERS = int(os.environ.get("PS_LOAD_SERVERS", 3))
FAILOVER_HB_S = float(os.environ.get("PS_LOAD_HB_S", 0.1))
FAILOVER_HB_TIMEOUT_S = float(os.environ.get("PS_LOAD_HB_TIMEOUT_S", 0.7))

# sharded-embedding-drill cache bound (PS_LOAD_SHARDED mode): small
# enough that the random workload exercises LRU eviction + the host tier
SHARDED_CACHE_ROWS = int(os.environ.get("PS_LOAD_CACHE_ROWS", 8192))

# flag defaults this tool (and the docs flag table) are written against;
# drift here means docs/fault_tolerance.md + this header need an update
REPLICA_FLAG_DEFAULTS = {
    "PADDLE_PS_REPLICA_BACKUPS": 0,
    "PADDLE_PS_REPLICA_QUORUM": 0,
    "PADDLE_PS_REPLICA_DELTA_LOG": 512,
    "PADDLE_PS_HEARTBEAT_S": 0.5,
    "PADDLE_PS_HEARTBEAT_TIMEOUT_S": 3.0,
    "PADDLE_PS_FAILOVER_RETRIES": 8,
    "PADDLE_PS_FAILOVER_BACKOFF_S": 0.25,
    # sharded embedding engine (PS_LOAD_SHARDED drill)
    "PADDLE_PS_FANOUT_THREADS": 4,
    "PADDLE_PS_PREFETCH_DEPTH": 2,
    "PADDLE_PS_HETER_CACHE_ROWS": 65536,
    "PADDLE_PS_HETER_HOST_ROWS": 262144,
}


def run_worker(endpoints, wid, results):
    client = PSClient(endpoints)
    rng = np.random.RandomState(wid)
    pulled = pushed = 0
    round_ms = []
    t0 = time.perf_counter()
    for _ in range(ROUNDS):
        tr = time.perf_counter()
        ids = np.unique(rng.randint(0, VOCAB, BATCH_IDS).astype(np.int64))
        rows = client.pull_sparse("emb", ids)
        pulled += len(ids)
        grads = np.asarray(rows, np.float32) * 0 + 0.01
        client.push_sparse_grad("emb", ids, grads)
        pushed += len(ids)
        round_ms.append((time.perf_counter() - tr) * 1e3)
    dt = time.perf_counter() - t0
    results[wid] = (pulled, pushed, dt, round_ms)
    client.close()


def run_failover():
    """PS_LOAD_FAILOVER: kill-and-promote under load. Reports the
    promotion latency (kill -> ps.replica.promotions tick), replica
    counters, and aggregate rows/sec through the outage."""
    from paddle_tpu.core import monitor
    from paddle_tpu.distributed.ps import ShardMap

    spec = {"emb": {"type": "sparse", "dim": DIM, "optimizer": "sgd",
                    "lr": 0.1, "init": "zeros"}}
    servers = [PSServer("127.0.0.1:0", dict(spec))
               for _ in range(FAILOVER_SERVERS)]
    eps = [s.start() for s in servers]
    smap = ShardMap.create(eps, n_backups=1)
    fast = dict(timeout=5.0, max_retries=2, backoff_base=0.01,
                backoff_max=0.05)
    for s in servers:
        s.enable_replication(shard_map=smap, peers=eps, n_backups=1,
                             heartbeat_s=FAILOVER_HB_S,
                             heartbeat_timeout_s=FAILOVER_HB_TIMEOUT_S,
                             rpc_opts=dict(fast))

    errors = []
    results = {}

    def worker(wid):
        client = PSClient(eps, **fast)
        rng = np.random.RandomState(wid)
        pushed = 0
        t0 = time.perf_counter()
        try:
            for _ in range(ROUNDS):
                ids = np.unique(rng.randint(0, VOCAB, BATCH_IDS)
                                .astype(np.int64))
                rows = client.pull_sparse("emb", ids)
                client.push_sparse_grad(
                    "emb", ids, np.asarray(rows, np.float32) * 0 + 0.01)
                pushed += len(ids)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(f"worker {wid}: {type(e).__name__}: {e}")
        results[wid] = (pushed, time.perf_counter() - t0)
        client.close()

    from paddle_tpu.traffic import harness
    pool = harness.run_worker_pool(worker, WORKERS, kill_after_s=0.5,
                                   on_kill=servers[0].shutdown)
    promote_latency = pool.promote_latency_s
    wall = pool.wall_s
    for s in servers[1:]:
        s.shutdown()

    total = sum(r[0] for r in results.values())
    replica = {k: int(v) for k, v in
               sorted(monitor.stats("ps.replica.").items())}
    print(f"failover drill: {FAILOVER_SERVERS} servers, 1 backup, "
          f"{WORKERS} workers x {ROUNDS} rounds, primary killed at 0.5s")
    print(f"promotion latency: "
          f"{'NONE RECORDED' if promote_latency is None else f'{promote_latency * 1000:.0f}ms'}"
          f" (heartbeat {FAILOVER_HB_S}s, deadline "
          f"{FAILOVER_HB_TIMEOUT_S}s)")
    print(f"rows pushed through the outage: {total:,} "
          f"({total / wall:,.0f} rows/sec aggregate)")
    print(f"replica counters: {replica}")
    if errors:
        print("worker errors:\n  " + "\n  ".join(errors))
        return 1
    if promote_latency is None:
        print("ERROR: no promotion was recorded")
        return 1
    print("all workers finished with zero errors")
    return 0


def run_sharded():
    """PS_LOAD_SHARDED: the full sharded-embedding engine under load +
    a kill-one-shard-primary drill. Workers pull through
    EmbeddingPrefetcher -> HeterPSCache -> PSClient's cross-shard
    fan-out and push merged grads back; shard 0's primary dies mid-run.
    Reports per-shard rows/s, cache hit rate, prefetch overlap ratio,
    promotion latency, and the replica counters."""
    from paddle_tpu.core import monitor
    from paddle_tpu.distributed.ps import (EmbeddingPrefetcher,
                                           HeterPSCache, ShardMap)

    spec = {"emb": {"type": "sparse", "dim": DIM, "optimizer": "sgd",
                    "lr": 0.1, "init": "uniform", "seed": 7}}
    servers = [PSServer("127.0.0.1:0", dict(spec))
               for _ in range(FAILOVER_SERVERS)]
    eps = [s.start() for s in servers]
    smap = ShardMap.create(eps, n_backups=1)
    fast = dict(timeout=5.0, max_retries=2, backoff_base=0.01,
                backoff_max=0.05)
    for s in servers:
        s.enable_replication(shard_map=smap, peers=eps, n_backups=1,
                             heartbeat_s=FAILOVER_HB_S,
                             heartbeat_timeout_s=FAILOVER_HB_TIMEOUT_S,
                             rpc_opts=dict(fast))

    errors = []
    results = {}

    def worker(wid):
        client = PSClient(eps, **fast)
        cache = HeterPSCache(client, "emb", DIM,
                             capacity=SHARDED_CACHE_ROWS)
        pf = EmbeddingPrefetcher(cache)
        rng = np.random.RandomState(wid)
        batches = [np.unique(rng.randint(0, VOCAB, BATCH_IDS)
                             .astype(np.int64)) for _ in range(ROUNDS)]
        pulled = 0
        # per-worker shard tally, merged after join — a shared
        # read-modify-write across worker threads would lose updates
        my_shard_rows = np.zeros(FAILOVER_SERVERS, np.int64)
        t0 = time.perf_counter()
        try:
            pf.prefetch(batches[0])
            for r in range(ROUNDS):
                ids = batches[r]
                rows = pf.get(ids)
                if r + 1 < ROUNDS:
                    pf.prefetch(batches[r + 1])
                pulled += len(ids)
                my_shard_rows += np.bincount(ids % FAILOVER_SERVERS,
                                             minlength=FAILOVER_SERVERS)
                pf.push_grad(ids, np.asarray(rows, np.float32) * 0 + 0.01)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(f"worker {wid}: {type(e).__name__}: {e}")
        finally:
            stats = pf.stats()
            try:
                pf.close()
            except Exception:
                pass
            client.close()
        results[wid] = (pulled, time.perf_counter() - t0, stats,
                        my_shard_rows)

    from paddle_tpu.traffic import harness
    pool = harness.run_worker_pool(worker, WORKERS, kill_after_s=0.5,
                                   on_kill=servers[0].shutdown)
    promote_latency = pool.promote_latency_s
    wall = pool.wall_s
    for s in servers[1:]:
        s.shutdown()

    total = sum(r[0] for r in results.values())
    shard_rows = np.sum([r[3] for r in results.values()], axis=0) \
        if results else np.zeros(FAILOVER_SERVERS, np.int64)
    hits = monitor.stat_get("ps.heter.hits")
    host_hits = monitor.stat_get("ps.heter.host_hits")
    misses = monitor.stat_get("ps.heter.misses")
    hit_rate = (hits + host_hits) / max(1, hits + host_hits + misses)
    overlaps = [r[2]["overlap_ratio"] for r in results.values()
                if r[2].get("pull_s")]
    print(f"sharded-embedding drill: {FAILOVER_SERVERS} shard servers, "
          f"1 backup each, {WORKERS} workers x {ROUNDS} rounds, shard-0 "
          "primary killed at 0.5s")
    print(f"promotion latency: "
          f"{'NONE RECORDED' if promote_latency is None else f'{promote_latency * 1000:.0f}ms'}"
          f" (heartbeat {FAILOVER_HB_S}s, deadline "
          f"{FAILOVER_HB_TIMEOUT_S}s)")
    print(f"rows pulled through the engine: {total:,} "
          f"({total / wall:,.0f} rows/sec aggregate)")
    for s in range(FAILOVER_SERVERS):
        print(f"  shard {s}: {int(shard_rows[s]):,} rows "
              f"({shard_rows[s] / wall:,.0f} rows/sec)")
    print(f"cache hit rate: {hit_rate:.1%} "
          f"(device {hits:,} + host {host_hits:,} hits, {misses:,} "
          "PS misses)")
    if overlaps:
        print(f"prefetch overlap ratio: {sum(overlaps) / len(overlaps):.2f}"
              f" (mean across {len(overlaps)} workers)")
    replica = {k: int(v) for k, v in
               sorted(monitor.stats("ps.replica.").items())}
    print(f"replica counters: {replica}")
    if errors:
        print("worker errors:\n  " + "\n  ".join(errors))
        return 1
    if promote_latency is None:
        print("ERROR: no promotion was recorded")
        return 1
    print("all workers finished with zero errors")
    return 0


def self_check():
    """framework_lint cross-check: flag defaults <-> this tool's knobs
    <-> docs/fault_tolerance.md. Returns a list of violations."""
    problems = []
    from paddle_tpu.core import flags as _flags
    for name, want in REPLICA_FLAG_DEFAULTS.items():
        defn = _flags._DEFS.get(name)
        if defn is None:
            problems.append(f"ps_load_test: flag {name} is no longer "
                            "defined in core/flags.py")
            continue
        if defn[1] != want:
            problems.append(
                f"ps_load_test: {name} default drifted "
                f"({defn[1]!r} != {want!r}) — update "
                "REPLICA_FLAG_DEFAULTS and docs/fault_tolerance.md")
    doc_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "fault_tolerance.md")
    try:
        with open(doc_path) as f:
            doc = f.read()
    except OSError as e:
        return problems + [f"ps_load_test: cannot read {doc_path}: {e}"]
    for name in REPLICA_FLAG_DEFAULTS:
        if name not in doc:
            problems.append(f"ps_load_test: flag {name} is not "
                            "documented in docs/fault_tolerance.md")
    if "PS_LOAD_FAILOVER" not in doc:
        problems.append("ps_load_test: the PS_LOAD_FAILOVER drill is not "
                        "documented in docs/fault_tolerance.md")
    if "PS_LOAD_SHARDED" not in doc:
        problems.append("ps_load_test: the PS_LOAD_SHARDED sharded-"
                        "embedding drill is not documented in "
                        "docs/fault_tolerance.md")
    for token in (f"heartbeat_s={FAILOVER_HB_S}",
                  f"heartbeat_timeout_s={FAILOVER_HB_TIMEOUT_S}"):
        if token not in doc:
            problems.append(
                f"ps_load_test: docs/fault_tolerance.md no longer states "
                f"the drill timing `{token}` — keep the doc's failover "
                "timeline in sync with PS_LOAD_HB_S/PS_LOAD_HB_TIMEOUT_S")
    # latency percentiles must come from the shared core/slo.py
    # estimator (same implementation as serve_load_test/online_drill)
    with open(os.path.abspath(__file__)) as f:
        self_src = f.read()
    if "from paddle_tpu.core.slo import percentile" not in self_src:
        problems.append("ps_load_test: round-latency percentiles must "
                        "come from core.slo.percentile")
    if "harness.run_worker_pool" not in self_src:
        problems.append("ps_load_test: the worker pool / kill-and-promote "
                        "loop must be the shared "
                        "paddle_tpu.traffic.harness.run_worker_pool")
    return problems


def main():
    if os.environ.get("PS_LOAD_SHARDED"):
        return run_sharded()
    if os.environ.get("PS_LOAD_FAILOVER"):
        return run_failover()
    srv = PSServer(tables={
        "emb": {"type": "sparse", "dim": DIM, "optimizer": "sgd",
                "lr": 0.1, "init": "zeros"}})
    srv.start()
    # PS_LOAD_CHAOS=<seed> measures throughput UNDER seeded faults
    # (resets + dropped replies), i.e. the retry/replay path's overhead
    chaos_seed = os.environ.get("PS_LOAD_CHAOS")
    if chaos_seed is not None:
        from paddle_tpu.testing import faults
        faults.install(faults.FaultInjector(
            seed=chaos_seed, p={faults.RESET: 0.01, faults.DROP: 0.01}))
    try:
        endpoints = [srv.endpoint]
        results = {}
        from paddle_tpu.traffic import harness
        wall = harness.run_worker_pool(
            lambda wid: run_worker(endpoints, wid, results),
            WORKERS).wall_s
    finally:
        srv.shutdown()

    total_pulled = sum(r[0] for r in results.values())
    total_pushed = sum(r[1] for r in results.values())
    rows_sec = (total_pulled + total_pushed) / wall
    pull_sec = total_pulled / wall
    push_sec = total_pushed / wall
    print(f"workers={WORKERS} rounds={ROUNDS} batch~{BATCH_IDS} dim={DIM}")
    print(f"pull rows/sec: {pull_sec:,.0f}")
    print(f"push rows/sec: {push_sec:,.0f}")
    print(f"aggregate rows/sec: {rows_sec:,.0f} (wall {wall:.2f}s)")
    # per-round (pull+push) latency through the SHARED estimator
    # (core/slo.py) so this line is comparable with serve_load_test's
    # ttft percentiles and online_drill's round percentiles
    from paddle_tpu.core.slo import percentile
    round_ms = [ms for r in results.values() for ms in r[3]]
    print(f"round latency ms: p50={percentile(round_ms, 50, ndigits=3)} "
          f"p99={percentile(round_ms, 99, ndigits=3)} "
          f"(n={len(round_ms)})")
    from paddle_tpu.core import monitor
    health = {k: int(v) for k, v in sorted(monitor.stats("ps.").items())}
    print(f"transport health counters: {health or 'all zero'}")

    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "ps_throughput.md")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        f.write(
            "# PS transport throughput\n\n"
            "Measured by `tools/ps_load_test.py` (local PSServer, "
            f"{WORKERS} worker threads x {ROUNDS} rounds of pull+push of "
            f"~{BATCH_IDS} unique rows, dim={DIM}, sgd accessor):\n\n"
            f"| pull rows/s | push rows/s | aggregate rows/s |\n"
            f"|---|---|---|\n"
            f"| {pull_sec:,.0f} | {push_sec:,.0f} | {rows_sec:,.0f} |\n\n"
            "Context: the reference's brpc Communicator targets millions "
            "of rows/sec across a cluster of servers; this single-host "
            "pickle-frame TCP transport serves the functional PS story "
            "(tables, accessors, geo/async modes). The dense-training "
            "path never touches it — embeddings ride XLA. Scaling knobs "
            "if it ever gates a workload: batch frames are already one "
            "roundtrip per table op; next would be multi-connection "
            "striping per server.\n")
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
