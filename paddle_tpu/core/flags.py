"""Global flag registry.

TPU-native analog of the reference's gflags backbone
(reference: paddle/fluid/platform/flags.cc:33-565 and
pybind/global_value_getter_setter.cc): flags are declared once with a type
and default, may be seeded from `FLAGS_*` environment variables at import
time (matching fluid/__init__.py __bootstrap__), and are get/set-able at
runtime via `paddle_tpu.set_flags` / `get_flags`.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Dict

_LOCK = threading.Lock()
_REGISTRY: Dict[str, Any] = {}
_DEFS: Dict[str, tuple] = {}  # name -> (type, default, help)


def define_flag(name: str, default, help_str: str = ""):
    ftype = type(default)
    with _LOCK:
        _DEFS[name] = (ftype, default, help_str)
        env = os.environ.get(name)
        if env is not None:
            _REGISTRY[name] = _parse(ftype, env)
        else:
            _REGISTRY[name] = default


def _parse(ftype, text: str):
    if ftype is bool:
        return text.strip().lower() in ("1", "true", "yes", "on")
    return ftype(text)


def set_flags(flags: Dict[str, Any]):
    """paddle.set_flags equivalent."""
    with _LOCK:
        for name, value in flags.items():
            if name not in _DEFS:
                raise KeyError(f"unknown flag {name!r}")
            ftype = _DEFS[name][0]
            _REGISTRY[name] = _parse(ftype, value) if isinstance(value, str) and ftype is not str else ftype(value)


def get_flags(flags):
    if isinstance(flags, str):
        flags = [flags]
    with _LOCK:
        return {name: _REGISTRY[name] for name in flags}


def flag(name: str):
    """Fast internal accessor."""
    return _REGISTRY[name]


# Core flag set (subset of reference platform/flags.cc relevant to TPU).
define_flag("FLAGS_check_nan_inf", False,
            "validate op outputs for nan/inf each step (reference platform/flags.cc:44)")
define_flag("FLAGS_benchmark", False, "sync and time each op")
define_flag("FLAGS_eager_delete_tensor_gb", 0.0, "GC threshold (no-op on XLA; kept for parity)")
define_flag("FLAGS_use_bf16_matmul", True, "prefer bfloat16 matmul accumulation on MXU")
define_flag("FLAGS_seed", 0, "global random seed")
define_flag("FLAGS_log_level", 0, "verbose log level (glog VLOG equivalent)")
define_flag("FLAGS_allocator_strategy", "xla", "kept for parity; XLA owns device memory")
define_flag("FLAGS_enable_profiler", False, "enable host event profiler")
define_flag("FLAGS_log_memory_estimate", False,
            "on each fresh Executor lowering, run the liveness-based "
            "peak-memory estimator (static/shape_infer.py analyze_memory) "
            "and publish executor/estimated_peak_bytes to the monitor")
define_flag("FLAGS_log_spmd_estimate", False,
            "on each fresh Executor lowering with a registered mesh, run "
            "the SPMD sharding analyzer (static/spmd_analyzer.py) and "
            "publish the spmd.{collective_bytes,hbm_estimate,"
            "resharding_count} monitor gauges (non-strict; set "
            "PADDLE_TPU_VERIFY_SPMD=1 to FAIL compilation on findings)")
define_flag("FLAGS_spmd_plan_beam", 4,
            "beam width of the auto-sharding planner's grouped search "
            "(static/spmd_planner.py). Must be wide enough to carry a "
            "chain-opening candidate (column-parallel qkv is illegal "
            "until the row-parallel out-proj closes the chain) past the "
            "always-legal replicated state")
define_flag("FLAGS_spmd_plan_sweeps", 1,
            "coordinate-descent polish passes the planner runs over the "
            "beam winner (feasible moves only; 0 disables)")
define_flag("FLAGS_spmd_plan_coll_weight", 1.0,
            "planner objective weight on predicted collective bytes/step "
            "(spmd_analyzer pricing)")
define_flag("FLAGS_spmd_plan_hbm_weight", 1.0,
            "planner objective weight on predicted peak per-device HBM "
            "bytes")
define_flag("FLAGS_spmd_plan_pp_micro", 8,
            "microbatch count the pipeline stage-cut planner prices a "
            "step with (static/spmd_planner.plan_pipeline): bubble "
            "fraction, ppermute wire bytes and per-tick hidden payload "
            "all scale with it")
define_flag("FLAGS_spmd_plan_pp_beam", 8,
            "beam width of the stage-cut search over legal cut "
            "boundaries (diagnostic-stratified, same machinery as the "
            "SPMD layout beam)")
define_flag("FLAGS_spmd_plan_pp_flops_weight", 1.0,
            "stage-cut objective weight on the pipeline-full compute "
            "proxy max(stage FLOPs) * num_micro (compute balance)")
define_flag("FLAGS_spmd_plan_pp_wire_weight", 1.0,
            "stage-cut objective weight on the ppermute wire bytes/step "
            "(pipeline.schedule_collectives of the cut frontier)")
define_flag("FLAGS_spmd_plan_pp_hbm_weight", 1.0,
            "stage-cut objective weight on max per-stage peak HBM "
            "(analyze_memory restricted to each stage's op range)")
define_flag("FLAGS_spmd_plan_pp_bubble_weight", 1.0,
            "stage-cut objective weight on the bubble cost "
            "bubble_fraction * total FLOPs (idle compute)")
define_flag("FLAGS_topology_ici_gbps", 90.0,
            "assumed per-device intra-pod (ICI) link bandwidth in GB/s "
            "for the two-tier topology cost model (mesh.axis_tiers / "
            "spmd_analyzer per-collective cost_us pricing)")
define_flag("FLAGS_topology_dcn_gbps", 6.25,
            "assumed per-device inter-pod (DCN) link bandwidth in GB/s — "
            "an order of magnitude below ICI, the cliff the hierarchical "
            "dp sync decomposition exists to avoid")
define_flag("FLAGS_topology_localsgd_k", 4,
            "k_steps the topology report prices the LocalSGD degraded "
            "sync mode with (one cross-replica average every k local "
            "steps amortizes the dp sync wire bytes by 1/k)")
define_flag("FLAGS_topology_localsgd_ratio", 8.0,
            "DCN-dominance threshold: when even the HIERARCHICAL dp "
            "sync's inter-pod cost_us exceeds its intra-pod cost_us by "
            "this factor, the topology report recommends the LocalSGD "
            "degraded mode instead (accuracy-for-bandwidth trade)")
define_flag("PADDLE_TRAFFIC_SEED", 0,
            "base seed for the traffic lab's named splitmix64 draw "
            "streams (traffic/workload.py); two runs of the same spec "
            "with the same seed are byte-identical — schedule AND "
            "per-request token draws")
define_flag("PADDLE_TRAFFIC_TIME_SCALE", 1.0,
            "wall-clock multiplier the harness paces a workload "
            "schedule with (traffic/harness.run_spec): 1.0 replays the "
            "spec in real time, 0.5 compresses it 2x (stress), 2.0 "
            "stretches it (debug)")
define_flag("PADDLE_TRAFFIC_CLIENTS", 4,
            "number of submitter threads the traffic harness partitions "
            "a schedule across (round-robin by event index)")
define_flag("FLAGS_capacity_p50_band_pct", 25.0,
            "capacity_plan --validate error band: hub-observed "
            "throughput and TTFT/token p50 must land within this "
            "percentage of the model's prediction")
define_flag("FLAGS_capacity_p99_band_pct", 40.0,
            "capacity_plan --validate error band for the tail: "
            "hub-observed TTFT/token p99 within this percentage of "
            "prediction (tails carry sampling noise the p50 band "
            "doesn't)")
define_flag("FLAGS_capacity_knee_rho", 0.85,
            "utilization the capacity report flags as the saturation "
            "knee: offered loads driving predicted slot utilization "
            "above this are marked over-knee (queueing delay diverges)")
define_flag("FLAGS_capacity_calib_beats", 32,
            "decode beats the CPU calibration measures per active-level "
            "when fitting the device profile's beat_ms base/slope "
            "(static/capacity.calibrate)")
define_flag("FLAGS_use_flash_attention", True,
            "route attention through the Pallas flash kernel on TPU "
            "(paddle_tpu.ops.pallas.flash_attention)")
define_flag("FLAGS_flash_min_seq", 1024,
            "dispatch threshold: the Pallas flash-attention kernel engages "
            "when s_k >= this (long-context regime where O(s^2) score "
            "materialization dominates); below it XLA's fused attention is "
            "faster on the MXU at these shapes. 0 forces the kernel on "
            "whenever shapes allow.")
define_flag("FLAGS_flash_block_q", 0,
            "flash attention q block size (0 = auto: 256 for s>=1024 else "
            "128)")
define_flag("FLAGS_flash_block_k", 0,
            "flash attention k block size (0 = auto)")
define_flag("FLAGS_fused_ce_block_n", 0,
            "fused CE token-block size (0 = auto 512)")
define_flag("FLAGS_fused_ce_block_v", 0,
            "fused CE vocab-block size (0 = auto 512)")
define_flag("FLAGS_flash_attention_interpret", False,
            "also use the flash kernel off-TPU via the Pallas interpreter "
            "(slow; for tests)")
define_flag("FLAGS_use_fused_ce", True,
            "route linear+cross-entropy loss heads through the Pallas "
            "fused kernel on TPU (paddle_tpu.ops.pallas.fused_ce)")
define_flag("FLAGS_pallas_interpret", False,
            "run all Pallas kernels off-TPU via the interpreter (slow; "
            "for tests)")
define_flag("FLAGS_use_decode_attention", True,
            "route StaticKVCache incremental-decode attention through the "
            "Pallas single-query flash kernel "
            "(paddle_tpu.ops.pallas.decode_attention): cache-length "
            "masking in-kernel, fully-masked KV blocks skipped via the "
            "grid instead of streaming the whole max_seq_len cache")
define_flag("FLAGS_decode_block_k", 0,
            "decode-attention KV block size (0 = auto: autotune table or "
            "the 128-column heuristic). Smaller blocks skip more of a "
            "mostly-empty cache; larger blocks amortize grid overhead")
define_flag("FLAGS_pallas_autotune", True,
            "block-size autotuning for Pallas kernels: measure candidate "
            "block configs at each new (kernel, shape-bucket, dtype, "
            "backend) key and cache the winner (in-process; on disk too "
            "when PADDLE_TPU_PALLAS_AUTOTUNE_CACHE names a json file). "
            "Off-TPU the heuristic defaults are used instead — interpret "
            "timings are meaningless. FLAGS_flash_block_* / "
            "FLAGS_fused_ce_block_* / FLAGS_decode_block_k overrides "
            "always win over the table")
define_flag("FLAGS_pallas_autotune_force", False,
            "measure autotune candidates even off-TPU (tests exercise the "
            "measuring path in interpreter mode; never useful in prod)")
define_flag("FLAGS_pallas_force_compile", False,
            "force compiled (Mosaic) lowering of Pallas kernels even "
            "off-TPU: tools/hlo_evidence.py uses this to AOT-lower bench "
            "graphs for a TPU target on a dev box. Such programs lower "
            "and cost-analyze fine but only *run* on real TPU hardware")

# --- continuous-batching decode serving (inference/serving.py,
# --- nn/kv_pool.py, ops/pallas/decode_attention.py paged kernel) --------
define_flag("FLAGS_use_paged_attention", True,
            "route paged (block-table) decode attention through the "
            "Pallas kernel (ops/pallas/decode_attention."
            "paged_decode_attention): per-request block tables ride the "
            "scalar-prefetch path next to the ragged lengths, so a "
            "decode step's KV reads scale with each request's LIVE "
            "blocks, not max_seq_len; the decode step's token write "
            "rides the same flag in both its forms: the token writer "
            "(paged_write_token, behind write_kv) and the write inside "
            "the multi-head kernel (nn/kv_pool.paged_write_attend, one "
            "call that writes and attends where its gate admits the "
            "shape). Off, the serve loop runs the jnp gather fallback "
            "(nn/kv_pool.paged_attention_ref) and write_kv's XLA loop")
define_flag("FLAGS_serve_block_size", 0,
            "tokens per physical KV-pool block (nn/kv_pool.KVBlockPool); "
            "0 = auto: the paged-decode autotune table on TPU, else the "
            "128-column heuristic. Must be a multiple of the 8-row "
            "sublane tile; multiples of 128 (a lane tile) keep the arenas "
            "free of layout copies. Smaller blocks waste less pool memory "
            "per short request; larger blocks amortize kernel grid "
            "overhead")
define_flag("FLAGS_serve_kv_blocks", 512,
            "physical blocks in the serving KV pool (per layer, k+v "
            "arenas); the pool is the admission currency — waiting "
            "requests stay queued until retiring streams free enough "
            "blocks (inference/serving.py backpressure)")
define_flag("FLAGS_serve_max_active", 64,
            "decode slots in the serving batch: the fused per-step "
            "decode processes this many concurrent streams (idle slots "
            "are masked to the trash block, costing no KV reads)")

define_flag("FLAGS_executor_max_inflight", 2,
            "async executor pipeline depth: how many dispatched-but-not-"
            "materialized steps the training hot loop keeps queued "
            "(static/pipeline_runner.py). jax dispatch is non-blocking, so "
            "N in-flight steps keep the device busy while the host "
            "converts/prefetches the next batches; fetches materialize "
            "only at print_period/callback/epoch boundaries. 0 restores "
            "the fully synchronous per-step loop")
define_flag("FLAGS_executor_scan_steps", 0,
            "scan-fused megasteps: when > 1 and the feed shapes are "
            "stable, the pipelined loop stacks K batches and runs ONE "
            "compiled lax.scan over the existing step — 1 dispatch per K "
            "steps instead of K, bitwise-equal to the serial loop (RNG "
            "keys, lr/t schedule threaded per iteration). Opt-in: "
            "dispatch-bound small programs win; large programs are "
            "already compute-bound. 0/1 disables fusion")
define_flag("FLAGS_executor_cache_size", 32,
            "LRU bound on the Executor's compiled-program cache (entries "
            "keyed on program.uid + feed/fetch signature); evictions bump "
            "executor/cache_evictions in core/monitor")

# --- observability (core/trace.py, core/monitor.py, flight recorder) ----
define_flag("FLAGS_trace_ring_size", 4096,
            "bounded ring of recent finished spans kept by the always-on "
            "tracer (core/trace.py) — the flight recorder's feed: on "
            "PipelineStepError / PS transport death / fatal signal the "
            "last N spans are dumped to PADDLE_TPU_DUMP_DIR. 0 disables "
            "the bound (unbounded ring; tests only). Runtime set_flags "
            "changes apply at the next trace.start()/reset() — call "
            "trace.set_ring_size() to resize immediately")
define_flag("FLAGS_monitor_series_len", 256,
            "per-metric bounded time-series ring in core/monitor: every "
            "stat_add/stat_set/observe appends (unix_ts, value) so dumps "
            "and dashboards see a trajectory, not just the final value")

# --- PS transport fault tolerance (distributed/ps/rpc.py) ---------------
# The reference's brpc channel exposes the same three knobs
# (connect_timeout_ms / timeout_ms / max_retry in brpc_ps_client.cc);
# flag names double as their env-var spelling, so a job script can export
# PADDLE_PS_CALL_TIMEOUT=5 without touching code.
define_flag("PADDLE_PS_CALL_TIMEOUT", 60.0,
            "per-RPC deadline in seconds; a call that stalls past it "
            "times out, retries, and finally raises DeadlineExceeded")
define_flag("PADDLE_PS_MAX_RETRIES", 5,
            "transport retry budget per call (attempts = retries + 1); "
            "mutating calls are made retry-safe by the server-side "
            "idempotent replay cache")
define_flag("PADDLE_PS_BACKOFF_BASE_S", 0.05,
            "first retry backoff in seconds; doubles per retry with "
            "jitter up to PADDLE_PS_BACKOFF_MAX_S")
define_flag("PADDLE_PS_BACKOFF_MAX_S", 2.0,
            "exponential backoff ceiling in seconds")
define_flag("PADDLE_PS_CONNECT_RETRY_S", 30.0,
            "initial-dial retry window: workers racing the server's bind "
            "at job start keep redialing this long before giving up")
define_flag("PADDLE_PS_MAX_FRAME", 1 << 30,
            "largest RPC frame either side will accept; a length prefix "
            "over this is rejected as a FrameError instead of an "
            "unbounded allocation from one garbled header")
define_flag("PADDLE_PS_REPLAY_CACHE", 512,
            "per-client entries in the server's idempotent-replay LRU; "
            "a retried mutating request inside this window replays the "
            "cached reply instead of re-applying the gradient")
define_flag("PADDLE_PS_SEND_RETRIES", 2,
            "extra Communicator send-thread attempts (with backoff) on "
            "top of the per-call transport retries before the thread "
            "declares itself dead")

# --- PS replicated storage tier (distributed/ps/{shard_map,replica}.py) --
define_flag("PADDLE_PS_REPLICA_BACKUPS", 0,
            "backups per shard when the fleet wiring builds the initial "
            "shard map (0 = replication off: the default map reproduces "
            "the legacy id%n_servers placement exactly). With k>0 every "
            "mutation is applied on the primary, forwarded to its "
            "backups under the SAME replay id, and acked only once "
            "durable on the write quorum")
define_flag("PADDLE_PS_REPLICA_QUORUM", 0,
            "replicas (primary included) that must ack a write before "
            "the client is acked; 0 = every LIVE replica (unreachable "
            "backups are evicted from the map rather than wedging "
            "writes)")
define_flag("PADDLE_PS_REPLICA_DELTA_LOG", 512,
            "per-table entries in the replay-keyed mutation log primaries "
            "keep for rejoin catch-up: a restarted server loads the "
            "snapshot, then replays the log suffix past its cursor; a "
            "cursor that fell off the bounded log restarts the fetch")
define_flag("PADDLE_PS_HEARTBEAT_S", 0.5,
            "replica heartbeat interval in seconds: every server beats "
            "replica_beat into its peers; beat replies gossip shard-map "
            "epochs so a behind server catches up")
define_flag("PADDLE_PS_HEARTBEAT_TIMEOUT_S", 3.0,
            "suspicion deadline: a primary whose beats stop for this "
            "long is declared dead and its first live backup promotes "
            "itself (shard-map epoch bump + broadcast)")
define_flag("PADDLE_PS_FAILOVER_RETRIES", 8,
            "extra client re-route attempts per logical call after a "
            "stale-map redirect or dead endpoint; paced by "
            "PADDLE_PS_FAILOVER_BACKOFF_S, the loop must outlast one "
            "heartbeat timeout + promotion")
define_flag("PADDLE_PS_FAILOVER_BACKOFF_S", 0.25,
            "base pause between client failover re-routes (grows "
            "linearly up to 4x)")

# --- sharded embedding engine (distributed/ps/{client,heter,embedding}.py) --
define_flag("PADDLE_PS_FANOUT_THREADS", 4,
            "per-shard fan-out concurrency of batched sparse lookups: a "
            "pull whose (deduped) ids span several shard primaries issues "
            "one RPC per shard from a pool of this many threads, so the "
            "batch costs max(shard latency), not the sum. 1 restores the "
            "serial per-shard loop (bitwise-identical results either way "
            "— shard slices are disjoint)")
define_flag("PADDLE_PS_PREFETCH_DEPTH", 2,
            "embedding-prefetch window depth (distributed/ps/embedding."
            "EmbeddingPrefetcher riding static/pipeline_runner."
            "InflightDriver): how many batches of sparse pulls may be in "
            "flight ahead of the training step. Results stay BITWISE "
            "equal to synchronous pulls: ids pushed after a batch's "
            "prefetch snapshot are re-pulled at materialization "
            "(conflict fix-up), so overlap never trades determinism")
define_flag("PADDLE_PS_HETER_CACHE_ROWS", 65536,
            "hot-id LRU bound on the HeterPS device-resident embedding "
            "cache (distributed/ps/heter.HeterPSCache): rows past the "
            "bound evict oldest-first into the host-RAM tier (see "
            "PADDLE_PS_HETER_HOST_ROWS), bumping ps.heter.evictions — "
            "device HBM holds the hot working set, not the vocab")
define_flag("PADDLE_PS_HETER_HOST_ROWS", 262144,
            "host-RAM second tier of the HeterPS cache: rows evicted "
            "from the device LRU park here (HeterPS lineage — tables "
            "larger than device memory tier through host DRAM before "
            "the PS); a host hit re-promotes without a PS RPC "
            "(ps.heter.host_hits). 0 disables the tier (evictions go "
            "straight back to the PS)")

# --- trainer-side fault tolerance (incubate/checkpoint.py,
# --- distributed/elastic.py Supervisor, distributed/launch.py --elastic) --
define_flag("PADDLE_CKPT_VERIFY", True,
            "verify checkpoint manifests (per-leaf sha256 + shape/dtype "
            "schema) on restore; a corrupt/partial/schema-mismatched "
            "step is quarantined and restore walks back to the newest "
            "VERIFIED checkpoint instead of loading garbage. Off, the "
            "manifest is still written but restore trusts the data")
define_flag("PADDLE_ELASTIC_MAX_RESTARTS", 3,
            "per-trainer restart budget of the elastic supervisor "
            "(distributed/elastic.py Supervisor / launch.py --elastic); "
            "a rank that dies or stalls more than this many times fails "
            "the whole job with the child's exit status")
define_flag("PADDLE_ELASTIC_RESTART_BACKOFF_S", 1.0,
            "base pause before an elastic trainer restart; grows "
            "linearly with that rank's restart count so a crash loop "
            "cannot hot-spin the supervisor")
define_flag("PADDLE_ELASTIC_STALL_TIMEOUT_S", 300.0,
            "supervisor-side stall deadline: a trainer whose heartbeat "
            "file keeps beating but whose step counter has not advanced "
            "for this long is flight-recorded, killed, and restarted "
            "(a hung collective or starved input pipeline looks exactly "
            "like this)")
define_flag("PADDLE_ELASTIC_HEARTBEAT_TIMEOUT_S", 60.0,
            "supervisor-side liveness deadline: a trainer whose "
            "heartbeat file is older than this (or unreadable) is "
            "declared dead and restarted")

# --- online learning (dataset/streaming.py, static/executor.py online
# --- mode, distributed/ps/publish.py, inference/serving.py hot-swap) ---
define_flag("PADDLE_STREAM_QUEUE_CAP", 1024,
            "bounded-queue capacity of dataset/streaming.StreamingDataset: "
            "producers (ServeLoop completion hooks) block in offer() once "
            "this many undelivered records are buffered — backpressure "
            "toward the serving tier instead of unbounded memory growth")
define_flag("PADDLE_STREAM_DEDUPE_WINDOW", 4096,
            "record-id dedupe window of StreamingDataset: the ids of the "
            "last N accepted records are remembered and re-offers of any "
            "of them are rejected (at-least-once transport in, exactly-"
            "once training batches out). The window rides checkpoints "
            "(state_dict), so a restarted trainer keeps rejecting "
            "records it already trained on")
define_flag("PADDLE_ONLINE_SYNC_EVERY", 1,
            "flush cadence of the online (continuous Downpour) trainer "
            "mode in static/executor.py: accumulated sparse deltas are "
            "pushed to the PS via push_sparse_delta every this many "
            "batches — one replay-id-protected RPC per touched shard "
            "per flush")
define_flag("PADDLE_ONLINE_STALENESS_BATCHES", 4,
            "bounded-staleness knob of the online trainer: the hard "
            "bound on batches trained past the last SUCCESSFUL delta "
            "flush. A transiently failing flush (PS chaos, failover in "
            "progress) is retried next cadence until this bound, then "
            "the flush error propagates (fail-stop) rather than letting "
            "the served model fall arbitrarily behind")

# --- cluster telemetry plane (core/telemetry.py, core/slo.py,
# --- tools/cluster_obs_drill.py) ---
define_flag("PADDLE_TELEMETRY_HUB", "",
            "host:port of a TelemetryHub. When set, processes that opt "
            "in (drills, metric snapshot emitters, anything that "
            "starts a TelemetryShipper) ship metric deltas / span "
            "batches there; empty (the default) means fully local "
            "observability, no network")
define_flag("PADDLE_TELEMETRY_FLUSH_S", 0.5,
            "TelemetryShipper flush cadence: every this many seconds "
            "the background thread snapshots the monitor registry and "
            "ships one replay-keyed delta batch to the hub. The hot "
            "path only ever appends to an in-memory buffer — a slow or "
            "dead hub can delay shipping, never a decode beat")
define_flag("PADDLE_TELEMETRY_SPAN_BUFFER", 2048,
            "bound on the shipper's finished-span buffer. When the hub "
            "falls behind and the buffer is full, new spans are dropped "
            "on the floor and counted in telemetry.dropped_spans / "
            "telemetry.dropped_batches (backpressure by shedding, "
            "never by blocking the thread that finished the span)")
define_flag("PADDLE_TELEMETRY_INCIDENT_WINDOW_S", 10.0,
            "incident coalescing window of the TelemetryHub: flight-"
            "recorder triggers and SLO breaches arriving within this "
            "many seconds of an open incident JOIN it (one incident id, "
            "one merged dump) instead of opening a new one")
define_flag("PADDLE_SLO_EVAL_S", 1.0,
            "cadence of the hub's SLO engine: every this many seconds "
            "the merged counters/histograms are appended to the burn-"
            "rate series and every SLOSpec is re-evaluated")
define_flag("PADDLE_SLO_FAST_WINDOW_S", 60.0,
            "fast burn-rate window: a breach requires the bad fraction "
            "over BOTH this window and the slow window to exceed the "
            "objective — the fast window bounds time-to-detect, the "
            "slow window filters blips")
define_flag("PADDLE_SLO_SLOW_WINDOW_S", 300.0,
            "slow burn-rate window (see PADDLE_SLO_FAST_WINDOW_S); "
            "also bounds how much burn-rate history the engine retains "
            "per SLO spec (2x this window)")
