"""Benchmarks on one TPU chip. Prints one JSON line per metric:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

A run that finds no TPU, meets a device kind with no entry in PEAKS, or
has any requested mode fail exits non-zero: nothing here rebuilds without
a kernel, continues on the CPU, or prints a record for a failed mode.
(`python chip_smoke.py` is the quicker proof that the program starts on
the chip at all.)

Modes (BENCH_MODE env): "all" (default) = bert + resnet + decode +
longseq + pipeline + serve + sparse + online + traffic; or a single one
of "bert" / "resnet" / "decode" / "longseq" / "pipeline" / "serve" /
"sparse" / "online" / "traffic".
- bert   — flagship: BERT-base MLM training (BASELINE config 3). The
  FIRST stdout line; vs_baseline = measured MFU / 0.40 (the BASELINE.md
  north-star; the reference publishes no numbers of its own).
- resnet — ResNet-50 conv training step (BASELINE configs 2/4). MFU uses
  XLA's own cost analysis for the step FLOPs (conv accounting is easy to
  get wrong by hand — documented convention per VERDICT r03 weak #8).
- decode — GPT incremental generation tokens/sec through the
  StaticKVCache scan path (VERDICT r03 item 2).
- pipeline — static-executor TRAIN hot-loop steps/s: serial vs async
  pipelined (in-flight steps, device-resident carry) vs scan-fused
  megasteps (docs/async_executor.md): it measures per-step HOST overhead, the
  thing the pipeline removes.
- sparse — the recsys sharded-embedding workload: rows/s pulled+pushed
  through EmbeddingPrefetcher -> HeterPSCache -> PSClient cross-shard
  fan-out against an in-process 3-shard-server cluster, with prefetch
  overlap ratio and cache hit rate: the PS engine is host machinery
  (docs/fault_tolerance.md, sharded embedding section).
- online — the serve->train->publish closed loop: completion records/s
  through StreamingDataset dedupe -> the continuous Downpour trainer's
  replay-keyed delta flushes -> EmbeddingSnapshotPublisher versioned
  cuts (docs/online_learning.md): host machinery plus a tiny jitted
  step.
- traffic — the traffic-lab closed loop: a seeded deterministic workload
  schedule (paddle_tpu/traffic/workload.py) paced at the tiny-GPT
  ServeLoop through the shared harness, reporting completed req/s and
  hub-comparable TTFT/token p50/p99 (docs/traffic_lab.md): scheduler +
  paged pool + paced arrivals are host machinery.

Peak rates come from the PEAKS table below, keyed by device_kind.

Honesty protocol: batches cycle through synthetic datasets (no
single-batch memorization), each step gets a fresh dropout key, and train
lines report loss_start/loss_end over the timed window so throughput wins
can't silently regress convergence.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BATCH = int(os.environ.get("BENCH_BATCH", 32))
SEQ = int(os.environ.get("BENCH_SEQ", 128))
STEPS = int(os.environ.get("BENCH_STEPS", 50))
WARMUP = int(os.environ.get("BENCH_WARMUP", 5))
DTYPE = os.environ.get("BENCH_DTYPE", "bfloat16")
# Peak dense bf16 FLOP/s of one chip, keyed by the device_kind string jax
# reports (chip_smoke.py prints it). Source: Google Cloud documentation,
# "TPU v5e" system architecture: 197 TFLOP/s bf16 per chip. A device that
# is not in the table is an error, not a default.
PEAKS = {"TPU v5 lite": 197e12}
N_BATCHES = int(os.environ.get("BENCH_N_BATCHES", 16))
PROFILE = os.environ.get("BENCH_PROFILE", "") not in ("", "0")


def _peak_flops():
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in PEAKS:
        raise SystemExit(
            f"bench: no peak rate on record for device_kind {kind!r} "
            f"(known: {sorted(PEAKS)}); add it to PEAKS with its source")
    return PEAKS[kind]


def _pallas_reset():
    """Zero the pallas.* monitor counters (per bench mode, so each metric
    line reports only its own graph's kernel engagement)."""
    from paddle_tpu.core import monitor
    monitor.reset(prefix="pallas.")


def _pallas_report():
    """Per-kernel {hits, gate_rejects} from the monitor counters
    (ops/pallas run_guarded + gate_reject), with the per-reason
    breakdown so a bench line says *why* a kernel didn't engage."""
    from paddle_tpu.core import monitor
    report = {}
    for name, value in monitor.stats("pallas.").items():
        parts = name.split(".")
        if len(parts) < 3 or parts[1] not in ("hit", "gate_reject"):
            continue
        kind, kernel = parts[1], parts[2]
        reason = ".".join(parts[3:])
        entry = report.setdefault(kernel, {
            "hits": 0, "gate_rejects": 0, "gate_reject_reasons": {}})
        if kind == "hit":
            entry["hits"] += int(value)
        else:
            entry["gate_rejects"] += int(value)
            entry["gate_reject_reasons"][reason] = \
                entry["gate_reject_reasons"].get(reason, 0) + int(value)
    return report


def _build(cfg, use_fused_head):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import optimizer as opt_mod
    from paddle_tpu.core import rng as _rng
    from paddle_tpu.core import tape as _tape
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.text.models.bert import Bert, BertPretrainingCriterion

    paddle.seed(0)
    net = Bert(cfg)
    net.train()
    criterion = BertPretrainingCriterion(cfg.vocab_size)
    # honest O2 AMP recipe: bf16 params/compute with f32 master weights +
    # f32 moments in the optimizer (paddle_tpu.amp.decorate semantics)
    optimizer = opt_mod.AdamW(learning_rate=1e-4,
                              parameters=net.parameters(),
                              multi_precision=(DTYPE == "bfloat16"))

    params, buffers = net.functional_state()
    if DTYPE == "bfloat16":
        params = {k: v.astype(jnp.bfloat16) if v.dtype == jnp.float32 else v
                  for k, v in params.items()}
    named = dict(net.named_parameters())
    optimizer._ensure_slots(params)
    slots = dict(optimizer._slots)
    meta = optimizer._param_meta(named)
    n_params = int(sum(np.prod(v.shape) for v in params.values()))

    def train_step(params, slots, ids, labels, lr, t, key):
        with _rng.rng_state(key), _tape.no_grad():
            def loss_of(p):
                net.load_functional_state(p, buffers)
                if use_fused_head:
                    loss = net(Tensor(ids, _internal=True),
                               masked_lm_labels=Tensor(labels,
                                                       _internal=True))
                else:
                    logits = net(Tensor(ids, _internal=True))
                    loss = criterion(logits, Tensor(labels, _internal=True))
                return loss._value.astype(jnp.float32)

            loss, grads = jax.value_and_grad(loss_of)(params)
            new_params, new_slots = optimizer.apply_gradients_pure(
                params, grads, slots, lr, t, param_meta=meta)
        return loss, new_params, new_slots

    step = jax.jit(train_step, donate_argnums=(0, 1))
    return step, params, slots, n_params


def bench_resnet():
    """ResNet-50 training step (BASELINE configs 2/4). Conv-MFU convention:
    FLOPs come from XLA cost analysis of the compiled train step (fwd+bwd+
    sgd), not a hand 6ND count."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import optimizer as opt_mod
    from paddle_tpu.core import rng as _rng
    from paddle_tpu.core import tape as _tape
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.vision.models import resnet50
    from paddle_tpu import nn

    batch = int(os.environ.get("BENCH_RESNET_BATCH", 64))
    steps = int(os.environ.get("BENCH_RESNET_STEPS", 30))
    warmup = int(os.environ.get("BENCH_RESNET_WARMUP", 3))
    img = int(os.environ.get("BENCH_RESNET_IMAGE", 224))
    n_batches = 8
    _pallas_reset()

    paddle.seed(0)
    net = resnet50()
    net.train()
    criterion = nn.CrossEntropyLoss()
    optimizer = opt_mod.Momentum(learning_rate=0.02, momentum=0.9,
                                 parameters=net.parameters(),
                                 weight_decay=1e-4,
                                 multi_precision=(DTYPE == "bfloat16"))
    params, buffers = net.functional_state()
    if DTYPE == "bfloat16":
        params = {k: v.astype(jnp.bfloat16) if v.dtype == jnp.float32 else v
                  for k, v in params.items()}
    named = dict(net.named_parameters())
    optimizer._ensure_slots(params)
    slots = dict(optimizer._slots)
    meta = optimizer._param_meta(named)
    n_params = int(sum(np.prod(v.shape) for v in params.values()))

    def train_step(params, buffers, slots, images, labels, lr, t, key):
        with _rng.rng_state(key), _tape.no_grad():
            def loss_of(p):
                net.load_functional_state(p, buffers)
                logits = net(Tensor(images, _internal=True))
                loss = criterion(logits, Tensor(labels, _internal=True))
                new_bufs = {n: b._value for n, b in net.named_buffers()}
                return loss._value.astype(jnp.float32), new_bufs

            (loss, new_bufs), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params)
            new_params, new_slots = optimizer.apply_gradients_pure(
                params, grads, slots, lr, t, param_meta=meta)
        return loss, new_bufs, new_params, new_slots

    step = jax.jit(train_step, donate_argnums=(0, 1, 2))

    rng = np.random.RandomState(0)
    imgs = jnp.asarray(rng.randn(n_batches, batch, 3, img, img),
                       jnp.bfloat16 if DTYPE == "bfloat16" else jnp.float32)
    labs = jnp.asarray(rng.randint(0, 1000, (n_batches, batch)), jnp.int32)
    lr = jnp.asarray(0.02, jnp.float32)
    t_arr = jnp.asarray(1, jnp.int32)
    key = jax.random.PRNGKey(3)

    # XLA's own flop count for the whole compiled step
    lowered = jax.jit(train_step).lower(
        params, buffers, slots, imgs[0], labs[0], lr, t_arr, key)
    flops_per_step = float(lowered.compile().cost_analysis()["flops"])

    for i in range(warmup):
        loss, buffers, params, slots = step(params, buffers, slots,
                                            imgs[0], labs[0], lr, t_arr,
                                            jax.random.fold_in(key, 999 + i))
    jax.block_until_ready(loss)
    losses = []
    t0 = time.perf_counter()
    for i in range(steps):
        loss, buffers, params, slots = step(params, buffers, slots,
                                            imgs[i % n_batches],
                                            labs[i % n_batches], lr, t_arr,
                                            jax.random.fold_in(key, i))
        if i in (0, steps - 1):
            losses.append(loss)
    loss_start = float(np.asarray(losses[0]))
    loss_end = float(np.asarray(losses[-1]))
    dt = time.perf_counter() - t0

    steps_per_sec = steps / dt
    mfu = flops_per_step * steps_per_sec / _peak_flops()
    print(json.dumps({
        "metric": f"resnet50_train_b{batch}_i{img}_{DTYPE}",
        "value": round(steps_per_sec * batch, 2),
        "unit": "samples/sec/chip",
        "vs_baseline": round(mfu / 0.40, 4),
        "mfu": round(mfu, 4),
        "flops_per_step": flops_per_step,
        "loss_start": round(loss_start, 4),
        "loss_end": round(loss_end, 4),
        "step_ms": round(1000 * dt / steps, 2),
        "params": n_params,
        "steps": steps,
        "pallas": _pallas_report(),
    }), flush=True)


def bench_decode():
    """GPT incremental decoding tokens/sec (StaticKVCache + scan; VERDICT
    r03 item 2 'Done' criterion)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.text.models.gpt import GPT, GPTConfig

    b = int(os.environ.get("BENCH_DECODE_BATCH", 8))
    prompt = int(os.environ.get("BENCH_DECODE_PROMPT", 32))
    new = int(os.environ.get("BENCH_DECODE_NEW", 128))

    _pallas_reset()
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                    num_heads=12, intermediate_size=3072, max_seq_len=1024)
    net = GPT(cfg)
    net.eval()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size,
                                       (b, prompt)).astype("int64"))
    net.generate(ids, max_new_tokens=new, temperature=0,
                 use_cache=True)  # compile
    t0 = time.perf_counter()
    reps = 3
    for i in range(reps):
        out = net.generate(ids, max_new_tokens=new, temperature=0,
                           use_cache=True, seed=i)
    dt = (time.perf_counter() - t0) / reps
    toks = b * new
    print(json.dumps({
        "metric": f"gpt124m_decode_b{b}_p{prompt}_n{new}",
        "value": round(toks / dt, 2),
        "unit": "tokens/sec/chip",
        "vs_baseline": 1.0,   # no reference decode figure; KV-cache path
        "ms_per_token": round(1000 * dt / new, 3),
        "batch": b,
        "pallas": _pallas_report(),
    }), flush=True)


def bench_serve():
    """Continuous-batching decode serving (inference/serving.py): N
    concurrent generate streams through the paged KV pool + block-table
    Pallas decode kernel. Reports tokens/s plus the latency distribution
    an online tier is actually judged on — p50/p99 time-to-first-token
    and p50/p99 per-token latency — and a pool-utilization/queue-depth
    snapshot from the serve gauges."""
    import threading

    import paddle_tpu as paddle
    from paddle_tpu.core import monitor
    from paddle_tpu.inference import ServeConfig, ServeLoop
    from paddle_tpu.text.models.gpt import GPT, GPTConfig

    n_req = int(os.environ.get("BENCH_SERVE_REQUESTS", 256))
    prompt = int(os.environ.get("BENCH_SERVE_PROMPT", 32))
    new = int(os.environ.get("BENCH_SERVE_NEW", 64))
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 64))
    blocks = int(os.environ.get("BENCH_SERVE_BLOCKS", 512))
    clients = int(os.environ.get("BENCH_SERVE_CLIENTS", 32))
    model = os.environ.get("BENCH_SERVE_MODEL", "gpt124m")

    _pallas_reset()
    monitor.reset(prefix="serve.")
    monitor.reset(prefix="serve/")   # ttft/token histograms
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                    num_heads=12, intermediate_size=3072,
                    max_seq_len=1024) if model == "gpt124m" \
        else GPTConfig.tiny()
    net = GPT(cfg)
    net.eval()
    loop = ServeLoop(net, ServeConfig(max_active=slots, kv_blocks=blocks,
                                      max_seq_len=min(cfg.max_seq_len,
                                                      prompt + new)))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, (prompt,)).astype(np.int64)
               for _ in range(n_req)]
    # warmup: compile prefill bucket + decode step outside the window;
    # drop its counters AND its serve/* latency histograms (the warmup
    # TTFT includes compile time — a huge outlier)
    loop.serve([prompts[0]], max_new_tokens=2)
    monitor.reset(prefix="serve.")
    monitor.reset(prefix="serve/")

    loop.start()
    reqs = [None] * n_req
    errors = []
    queue_peak = [0]

    def client(base):
        for i in range(base, n_req, clients):
            try:
                reqs[i] = loop.submit(prompts[i], max_new_tokens=new)
                queue_peak[0] = max(queue_peak[0],
                                    loop.stats()["queue_depth"])
            except Exception as e:  # noqa: BLE001 — report, don't wedge
                errors.append(f"{type(e).__name__}: {e}")

    t0 = time.perf_counter()
    ths = [threading.Thread(target=client, args=(c,))
           for c in range(clients)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    toks = 0
    ttfts, per_tok = [], []
    for r in reqs:
        if r is None:
            continue
        try:
            out = r.result(timeout=3600)
            toks += len(out)
            if r.ttft_s is not None:
                ttfts.append(r.ttft_s * 1e3)
            if r.per_token_s is not None:
                per_tok.append(r.per_token_s * 1e3)
        except Exception as e:  # noqa: BLE001
            errors.append(f"{type(e).__name__}: {e}")
    dt = time.perf_counter() - t0
    loop.stop()
    if errors:
        raise RuntimeError(f"serve bench: {len(errors)} request errors, "
                           f"first: {errors[:5]}")

    def pct(xs, p):
        return round(float(np.percentile(xs, p)), 3) if xs else None

    serve_stats = {k: v for k, v in monitor.stats("serve.").items()}
    print(json.dumps({
        "metric": f"serve_decode_{model}_r{n_req}_p{prompt}_n{new}",
        "value": round(toks / dt, 2),
        "unit": "tokens/sec/chip",
        "vs_baseline": 1.0,   # first serving round: becomes the baseline
        "requests": n_req,
        "ttft_ms": {"p50": pct(ttfts, 50), "p99": pct(ttfts, 99)},
        "token_ms": {"p50": pct(per_tok, 50), "p99": pct(per_tok, 99)},
        "serve": {
            "slots": slots,
            "kv_blocks": blocks,
            "block_size": loop.stats()["block_size"],
            "queue_depth_peak": queue_peak[0],
            "pool_used_blocks_final":
                int(serve_stats.get("serve.kv_pool_used_blocks", 0)),
            "preempted": int(serve_stats.get("serve.preempted", 0)),
            "completed":
                int(serve_stats.get("serve.requests_completed", 0)),
        },
        "pallas": _pallas_report(),
    }), flush=True)


def bench_bert():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.text.datasets import LMDataset
    from paddle_tpu.text.models.bert import BertConfig

    cfg = BertConfig.bert_base()

    # real (synthetic-Zipfian) data, cycled — not one memorized batch
    ds = LMDataset(vocab_size=cfg.vocab_size, seq_len=SEQ,
                   n=N_BATCHES * BATCH, mode="mlm", seed=0)
    # int32 ids/labels: TPUs index natively in int32; int64 costs a widen
    ids_all = jnp.asarray(ds.inputs.reshape(N_BATCHES, BATCH, SEQ), jnp.int32)
    lab_all = jnp.asarray(ds.labels.reshape(N_BATCHES, BATCH, SEQ), jnp.int32)
    lr = jnp.asarray(1e-4, jnp.float32)
    t_arr = jnp.asarray(1, jnp.int32)

    assert STEPS >= 1, "BENCH_STEPS must be >= 1"

    def run(step, params, slots):
        base_key = jax.random.PRNGKey(7)
        for i in range(WARMUP):
            loss, params, slots = step(params, slots, ids_all[0], lab_all[0],
                                       lr, t_arr, jax.random.fold_in(
                                           base_key, 10_000 + i))
        if WARMUP:
            jax.block_until_ready(loss)

        losses = []
        t0 = time.perf_counter()
        for i in range(STEPS):
            loss, params, slots = step(
                params, slots, ids_all[i % N_BATCHES],
                lab_all[i % N_BATCHES], lr, t_arr,
                jax.random.fold_in(base_key, i))
            if i in (0, STEPS - 1):
                losses.append(loss)
        loss_start = float(np.asarray(losses[0]))
        loss_end = float(np.asarray(losses[-1]))
        dt = time.perf_counter() - t0
        return dt, loss_start, loss_end

    if PROFILE:
        from paddle_tpu import profiler as prof
        prof.reset_profiler()
        prof.start_profiler()

    _pallas_reset()
    step, params, slots, n_params = _build(cfg, use_fused_head=True)
    if PROFILE:
        ca = prof.cost_analysis(
            step, params, slots, ids_all[0], lab_all[0], lr, t_arr,
            jax.random.PRNGKey(0))
        print(f"# xla cost analysis: flops={ca.get('flops')} "
              f"bytes={ca.get('bytes accessed')}", file=sys.stderr)
    dt, loss_start, loss_end = run(step, params, slots)

    if PROFILE:
        prof.stop_profiler()
        print(prof.summary(sorted_key="total"), file=sys.stderr)

    steps_per_sec = STEPS / dt
    samples_per_sec = steps_per_sec * BATCH
    tokens = BATCH * SEQ
    # 6ND for matmul params + attention quadratic term (fwd 1x, bwd 2x)
    L, H = cfg.num_hidden_layers, cfg.hidden_size
    attn_flops = 12 * L * H * SEQ * tokens
    flops_per_step = 6 * n_params * tokens + attn_flops
    mfu = flops_per_step * steps_per_sec / _peak_flops()

    # which Pallas kernels actually engaged, from the monitor counters
    # (ops/pallas run_guarded hits / gate rejects), not a re-derivation
    # of the gate logic
    pallas = _pallas_report()
    result = {
        "metric": f"bert_base_mlm_train_b{BATCH}_s{SEQ}_{DTYPE}",
        "value": round(samples_per_sec, 2),
        "unit": "samples/sec/chip",
        "vs_baseline": round(mfu / 0.40, 4),
        "mfu": round(mfu, 4),
        "loss_start": round(loss_start, 4),
        "loss_end": round(loss_end, 4),
        "step_ms": round(1000 * dt / STEPS, 2),
        "params": n_params,
        "steps": STEPS,
        "pallas": pallas,
        "pallas_kernels_in_graph": sorted(
            k for k, v in pallas.items() if v["hits"] > 0),
    }
    print(json.dumps(result))


def bench_longseq():
    """Long-context GPT training step at s=4096 — the regime the Pallas
    flash-attention kernel exists for (O(s) attention memory, in-kernel
    causal block skipping). Reports samples/sec with the kernel ON and
    the measured delta vs the jnp/XLA attention path on the same chip,
    quantifying the kernels' value (VERDICT r03 item 1 'Done' clause)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import optimizer as opt_mod
    from paddle_tpu.core import rng as _rng
    from paddle_tpu.core import tape as _tape
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.text.models.gpt import GPT, GPTConfig

    seq = int(os.environ.get("BENCH_LONGSEQ", 4096))
    batch = int(os.environ.get("BENCH_LONGSEQ_BATCH", 1))
    steps = int(os.environ.get("BENCH_LONGSEQ_STEPS", 15))
    warmup = 2
    _pallas_reset()
    cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                    num_heads=12, intermediate_size=3072,
                    max_seq_len=seq, dropout=0.0)

    def build_and_time(flash_on):
        paddle.set_flags({"FLAGS_use_flash_attention": bool(flash_on),
                          "FLAGS_flash_min_seq": 0})
        paddle.seed(0)
        net = GPT(cfg)
        net.train()
        optimizer = opt_mod.AdamW(learning_rate=1e-4,
                                  parameters=net.parameters(),
                                  multi_precision=True)
        params, buffers = net.functional_state()
        params = {k: v.astype(jnp.bfloat16) if v.dtype == jnp.float32
                  else v for k, v in params.items()}
        named = dict(net.named_parameters())
        optimizer._ensure_slots(params)
        slots = dict(optimizer._slots)
        meta = optimizer._param_meta(named)
        n_params = int(sum(np.prod(v.shape) for v in params.values()))

        def train_step(params, slots, ids, labels, lr, t, key):
            with _rng.rng_state(key), _tape.no_grad():
                def loss_of(p):
                    net.load_functional_state(p, buffers)
                    loss = net(Tensor(ids, _internal=True),
                               labels=Tensor(labels, _internal=True))
                    return loss._value.mean().astype(jnp.float32)

                loss, grads = jax.value_and_grad(loss_of)(params)
                new_params, new_slots = optimizer.apply_gradients_pure(
                    params, grads, slots, lr, t, param_meta=meta)
            return loss, new_params, new_slots

        step = jax.jit(train_step, donate_argnums=(0, 1))
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(4, cfg.vocab_size, (batch, seq)),
                          jnp.int32)
        labels = jnp.asarray(np.roll(np.asarray(ids), -1, axis=1),
                             jnp.int32)
        lr = jnp.asarray(1e-4, jnp.float32)
        t_arr = jnp.asarray(1, jnp.int32)
        key = jax.random.PRNGKey(0)
        for i in range(warmup):
            loss, params, slots = step(params, slots, ids, labels, lr,
                                       t_arr, jax.random.fold_in(key, i))
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for i in range(steps):
            loss, params, slots = step(params, slots, ids, labels, lr,
                                       t_arr, jax.random.fold_in(key, i))
        lv = float(jax.block_until_ready(loss))
        dt = (time.perf_counter() - t0) / steps
        return dt, lv, n_params

    dt_flash, loss_end, n_params = build_and_time(True)
    dt_jnp, _, _ = build_and_time(False)
    paddle.set_flags({"FLAGS_use_flash_attention": True,
                      "FLAGS_flash_min_seq": 1024})
    toks = batch * seq
    # 6ND + causal attention term (12*L*H*s*T/2 for causal)
    L, H = cfg.num_layers, cfg.hidden_size
    flops = 6 * n_params * toks + 6 * L * H * seq * toks
    mfu = flops / dt_flash / _peak_flops()
    print(json.dumps({
        "metric": f"gpt124m_longseq_train_b{batch}_s{seq}_bf16",
        "value": round(toks / dt_flash, 2),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(dt_jnp / dt_flash, 4),  # >1 = kernel wins
        "mfu": round(mfu, 4),
        "step_ms_flash": round(1000 * dt_flash, 2),
        "step_ms_jnp_attention": round(1000 * dt_jnp, 2),
        "loss_end": round(loss_end, 4),
        "steps": steps,
        "pallas": _pallas_report(),
    }), flush=True)


def bench_pipeline():
    """Static-executor TRAIN hot loop: serial Executor.run vs the async
    pipelined loop vs scan-fused megasteps, on a small dispatch-bound
    program — the regime where per-step host overhead (feed conversion,
    scope round-trip, fetch sync) dominates the compiled step itself.
    The win measured here is host overhead, not device compute.
    Defaults mirror
    tools/pipeline_lint.py PIPELINE_CFG (framework_lint cross-checks)."""
    import jax  # noqa: F401  (backend init before timing)
    import paddle_tpu as paddle
    from paddle_tpu import nn, ops, optimizer, static
    from paddle_tpu.core import monitor
    from paddle_tpu.static import PipelineRunner

    batch = int(os.environ.get("BENCH_PIPE_BATCH", 256))
    hidden = int(os.environ.get("BENCH_PIPE_HIDDEN", 64))
    steps = int(os.environ.get("BENCH_PIPE_STEPS", 200))
    scan_k = int(os.environ.get("BENCH_PIPE_SCAN_K", 8))
    inflight = int(os.environ.get("BENCH_PIPE_INFLIGHT", 2))
    warmup = 10
    rng = np.random.RandomState(0)
    n_batches = 16
    xs = [rng.rand(batch, hidden).astype("float32")
          for _ in range(n_batches)]
    ys = [rng.rand(batch, 1).astype("float32") for _ in range(n_batches)]

    def build(name):
        paddle.seed(0)
        prog = static.Program(name)
        with static.program_guard(prog):
            x = static.data("x", [-1, hidden], "float32")
            y = static.data("y", [-1, 1], "float32")
            h = ops.relu(nn.Linear(hidden, hidden)(x))
            loss = ops.mse_loss(nn.Linear(hidden, 1)(h), y)
            optimizer.Adam(learning_rate=1e-3).minimize(loss)
        return prog, loss

    def feeds(n):
        for i in range(n):
            yield {"x": xs[i % n_batches], "y": ys[i % n_batches]}

    paddle.enable_static()
    try:
        results = {}
        overhead = {}
        losses = {}
        # serial: materialize every step (the pre-pipeline loop)
        prog, loss = build("bench_serial")
        exe = static.Executor()
        paddle.seed(7)
        for f in feeds(warmup):
            exe.run(prog, feed=f, fetch_list=[loss])
        t0 = time.perf_counter()
        for f in feeds(steps):
            out = exe.run(prog, feed=f, fetch_list=[loss])
        results["serial"] = steps / (time.perf_counter() - t0)
        losses["serial"] = float(np.asarray(out[0]))

        def timed_runner(name, k):
            prog, loss = build(f"bench_{name}")
            exe = static.Executor()
            paddle.seed(7)
            with PipelineRunner(exe, prog, fetch_list=[loss],
                                max_inflight=inflight, scan_steps=k) as r:
                for _ in r.run(feeds(warmup)):
                    pass
                r.sync()
                t0 = time.perf_counter()
                last = None
                for handles in r.run(feeds(steps)):
                    last = handles
                val = float(np.asarray(last[0]))
                dt = time.perf_counter() - t0
            results[name] = steps / dt
            losses[name] = val
            overhead[name] = monitor.stat_get("executor/host_overhead_ms")

        timed_runner("pipelined", 0)
        timed_runner("scan_fused", scan_k)

        print(json.dumps({
            "metric": f"static_train_hotloop_b{batch}_h{hidden}",
            "value": round(results["pipelined"], 2),
            "unit": "steps/sec",
            "vs_baseline": round(results["pipelined"] / results["serial"],
                                 4),
            "pipeline": {
                "inflight": inflight,
                "scan_k": scan_k,
                "steps_per_s": {k: round(v, 2)
                                for k, v in results.items()},
                "host_overhead_ms": {k: round(v, 4)
                                     for k, v in overhead.items()},
                "dispatches_per_step": {"serial": 1.0, "pipelined": 1.0,
                                        "scan_fused": round(1.0 / scan_k,
                                                            4)},
            },
            "loss_end": {k: round(v, 6) for k, v in losses.items()},
            "steps": steps,
        }), flush=True)
    finally:
        paddle.disable_static()


def bench_sparse_embedding():
    """Recsys sparse-embedding engine throughput (BENCH_MODE=sparse):
    a zipf-ish batched pull/push loop through the full stack —
    EmbeddingPrefetcher (async overlap) -> HeterPSCache (tiered LRU) ->
    PSClient (batched deduped cross-shard fan-out) — against an
    in-process 3-shard-server cluster. Host machinery end to end.
    Reports rows/s pulled, the prefetch overlap ratio
    (fraction of PS latency hidden behind the 'dense step'), and the
    cache hit rate; knobs mirror tools/ps_load_test.py's sharded
    drill."""
    from paddle_tpu.core import monitor
    from paddle_tpu.distributed.ps import (EmbeddingPrefetcher,
                                           HeterPSCache, PSClient,
                                           PSServer, ShardMap)

    n_servers = int(os.environ.get("BENCH_SPARSE_SERVERS", 3))
    vocab = int(os.environ.get("BENCH_SPARSE_VOCAB", 100_000))
    dim = int(os.environ.get("BENCH_SPARSE_DIM", 32))
    batch = int(os.environ.get("BENCH_SPARSE_BATCH", 2048))
    rounds = int(os.environ.get("BENCH_SPARSE_ROUNDS", 40))
    cache_rows = int(os.environ.get("BENCH_SPARSE_CACHE_ROWS", 16384))
    compute_s = float(os.environ.get("BENCH_SPARSE_COMPUTE_S", 0.004))

    spec = {"emb": {"type": "sparse", "dim": dim, "optimizer": "adagrad",
                    "lr": 0.05, "init": "uniform", "seed": 1}}
    servers = [PSServer("127.0.0.1:0", dict(spec))
               for _ in range(n_servers)]
    eps = [s.start() for s in servers]
    smap = ShardMap.create(eps, n_backups=0)
    client = PSClient(eps, shard_map=smap)
    cache = HeterPSCache(client, "emb", dim, capacity=cache_rows)
    pf = EmbeddingPrefetcher(cache)
    monitor.reset(prefix="ps.heter.")
    # zipf-ish hot set: 80% of ids from 10% of the vocab, like recsys
    hot = vocab // 10

    def batch_ids(r):
        rs = np.random.RandomState(1000 + r)
        cold = rs.randint(0, vocab, batch // 5)
        return np.unique(np.concatenate(
            [rs.randint(0, hot, batch - batch // 5), cold])
            .astype(np.int64))

    pulled = pushed = 0
    try:
        pf.prefetch(batch_ids(0))
        t0 = time.perf_counter()
        for r in range(rounds):
            ids = batch_ids(r)
            rows = pf.get(ids)
            if r + 1 < rounds:
                pf.prefetch(batch_ids(r + 1))
            if compute_s:
                time.sleep(compute_s)           # stand-in dense step
            pulled += len(ids)
            pf.push_grad(ids, np.asarray(rows, np.float32) * 0 + 0.01)
            pushed += len(ids)
        wall = time.perf_counter() - t0
    finally:
        stats = pf.stats()
        try:
            pf.close()
        finally:
            client.close()
            for s in servers:
                s.shutdown()

    hits = monitor.stat_get("ps.heter.hits")
    host_hits = monitor.stat_get("ps.heter.host_hits")
    misses = monitor.stat_get("ps.heter.misses")
    hit_rate = (hits + host_hits) / max(1, hits + host_hits + misses)
    print(json.dumps({
        "metric": f"sparse_embedding_b{batch}_d{dim}_s{n_servers}",
        "value": round(pulled / wall, 1),
        "unit": "rows/sec pulled",
        "vs_baseline": 1.0,
        "sparse": {
            "shard_servers": n_servers,
            "rows_pulled": pulled,
            "rows_pushed": pushed,
            "push_rows_per_s": round(pushed / wall, 1),
            "prefetch_overlap_ratio": round(stats["overlap_ratio"], 4),
            "prefetched_batches": stats["prefetched"],
            "conflict_rows_repulled": stats["conflict_rows"],
            "cache_hit_rate": round(hit_rate, 4),
            "cache_rows": cache_rows,
            "rounds": rounds,
        },
    }), flush=True)


def bench_online():
    """Online-learning loop throughput (BENCH_MODE=online): synthetic
    completion records stream through dataset/streaming.StreamingDataset
    (dedupe + bounded queue) into the continuous Downpour trainer
    (static/executor.py ps_config mode="online", replay-keyed
    push_sparse_delta), with EmbeddingSnapshotPublisher cutting a
    versioned snapshot every BENCH_ONLINE_PUBLISH_EVERY batches. Host +
    tiny-program machinery end to end. Reports records/s
    trained end to end, delta rows/s flushed, and publish latency;
    knobs are pinned by tools/online_drill.py's self_check."""
    import threading

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer, static
    from paddle_tpu.core import monitor
    from paddle_tpu.dataset import StreamingDataset
    from paddle_tpu.distributed.ps import (EmbeddingSnapshotPublisher,
                                           PSClient, PSServer)

    records = int(os.environ.get("BENCH_ONLINE_RECORDS", 512))
    batch = int(os.environ.get("BENCH_ONLINE_BATCH", 16))
    vocab = int(os.environ.get("BENCH_ONLINE_VOCAB", 4096))
    dim = int(os.environ.get("BENCH_ONLINE_DIM", 32))
    sync_every = int(os.environ.get("BENCH_ONLINE_SYNC_EVERY", 4))
    publish_every = int(os.environ.get("BENCH_ONLINE_PUBLISH_EVERY", 8))
    tokens_per = int(os.environ.get("BENCH_ONLINE_TOKENS", 16))

    srv = PSServer("127.0.0.1:0", {"emb": {"type": "geo_sparse",
                                           "dim": dim, "init": "zeros"}})
    ep = srv.start()
    client = PSClient([ep])
    target = np.random.RandomState(3).uniform(
        -1, 1, (vocab, dim)).astype(np.float32)

    def collate(recs):
        ids = np.concatenate([np.asarray(r["prompt"] + r["tokens"],
                                         np.int64) for r in recs])
        return {"ids": ids, "target": target[ids]}

    ds = StreamingDataset(batch_size=batch, collate=collate,
                          name="bench_online")

    def produce():
        rs = np.random.RandomState(11)
        for rid in range(records):
            toks = rs.randint(0, vocab, tokens_per).tolist()
            rec = {"rid": rid, "prompt": toks[:4], "tokens": toks[4:]}
            ds.offer(rec)
            if rid % 3 == 0:    # at-least-once transport duplicates
                ds.offer(rec)
        ds.close()

    paddle.enable_static()
    try:
        prog = static.Program("bench-online")
        with static.program_guard(prog):
            ids_v = static.data("ids", [-1], "int64")
            tgt = static.data("target", [-1, dim], "float32")
            emb = nn.Embedding(vocab, dim)
            diff = emb(ids_v) - tgt
            loss = paddle.ops.mean(paddle.ops.sum(diff * diff, axis=-1))
            optimizer.SGD(learning_rate=0.25).minimize(loss)
        exe = static.Executor()

        pub = EmbeddingSnapshotPublisher(client, "emb")
        publish_s = []
        seen = {"batches": 0}

        def on_batch(_drv):
            seen["batches"] += 1
            if seen["batches"] % publish_every == 0:
                tp = time.perf_counter()
                pub.publish()
                publish_s.append(time.perf_counter() - tp)

        monitor.reset(prefix="ps.online.")
        monitor.reset(prefix="stream.")
        th = threading.Thread(target=produce, daemon=True)
        t0 = time.perf_counter()
        th.start()
        exe.train_from_dataset(program=prog, dataset=ds, ps_config={
            "client": client, "mode": "online", "sync_every": sync_every,
            "sparse": [{"param": emb.weight.scope_name, "slot": "ids",
                        "table": "emb"}],
            "on_batch": on_batch})
        th.join()
        wall = time.perf_counter() - t0
    finally:
        paddle.disable_static()
        client.close()
        srv.shutdown()

    st = ds.stats()
    delta_rows = monitor.stat_get("ps.online.delta_rows")
    print(json.dumps({
        "metric": f"online_learning_loop_b{batch}_d{dim}",
        "value": round(st["delivered_records"] / wall, 1),
        "unit": "records/sec trained",
        "vs_baseline": 1.0,
        "online": {
            "records": st["delivered_records"],
            "duplicates_rejected": st["duplicates"],
            "batches": st["delivered_batches"],
            "sync_every": sync_every,
            "flushes": int(monitor.stat_get("ps.online.flushes")),
            "delta_rows_per_s": round(delta_rows / wall, 1),
            "publishes": len(publish_s),
            "publish_ms_p50": round(float(
                np.percentile(publish_s, 50)) * 1e3, 3)
            if publish_s else None,
            "published_rows": int(monitor.stat_get("ps.publish.rows")),
        },
    }), flush=True)


def bench_traffic():
    """Traffic-lab closed loop (BENCH_MODE=traffic): replay a seeded
    Poisson workload (paddle_tpu/traffic/workload.py) through the shared
    harness (traffic/harness.py run_spec) over the tiny-GPT ServeLoop
    and report completed requests/s plus the hub-comparable p50/p99
    TTFT/token latencies. Scheduler + paged pool + paced arrivals are
    host/dispatch machinery; knobs are pinned by tools/capacity_plan.py's
    self_check."""
    from paddle_tpu.traffic import harness, workload

    requests = int(os.environ.get("BENCH_TRAFFIC_REQUESTS", 96))
    rate = int(os.environ.get("BENCH_TRAFFIC_RATE", 40))
    new = int(os.environ.get("BENCH_TRAFFIC_NEW", 8))
    clients = int(os.environ.get("BENCH_TRAFFIC_CLIENTS", 4))

    spec = workload.WorkloadSpec(
        name="bench-traffic",
        arrival={"kind": "poisson", "rate": float(rate)},
        duration_s=requests / float(rate),
        tenants=({"name": "bench", "weight": 1.0, "kind": "llm",
                  "prompt": {"kind": "lognormal", "median": 8,
                             "sigma": 0.5, "lo": 2},
                  "new": {"kind": "fixed", "value": new}},),
        vocab=1024, max_seq_len=48)
    rep = harness.run_spec(spec, seed=0, clients=clients)
    print(json.dumps({
        "metric": f"traffic_closed_loop_r{rate}",
        "value": rep.throughput_rps,
        "unit": "requests/sec served",
        "vs_baseline": 1.0,
        "traffic": {
            "events": rep.events,
            "completed": rep.completed,
            "errors": rep.errors,
            "offered_rps": rep.offered_rps,
            "tokens_per_s": rep.tokens_per_s,
            "ttft_ms": rep.ttft_ms,
            "token_ms": rep.token_ms,
            "backpressure_waits": rep.backpressure_waits,
            "preempted": rep.preempted,
            "schedule_digest": rep.schedule_digest[:16],
            "scored_by": rep.scored_by,
        },
    }), flush=True)


def _emit_metrics_snapshot(mode):
    """One `{mode}_metrics_snapshot` line per bench mode: the full typed
    monitor snapshot (counters/gauges/histograms — executor pipeline
    gauges, pallas engagement, ps health), so the bench output carries
    the counters behind the perf numbers, not just the numbers
    (tools/obs_report.py self_check pins this emission).

    When PADDLE_TELEMETRY_HUB points at a running telemetry hub
    (core/telemetry.py) and the mode has a fleet behind it
    (serve/online/sparse), the line additionally carries the hub's
    cluster-wide view under "hub" — fleet counters, merged histograms
    and active SLOs next to the local process's numbers."""
    from paddle_tpu.core import monitor
    snap = monitor.snapshot(include_series=False)
    line = {"metric": f"{mode}_metrics_snapshot",
            "value": len(snap["values"]),
            "unit": "metrics", "monitor": snap}
    hub_ep = os.environ.get("PADDLE_TELEMETRY_HUB", "")
    if hub_ep and mode in ("serve", "online", "sparse"):
        from paddle_tpu.core import telemetry
        hub = telemetry.fetch_snapshot(hub_ep)
        line["hub"] = {
            "endpoint": hub_ep,
            "members": hub.get("members"),
            "counters": hub.get("counters"),
            "active_slos": hub.get("active_slos"),
            "span_count": hub.get("span_count"),
        }
    print(json.dumps(line, default=str), flush=True)


def main():
    import jax
    dev = jax.devices()[0]
    print(f"# devices: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(jax.devices())}", file=sys.stderr, flush=True)
    if dev.platform != "tpu":
        raise SystemExit(f"bench: no TPU (platform {dev.platform!r}); "
                         "this benchmark does not run on the CPU")
    _peak_flops()  # an unknown device_kind fails before any mode runs
    modes = {"bert": bench_bert,          # flagship: FIRST stdout line
             "resnet": bench_resnet, "decode": bench_decode,
             "longseq": bench_longseq, "pipeline": bench_pipeline,
             "serve": bench_serve, "sparse": bench_sparse_embedding,
             "online": bench_online, "traffic": bench_traffic}
    mode = os.environ.get("BENCH_MODE", "all")
    if mode != "all" and mode not in modes:
        raise SystemExit(f"bench: unknown BENCH_MODE {mode!r} "
                         f"(choose from all, {', '.join(modes)})")
    for name, fn in modes.items():
        if mode in (name, "all"):
            fn()
            _emit_metrics_snapshot(name)


if __name__ == "__main__":
    main()
