#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two normal entry points once, at the full width of models the
repo has, in ONE process on one TPU (random weights from a seed, data from
a seed, no network, no git, no child process that needs the chip):

- train      BERT-base MLM through paddle.Model(...).prepare(amp "O2").fit
- serve      GPT-2 124M (bf16) behind ServeLoop.start(), ragged greedy
             requests from client threads, plus a teacher-forced
             paged-kernel vs paged_attention_ref logits check; one period
             of the hybrid stack (linear-attention state a slot beside
             paged keys and values) served, its kernels on against off
- kernels    each Pallas kernel compiled by Mosaic against its jnp
             reference, fwd and bwd where it has one
- multichip  (>= 4 devices) the train path through fleet on dp=4 and
             dp=2 x tp=2, a dp=4 fit with BERT's dropout on (each chip
             draws its own rows' bits), and the LocalSGD shard_map step;
             on fewer devices an explicit SKIP

Every phase prints PASS/FAIL with wall time split into compile and run,
peak device memory, and the pallas.* counters with reasons. Any FAIL, any
uncaught exception, or a platform other than "tpu" exits non-zero. The
last stdout line of a passing run is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The phases are plain functions of a `Sizes`; tests/test_chip_smoke.py calls
them at toy size on the CPU (kernels interpreted). This script has no
"run on CPU" switch: off-TPU `main()` refuses.
"""
from __future__ import annotations

import dataclasses
import faulthandler
import json
import sys
import threading
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

SEED = 0
DTYPE = jnp.bfloat16   # what both models compute in on the chip
# normalized max error |got - ref|_max / |ref|_max a bf16 kernel may show
# against its f32 jnp reference on the same bf16 inputs (bf16 eps is 2^-8
# ~ 3.9e-3; probabilities and outputs are each rounded once)
KERNEL_TOL = 2e-2
# same measure for last-position logits of the whole 12-layer model, paged
# kernel on vs paged_attention_ref (both bf16; rounding compounds by layer)
LOGITS_TOL = 5e-2
# relative gap allowed between the one-chip and the sharded final loss
# (same global batches, dropout off; bf16 reductions reorder under GSPMD)
MULTICHIP_LOSS_TOL = 2e-2
# every serve request must be back by then, compiles included: a scheduler
# thread that died leaves its requests waiting, and the smoke must end
SERVE_DEADLINE_S = 600.0


@dataclasses.dataclass
class Sizes:
    """Everything a phase needs to know about how big to run."""
    bert: object                 # BertConfig
    train_batch: int
    train_seq: int
    train_steps: int
    train_lr: float
    gpt: object                  # GPTConfig
    serve_requests: int
    serve_max_active: int
    serve_kv_blocks: int
    serve_prompt_bands: tuple    # ((lo, hi), ...) inclusive
    serve_new_tokens: tuple      # (lo, hi) inclusive
    serve_clients: int
    serve_generate_checks: int   # requests compared to net.generate
    serve_expect_hits: tuple     # kernels that must engage in serve
    forced_prompts: tuple        # teacher-forced check: real prompt lens
    forced_bucket: int           #   padded to this prefill bucket
    flash_shape: tuple           # (b, h, s, d)
    ce_shape: tuple              # (n, hidden, vocab)
    decode_shape: tuple          # (b, h, L, d)
    paged_checks: tuple          # (query rows, block size or None=picker)
    multichip_layers: int
    multichip_steps: int
    latent: object               # KimiK2Config: the latent-cache programs
    latent_serve: tuple          # (slots, blocks, block, max_seq, bucket)
    scmoe: object                # LongCatFlashConfig: two caches a layer
    scmoe_serve: tuple           # (slots, blocks, block, max_seq, bucket)
    hybrid: object               # OlmoHybridConfig: one period, served
    hybrid_serve: tuple          # (slots, blocks, block, max_seq, prompts)
    window: object               # LagunaConfig: a full and a sliding layer
    window_serve: tuple          # (slots, blocks, block, max_seq)
    sink: object                 # MiMoV2Config: a full and a sliding layer
    sink_serve: tuple            # (slots, blocks, block, max_seq)

    @staticmethod
    def full():
        from paddle_tpu.text.models.bert import BertConfig
        from paddle_tpu.text.models.gpt import GPTConfig
        from paddle_tpu.text.models.kimi_k2 import KimiK2Config
        from paddle_tpu.text.models.laguna import LagunaConfig
        from paddle_tpu.text.models.longcat_flash import LongCatFlashConfig
        from paddle_tpu.text.models.mimo_v2 import MiMoV2Config
        from paddle_tpu.text.models.olmo_hybrid import OlmoHybridConfig
        return Sizes(
            bert=BertConfig.bert_base(), train_batch=32, train_seq=128,
            train_steps=40, train_lr=1e-4,
            gpt=GPTConfig(), serve_requests=16, serve_max_active=8,
            serve_kv_blocks=96,
            # two bands, not one length per power of two: every prefill
            # bucket is a compile
            serve_prompt_bands=((16, 64), (256, 512)),
            serve_new_tokens=(32, 128), serve_clients=4,
            serve_generate_checks=4,
            serve_expect_hits=("paged_decode_attention",
                               "paged_write_attend"),
            forced_prompts=(37, 120, 200, 256), forced_bucket=256,
            flash_shape=(2, 12, 1024, 64), ce_shape=(4096, 768, 30522),
            decode_shape=(8, 12, 1024, 64),
            # a decode beat, a prefill chunk, and the smallest block
            # FLAGS_serve_block_size admits: 8 rows is under bf16's
            # (16, 128) tile, which Mosaic compiles all the same
            paged_checks=((1, None), (64, None), (1, 8)),
            multichip_layers=4, multichip_steps=8,
            # the benchmark's share of Kimi-K2.7-Code at its published
            # widths, one dense and one expert layer, its pool and its
            # largest prefill bucket
            latent=KimiK2Config(
                vocab_size=20480, num_layers=2, experts_held=(0, 12),
                max_seq_len=3072, dtype="bfloat16", rope_scaling={
                    "type": "yarn", "factor": 64, "beta_fast": 32,
                    "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                    "original_max_position_embeddings": 4096}),
            latent_serve=(64, 1024, 128, 3072, 2048),
            # the benchmark's share of LongCat-Flash-Chat at its published
            # widths, one shortcut-connected layer (two latent attentions,
            # two dense FFNs, 16 of 512 routed experts held beside 256
            # zero-compute experts), its pool and its largest bucket
            scmoe=LongCatFlashConfig(
                vocab_size=16384, num_layers=1, experts_held=(0, 16),
                max_seq_len=3072, dtype="bfloat16"),
            scmoe_serve=(128, 1536, 128, 3072, 2048),
            # one period of the hybrid stack at its published widths (a
            # sixteenth of the vocabulary): the gated delta-rule kernels'
            # first compile on a chip, outside the benchmark
            hybrid=OlmoHybridConfig(
                vocab_size=6272, dtype="bfloat16", max_seq_len=1024,
                layer_types=["linear_attention"] * 3 + ["full_attention"]),
            hybrid_serve=(4, 32, 128, 1024, (70, 200, 513)),
            # the benchmark's share of Laguna-S-2.1 at its published
            # widths, the leading full layer (48 query heads over 8) and
            # one sliding layer (72 over 8, a ring of 512 tokens a slot),
            # its slots, and a pool that pages one layer
            window=LagunaConfig(vocab_size=12544, num_layers=2,
                                experts_held=(0, 16), dtype="bfloat16"),
            window_serve=(128, 1024, 128, 9216),
            # the benchmark's share of MiMo-V2-Flash at its published
            # widths, the leading full layer (64 query heads over 4, keys
            # of 192 over values of 128, dense FFN) and one sliding expert
            # layer (64 over 8, sinks, a ring of ONE 128-token block a
            # slot), its slots, and a pool that pages one layer
            sink=MiMoV2Config(vocab_size=19072, num_hidden_layers=2,
                              experts_held=(0, 16), dtype="bfloat16"),
            sink_serve=(128, 2048, 128, 14336))

    @staticmethod
    def toy():
        from paddle_tpu.text.models.bert import BertConfig
        from paddle_tpu.text.models.gpt import GPTConfig
        from paddle_tpu.text.models.kimi_k2 import KimiK2Config
        from paddle_tpu.text.models.laguna import LagunaConfig
        from paddle_tpu.text.models.longcat_flash import LongCatFlashConfig
        from paddle_tpu.text.models.mimo_v2 import MiMoV2Config
        from paddle_tpu.text.models.olmo_hybrid import OlmoHybridConfig
        return Sizes(
            bert=BertConfig.tiny(), train_batch=8, train_seq=16,
            train_steps=12, train_lr=1e-2,
            gpt=GPTConfig.tiny(), serve_requests=6, serve_max_active=2,
            serve_kv_blocks=24, serve_prompt_bands=((3, 8), (17, 30)),
            serve_new_tokens=(3, 6), serve_clients=2,
            serve_generate_checks=1, serve_expect_hits=(),
            forced_prompts=(5, 14), forced_bucket=16,
            flash_shape=(1, 2, 128, 16), ce_shape=(64, 32, 200),
            decode_shape=(2, 2, 64, 16), paged_checks=((1, None),),
            multichip_layers=1, multichip_steps=3,
            latent=KimiK2Config.tiny(num_layers=2, experts_held=(4, 8),
                                     dtype="bfloat16"),
            latent_serve=(2, 8, 16, 64, 32),
            scmoe=LongCatFlashConfig.tiny(num_layers=1, experts_held=(4, 8),
                                          dtype="bfloat16"),
            scmoe_serve=(2, 8, 16, 64, 32),
            hybrid=OlmoHybridConfig.tiny(
                dtype="bfloat16", init_std=0.1,
                layer_types=["linear_attention"] * 3 + ["full_attention"]),
            hybrid_serve=(2, 8, 16, 64, (5, 14, 23)),
            window=LagunaConfig.tiny(num_layers=2, experts_held=(4, 8),
                                     dtype="bfloat16", sliding_window=16,
                                     ring_block=8),
            window_serve=(2, 16, 8, 64),
            sink=MiMoV2Config.tiny(num_hidden_layers=2, experts_held=(4, 8),
                                   dtype="bfloat16"),
            sink_serve=(2, 16, 8, 64))


# --------------------------------------------------------------------------
# accounting: compile seconds and persistent-cache traffic from jax's own
# monitoring events, so "compile" is what jax says it is
# --------------------------------------------------------------------------

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_acct = {"compile_s": 0.0, "backend_compiles": 0, "cache_hits": 0,
         "cache_misses": 0}
_acct_lock = threading.Lock()
_listening = False


def _on_duration(event, duration, **_):
    if event in _COMPILE_EVENTS:
        with _acct_lock:
            _acct["compile_s"] += duration
            if event == _COMPILE_EVENTS[2]:
                _acct["backend_compiles"] += 1


def _on_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        with _acct_lock:
            _acct["cache_hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        with _acct_lock:
            _acct["cache_misses"] += 1


def _listen():
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True


def _acct_snapshot():
    with _acct_lock:
        return dict(_acct)


def _pallas_counters():
    from paddle_tpu.core import monitor
    return {k: int(v) for k, v in sorted(monitor.stats("pallas.").items())}


def _block_sizes():
    """Measured autotune winners (the only block sizes that can differ
    from run to run; everything else is the static heuristic)."""
    from paddle_tpu.ops.pallas import autotune
    return {"|".join(str(x) for x in k): list(v)
            for k, v in sorted(autotune.table_snapshot().items())}


def _rel_err(got, ref):
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


def run_phase(name, fn, *args):
    """Run one phase; print its START line first (a SIGABRT inside Mosaic
    kills the process, and the last START line names the culprit), then a
    PASS/FAIL line. Returns the phase's result dict with "ok" set."""
    from paddle_tpu import memory
    from paddle_tpu.core import monitor
    _listen()
    monitor.reset(prefix="pallas.")
    print(f"PHASE {name} START", flush=True)
    before = _acct_snapshot()
    t0 = time.perf_counter()
    try:
        result = fn(*args)
    except Exception:  # the smoke must report every phase, then fail
        traceback.print_exc()
        sys.stderr.flush()
        result = {"failures": ["uncaught exception (traceback on stderr)"]}
    wall = time.perf_counter() - t0
    after = _acct_snapshot()
    counters = _pallas_counters()
    fallbacks = {k: v for k, v in counters.items()
                 if k.startswith("pallas.fallback.") and v}
    if fallbacks:
        result["failures"].append(f"pallas fallbacks: {fallbacks}")
    result["ok"] = not result["failures"]
    compile_s = after["compile_s"] - before["compile_s"]
    peak = memory.max_memory_allocated()  # 0 where PJRT tracks no peak
    status = result.get("skip") or ("PASS" if result["ok"] else "FAIL")
    print(f"PHASE {name} {status} wall={wall:.1f}s compile={compile_s:.1f}s "
          f"run={max(wall - compile_s, 0.0):.1f}s "
          f"backend_compiles={after['backend_compiles'] - before['backend_compiles']} "
          f"cache_hits={after['cache_hits'] - before['cache_hits']} "
          f"cache_writes={after['cache_misses'] - before['cache_misses']} "
          f"peak_bytes_in_use_so_far={peak or 'n/a'}",
          flush=True)
    for key, value in result.items():
        if key not in ("failures", "ok", "skip"):
            print(f"  {name}.{key}: {value}", flush=True)
    print(f"  {name}.pallas_counters: {counters}", flush=True)
    print(f"  {name}.autotuned_blocks: {_block_sizes()}", flush=True)
    for failure in result["failures"]:
        print(f"  {name} FAILURE: {failure}", flush=True)
    return result


# --------------------------------------------------------------------------
# train: BERT MLM through Model.prepare(amp O2).fit
# --------------------------------------------------------------------------

def _bert_model(cfg, sizes, strategy=None):
    """Seeded BERT behind paddle.Model, prepared for bf16 O2 + AdamW. With
    a fleet `strategy` the optimizer is fleet-wrapped (the engine reads
    e.g. LocalSGD from it) and amp comes from the strategy."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models.bert import Bert, BertPretrainingCriterion

    paddle.seed(SEED)
    net = Bert(cfg)
    model = paddle.Model(net)
    opt = paddle.optimizer.AdamW(learning_rate=sizes.train_lr,
                                 parameters=net.parameters())
    # Model.fit's contract is loss(net(inputs), labels): it reaches
    # BertPretrainingCriterion over materialized logits. The fused MLM
    # head needs the labels as a network INPUT, which fit does not do;
    # the kernels phase compiles the fused-CE kernel instead.
    criterion = BertPretrainingCriterion(cfg.vocab_size)
    if strategy is None:
        return model.prepare(opt, criterion, amp_configs="O2")
    from paddle_tpu.distributed import fleet
    return model.prepare(fleet.distributed_optimizer(opt, strategy),
                         criterion)


def _fit(model, sizes, steps):
    """`steps` batches of seeded MLM data through Model.fit. Returns
    (per-step losses, backend compiles after step 1)."""
    from paddle_tpu.hapi.callbacks import Callback
    from paddle_tpu.text.datasets import LMDataset

    class Recorder(Callback):
        def __init__(self):
            super().__init__()
            self.losses = []
            self.compiles_at_step1 = None

        def on_train_batch_end(self, step, logs=None):
            self.losses.append(float(logs["loss"]))  # syncs this step
            if self.compiles_at_step1 is None:
                self.compiles_at_step1 = _acct_snapshot()["backend_compiles"]

    data = LMDataset(vocab_size=model.network.config.vocab_size,
                     seq_len=sizes.train_seq, n=steps * sizes.train_batch,
                     mode="mlm", seed=SEED)
    rec = Recorder()
    # num_workers=0: forked loader workers under a live TPU client are
    # untested (docs/chip_runs.md)
    model.fit(data, batch_size=sizes.train_batch, epochs=1, shuffle=False,
              drop_last=True, num_workers=0, verbose=0, callbacks=[rec])
    late = _acct_snapshot()["backend_compiles"] - rec.compiles_at_step1
    return rec.losses, late


def train_phase(sizes):
    failures = []
    model = _bert_model(sizes.bert, sizes)
    losses, late_compiles = _fit(model, sizes, sizes.train_steps)
    k = max(1, len(losses) // 8)
    start, end = float(np.mean(losses[:k])), float(np.mean(losses[-k:]))
    if len(losses) != sizes.train_steps:
        failures.append(f"ran {len(losses)} steps, wanted "
                        f"{sizes.train_steps}")
    if not np.isfinite(losses).all():
        failures.append(f"non-finite loss: {losses}")
    elif not end < start:
        failures.append(f"loss did not fall: {start:.4f} -> {end:.4f}")
    step_variants = model._engine._train_fn._cache_size()
    if step_variants != 1:
        failures.append(f"train step compiled {step_variants} times")
    if late_compiles:
        failures.append(f"{late_compiles} backend compiles after step 1")
    want = jax.devices()[0].platform
    named = dict(model.network.named_parameters())
    params = list(named.values())
    off = [n for n, p in named.items()
           if {d.platform for d in p._value.devices()} != {want}]
    if off:
        failures.append(f"parameters not on {want}: {off[:3]}")
    return {
        "failures": failures,
        "loss_head": "BertPretrainingCriterion (materialized logits)",
        "params": int(sum(p.size for p in params)),
        "param_dtypes": sorted({str(p._value.dtype) for p in params}),
        "loss_start": round(start, 4), "loss_end": round(end, 4),
        "steps": len(losses), "compiles_after_step1": late_compiles,
    }


# --------------------------------------------------------------------------
# serve: GPT behind ServeLoop.start(), client threads, teacher-forced check
# --------------------------------------------------------------------------

def _bf16_gpt(cfg):
    import paddle_tpu as paddle
    from paddle_tpu.text.models.gpt import GPT
    paddle.seed(SEED)
    net = GPT(cfg)
    net.eval()
    paddle.amp.decorate(net, level="O2", dtype="bfloat16")
    return net


def _forced_logits(net, sizes, block_size):
    """Teacher-force one bucket-padded prefill and one decode beat through
    net._forward_paged twice — paged kernel on, then off (the counted
    flag_off gate onto paged_attention_ref) — and return the normalized
    max logits error of each."""
    import paddle_tpu as paddle
    from paddle_tpu.core import tape
    from paddle_tpu.nn.kv_pool import KVBlockPool, PagedKVCache

    cfg = net.config
    lens = np.asarray(sizes.forced_prompts, np.int32)
    b, bucket = len(lens), sizes.forced_bucket
    per_slot = -(-(bucket + 1) // block_size)
    pool = KVBlockPool(b * per_slot, block_size)
    tables = np.asarray([pool.alloc(per_slot) for _ in range(b)], np.int32)
    heads, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    rng = np.random.RandomState(SEED + 1)
    ids = np.zeros((b, bucket), np.int32)
    for i, n in enumerate(lens):
        ids[i, :n] = rng.randint(1, cfg.vocab_size, n)
    params, buffers = net.functional_state()

    def forward(params, arenas, tokens, lengths, last_index):
        with tape.no_grad():
            net.load_functional_state(params, buffers)
            caches = [PagedKVCache(k, v, jnp.asarray(tables), lengths)
                      for k, v in arenas]
            logits, new = net._forward_paged(tokens, caches,
                                             last_index=last_index)
        return logits, [(c.k, c.v) for c in new]

    def both_paths(arenas, tokens, lengths, last_index):
        out = {}
        for kernel_on in (True, False):
            paddle.set_flags({"FLAGS_use_paged_attention": kernel_on})
            try:
                # the flag is read at trace time and jit caches by function
                # identity: a fresh lambda per path forces a fresh trace
                out[kernel_on] = jax.jit(lambda *a: forward(*a))(
                    params, arenas, tokens, lengths, last_index)
            finally:
                paddle.set_flags({"FLAGS_use_paged_attention": True})
                net.load_functional_state(params, buffers)
        return out

    arenas = pool.arenas(cfg.num_layers, heads, hd, DTYPE)
    pre = both_paths(arenas, jnp.asarray(ids), jnp.zeros((b,), jnp.int32),
                     jnp.asarray(lens - 1))
    nxt = jnp.argmax(pre[False][0], axis=-1).astype(jnp.int32)
    dec = both_paths(pre[False][1], nxt[:, None], jnp.asarray(lens), None)
    return {"prefill": _rel_err(pre[True][0], pre[False][0]),
            "decode": _rel_err(dec[True][0], dec[False][0])}


def arena_relayouts(hlo_text, arena_shape):
    """The copy / transpose instructions of arena shape in a compiled
    program's optimized HLO."""
    import re
    dims = ",".join(str(n) for n in arena_shape)
    return re.findall(r"= \w+\[%s\]\S* (copy|transpose)\(" % dims, hlo_text)


_HLO_ITEM_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2,
                   "s16": 2, "u16": 2, "f32": 4, "s32": 4, "u32": 4,
                   "f64": 8, "s64": 8, "u64": 8}


def lane_padded_results(hlo_text, min_bytes=64 << 10):
    """The instructions of a compiled program's optimized HLO whose
    result is an array with a minor dimension of 1 (by its layout) and
    more than `min_bytes` as laid out: one element a 128-lane row. The
    decode step's token writer once asked for one, `bf16[32,25,64,1]
    {3,2,1,0}`: 13 MB for 102 KB of tokens, a `copy` before every call
    (PR 34). Instructions inside a fusion are no buffers and are left
    out."""
    import re
    fused = set(re.findall(r" fusion\(.*?calls=%?([\w.\-]+)", hlo_text))
    found, skip = [], False
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            skip = head.group(1) in fused
        m = not skip and re.match(
            r"\s+(?:ROOT )?%?[\w.\-]+ = ((\w+)\[([\d,]+)\]"
            r"\{([\d,]+)[^}]*\}) ([\w\-]+)\(", line)
        if not m:
            continue
        shape, dtype, dims, minor_to_major, op = m.groups()
        dims = [int(n) for n in dims.split(",")]
        if len(dims) < 2 or dims[int(minor_to_major.split(",")[0])] != 1:
            continue
        laid_out = int(np.prod(dims)) * 128 * _HLO_ITEM_BYTES.get(dtype, 4)
        if laid_out > min_bytes:
            found.append(f"{op} {shape}")
    return found


def _serve_program_memory(loop, bucket):
    """Lower and compile ServeLoop's own decode step and one prefill
    bucket (the persistent cache has both after the run above) and read
    what a compiled program does to the KV arenas: its temp bytes beside
    one arena's, and the copy / transpose instructions of arena shape in
    its optimized HLO. The arenas have one device layout (nn/kv_pool.py):
    a relayout, or a temp the size of an arena, means XLA is copying them
    again. `head_bytes` is the one large temp a serve program does hold:
    the tied embedding, transposed for the head's matmul."""
    A, MB = loop._A, loop._MB
    arena = loop._arenas[0][0]
    arena_bytes = arena.size * arena.dtype.itemsize
    wte = getattr(loop.net, "wte", None)   # an untied head holds no such temp
    head_bytes = 0 if wte is None else \
        wte.weight._value.size * wte.weight._value.dtype.itemsize

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    state = (loop._params, loop._buffers, loop._arenas)
    i32, u32 = jnp.int32, jnp.uint32
    lowered = {
        "decode": lambda: loop._step_jit.lower(
            *state, spec((A, MB), i32), spec((A,), i32), loop._tokens,
            spec((A, 2), u32)),
        f"prefill{bucket}": lambda: loop._prefill_jit.lower(
            *state, loop._tokens, spec((1, MB), i32), spec((1, bucket), i32),
            spec((), i32), spec((2,), u32), spec((), i32)),
    }
    out = {}
    try:
        for name, lower in lowered.items():
            compiled = lower().compile()
            text, mem = compiled.as_text(), compiled.memory_analysis()
            out[name] = {
                "temp_bytes": int(mem.temp_size_in_bytes),
                "alias_bytes": int(mem.alias_size_in_bytes),
                "arena_bytes": int(arena_bytes),
                "arenas": sum(len(layer) for layer in loop._arenas),
                "head_bytes": int(head_bytes),
                "arena_relayouts": len(arena_relayouts(text, arena.shape)),
                "conditionals": text.count(" conditional("),
            }
    finally:  # tracing rebinds the live layers' parameters to tracers
        loop.net.load_functional_state(loop._params, loop._buffers)
    return out


def latent_serve_programs(sizes):
    """`_serve_program_memory` for the nets that cache latents: ONE a
    token a layer (text/models/kimi_k2.py, at `sizes.latent`'s widths
    behind a ServeLoop of `sizes.latent_serve`) and TWO a layer
    (text/models/longcat_flash.py, `sizes.scmoe` / `sizes.scmoe_serve`,
    its programs under `scmoe_<name>`): the decode step and the largest
    prefill bucket of each, which at full size works tile by tile
    (`tiles`: how many the net cuts that bucket into, 0 for a bucket that
    runs whole; a tile of queries past the first is a conditional). The
    pool's one layout has to hold for the one-head arena too: no copy or
    transpose of arena shape, every donated arena aliased. The temp is
    reported and not held to an arena's size: these are whole model
    programs, and their temps are activations (tests/test_chip_smoke.py
    holds the decode steps' to a bound ahead of time: the latent kernel
    reads a slot's blocks where they lie)."""
    from paddle_tpu.inference import ServeConfig, ServeLoop
    from paddle_tpu.text.models.kimi_k2 import KimiK2
    from paddle_tpu.text.models.longcat_flash import LongCatFlash
    out = {}
    for prefix, model, config, serve in (
            ("", KimiK2, sizes.latent, sizes.latent_serve),
            ("scmoe_", LongCatFlash, sizes.scmoe, sizes.scmoe_serve)):
        slots, blocks, block, max_seq, bucket = serve
        net = model(config)
        net.eval()
        loop = ServeLoop(net, ServeConfig(
            max_active=slots, kv_blocks=blocks, block_size=block,
            max_seq_len=max_seq))
        programs = _serve_program_memory(loop, bucket)
        tile = net.prefill_tile(bucket)
        programs[f"prefill{bucket}"]["tiles"] = bucket // tile if tile else 0
        out.update((prefix + name, mem) for name, mem in programs.items())
        del net, loop
    return out


def hybrid_serve(sizes):
    """A net that keeps one state a decode slot beside its paged keys and
    values (text/models/olmo_hybrid.py at `sizes.hybrid`, one period)
    behind a ServeLoop of `sizes.hybrid_serve`: its prompts served
    greedily, then the same prompts once more with the pool's kernels
    gated off (`FLAGS_use_paged_attention`: the chunked scan, the state
    update, the paged pair all on their `jax.numpy` forms). The served
    tokens are compared position by position through teacher-forced
    logits, not by equality: -> {"completed", "hits" (the four kernels'
    engagements with the flag on), "logits_err" (normalized max error of
    the kernels-on logits against the kernels-off ones over every served
    position, the kernels-on server fed the kernels-off tokens)}."""
    import paddle_tpu as paddle
    from paddle_tpu.core import monitor
    from paddle_tpu.core import tape
    from paddle_tpu.inference import ServeConfig, ServeLoop
    from paddle_tpu.nn.kv_pool import (KVBlockPool, cache_arenas,
                                       fresh_slot_rows, paged_caches,
                                       put_slot_rows)
    from paddle_tpu.text.models.olmo_hybrid import OlmoHybrid

    slots, blocks, block, max_seq, prompt_lens = sizes.hybrid_serve
    paddle.seed(SEED)
    net = OlmoHybrid(sizes.hybrid)
    net.eval()
    rng = np.random.RandomState(SEED + 2)
    prompts = [rng.randint(1, sizes.hybrid.vocab_size, n)
               for n in prompt_lens]
    before = _pallas_counters()
    loop = ServeLoop(net, ServeConfig(max_active=slots, kv_blocks=blocks,
                                      block_size=block, max_seq_len=max_seq))
    outs = loop.serve(prompts, max_new_tokens=4)
    hits = {k.rsplit(".", 1)[1]: v - before.get(k, 0)
            for k, v in _pallas_counters().items()
            if k.startswith("pallas.hit.")}
    bucket_of = {n: loop._bucket(n) for n in prompt_lens}
    del loop

    params, buffers = net.functional_state()
    spec = net.paged_cache_spec()
    pool = KVBlockPool(blocks, block)
    width = -(-max_seq // block)

    def forced(ids, prompt_len):
        """Logits of positions prompt_len - 1 .. len(ids) - 2 through a
        bucket-padded prefill of slot 0 and one decode step a token."""
        table = np.zeros((1, width), np.int32)
        table[0, :pool.blocks_for(len(ids))] = np.arange(
            1, pool.blocks_for(len(ids)) + 1)
        table = jnp.asarray(table)

        def step(params, arenas, tokens, lengths, last_index):
            with tape.no_grad():
                net.load_functional_state(params, buffers)
                view = fresh_slot_rows(spec, arenas) \
                    if last_index is not None else arenas
                logits, caches, _ = net._forward_paged(
                    tokens, paged_caches(spec, view, table, lengths),
                    last_index=last_index)
                new = cache_arenas(caches)
                if last_index is not None:
                    new = put_slot_rows(spec, arenas, new, jnp.int32(0))
            return logits, new

        step = jax.jit(step)
        padded = np.zeros((1, bucket_of[prompt_len]), np.int32)
        padded[0, :prompt_len] = ids[:prompt_len]
        arenas = pool.arenas_for(spec, DTYPE, slots=1)
        try:
            logits, arenas = step(params, arenas, jnp.asarray(padded),
                                  jnp.zeros((1,), jnp.int32),
                                  jnp.asarray([prompt_len - 1], jnp.int32))
            rows = [logits[0]]
            for n in range(prompt_len, len(ids) - 1):
                logits, arenas = step(
                    params, arenas, jnp.asarray(ids[n:n + 1][None],
                                                jnp.int32),
                    jnp.asarray([n], jnp.int32), None)
                rows.append(logits[0])
        finally:
            net.load_functional_state(params, buffers)
        return np.stack([np.asarray(r, np.float32) for r in rows])

    errs = []
    for prompt, out in zip(prompts, outs):
        ids = np.concatenate([prompt, out]).astype(np.int32)
        on = forced(ids, len(prompt))
        paddle.set_flags({"FLAGS_use_paged_attention": False})
        try:
            off = forced(ids, len(prompt))
        finally:
            paddle.set_flags({"FLAGS_use_paged_attention": True})
        errs.append(_rel_err(on, off))
    return {"completed": sum(len(o) == 4 for o in outs), "hits": hits,
            "logits_err": float(f"{max(errs):.3g}"),
            "state_bytes": int(monitor.stat_get("serve.state_bytes"))}


HYBRID_KERNELS = ("gdn_chunk_scan", "gdn_step")


def paged_step_engaged(hits) -> bool:
    """Did a net's decode step write its token and attend through Pallas?
    One kernel that does both where `nn/kv_pool.paged_write_attend`'s gate
    admits the shape, else the token writer and the kernel apart (the
    hybrid at its published widths: 30 heads of 128)."""
    return bool(hits.get("paged_write_attend")
                or (hits.get("paged_decode_attention")
                    and hits.get("paged_write_token")))


def serve_phase(sizes):
    import paddle_tpu as paddle
    from paddle_tpu.core import monitor
    from paddle_tpu.inference import ServeConfig, ServeLoop

    failures = []
    cfg = sizes.gpt
    net = _bf16_gpt(cfg)
    monitor.reset(prefix="serve.")
    loop = ServeLoop(net, ServeConfig(
        max_active=sizes.serve_max_active, kv_blocks=sizes.serve_kv_blocks,
        max_seq_len=cfg.max_seq_len))
    block_size = loop.stats()["block_size"]

    rng = np.random.RandomState(SEED)
    n = sizes.serve_requests
    prompts, news = [], []
    for i in range(n):
        lo, hi = sizes.serve_prompt_bands[i % len(sizes.serve_prompt_bands)]
        prompts.append(rng.randint(1, cfg.vocab_size,
                                   rng.randint(lo, hi + 1)).astype(np.int64))
        news.append(int(rng.randint(sizes.serve_new_tokens[0],
                                    sizes.serve_new_tokens[1] + 1)))
    outs, errors = [None] * n, []
    deadline = time.monotonic() + SERVE_DEADLINE_S

    def client(base):
        reqs = [(i, loop.submit(prompts[i], max_new_tokens=news[i]))
                for i in range(base, n, sizes.serve_clients)]
        for i, req in reqs:
            try:
                outs[i] = req.result(
                    timeout=max(1.0, deadline - time.monotonic()))
            except Exception as e:  # every request's error is a finding
                errors.append(f"request {i}: {type(e).__name__}: {e}")

    loop.start()
    try:
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(sizes.serve_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        loop.stop(timeout=120)

    failures += errors
    bad = [i for i, o in enumerate(outs)
           if o is not None and len(o) != news[i]]
    if bad:
        failures.append(f"requests with wrong token count: {bad}")
    completed = int(monitor.stat_get("serve.requests_completed"))
    if completed != n:
        failures.append(f"serve.requests_completed={completed}, "
                        f"submitted {n}")
    counters = _pallas_counters()
    for kernel in sizes.serve_expect_hits:
        if not counters.get(f"pallas.hit.{kernel}"):
            failures.append(f"kernel {kernel} never engaged in serve")

    buckets = sorted({loop._bucket(len(p)) for p in prompts})
    programs = _serve_program_memory(loop, buckets[-1])
    for name, mem in programs.items():
        # a property of the TPU's compiler: the toy run on the CPU
        # (tests/test_chip_smoke.py) reports the numbers and gates nothing
        if jax.default_backend() == "tpu" and (
                mem["temp_bytes"] - mem["head_bytes"] >= mem["arena_bytes"]
                or mem["arena_relayouts"]):
            failures.append(
                f"compiled {name} program relays out the KV arena: "
                f"temp {mem['temp_bytes']} B (head {mem['head_bytes']} B, "
                f"one arena {mem['arena_bytes']} B), "
                f"{mem['arena_relayouts']} "
                "copy/transpose instructions of arena shape")

    programs_latent = latent_serve_programs(sizes)
    for name, mem in programs_latent.items():
        if jax.default_backend() != "tpu":
            continue
        if mem["arena_relayouts"]:
            failures.append(
                f"compiled latent-cache {name} program relays out the "
                f"arena: {mem['arena_relayouts']} copy/transpose "
                "instructions of arena shape")
        if mem["alias_bytes"] < mem["arenas"] * mem["arena_bytes"]:
            failures.append(
                f"compiled latent-cache {name} program aliases "
                f"{mem['alias_bytes']} B of {mem['arenas']} donated arenas "
                f"of {mem['arena_bytes']} B")
        if mem.get("tiles") and mem["conditionals"] < mem["tiles"] - 1:
            failures.append(
                f"compiled latent-cache {name} program is cut into "
                f"{mem['tiles']} tiles and holds {mem['conditionals']} "
                "conditionals: its queries' tiles run whole")

    hybrid = hybrid_serve(sizes)
    if hybrid["completed"] != len(sizes.hybrid_serve[4]):
        failures.append(f"hybrid net: {hybrid['completed']} of "
                        f"{len(sizes.hybrid_serve[4])} requests completed")
    if not hybrid["logits_err"] <= LOGITS_TOL:
        failures.append("hybrid net: teacher-forced logits, kernels vs "
                        f"their jnp forms err {hybrid['logits_err']:.3g} > "
                        f"{LOGITS_TOL}")
    if jax.default_backend() == "tpu":
        for kernel in HYBRID_KERNELS:
            if not hybrid["hits"].get(kernel):
                failures.append(f"hybrid net: {kernel} never engaged")
        if not paged_step_engaged(hybrid["hits"]):
            failures.append("hybrid net: the paged write and attention "
                            f"never engaged: {hybrid['hits']}")

    forced = _forced_logits(net, sizes, block_size)
    if not _pallas_counters().get(
            "pallas.gate_reject.paged_decode_attention.flag_off"):
        failures.append("teacher-forced check never traced the "
                        "paged_attention_ref path")
    for beat, err in forced.items():
        if not err <= LOGITS_TOL:
            failures.append(f"teacher-forced {beat} logits: kernel vs "
                            f"paged_attention_ref err {err:.3g} > "
                            f"{LOGITS_TOL}")

    # reported, not gated: bf16 argmax ties may flip between kernels, and
    # a stream never rejoins after its first flipped token
    agree = []
    for i in [i for i in range(n) if outs[i] is not None][
            :sizes.serve_generate_checks]:
        ref = net.generate(paddle.to_tensor(prompts[i][None]),
                           max_new_tokens=news[i], temperature=0)
        ref = np.asarray(ref._value)[0, len(prompts[i]):]
        differ = np.nonzero(ref != outs[i])[0]
        agree.append(f"{differ[0] if len(differ) else len(ref)}/{len(ref)}")

    return {
        "failures": failures, "requests": n, "completed": completed,
        "programs": programs,
        "programs_latent": programs_latent,
        "hybrid": hybrid,
        "tokens_generated": int(monitor.stat_get("serve.tokens_generated")),
        "preempted": int(monitor.stat_get("serve.preempted")),
        "backpressure_waits":
            int(monitor.stat_get("serve.backpressure_waits")),
        "pool_block_size": block_size, "prefill_buckets": buckets,
        "prompt_lens": [len(p) for p in prompts], "new_tokens": news,
        "forced_logits_err": {k: float(f"{v:.3g}")
                              for k, v in forced.items()},
        "logits_tol": LOGITS_TOL,
        "tokens_agreeing_with_generate_before_first_flip": agree,
    }


# --------------------------------------------------------------------------
# kernels: each Pallas kernel against its jnp reference
# --------------------------------------------------------------------------

def _f32(*xs):
    return [x.astype(jnp.float32) for x in xs]


def _check_flash(sizes, causal):
    from paddle_tpu.nn import functional as F
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    b, h, s, d = sizes.flash_shape
    ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q, k, v, w = (jax.random.normal(kk, (b, h, s, d), jnp.float32)
                  .astype(DTYPE) for kk in ks)
    bias = None
    if not causal:  # padding mask: the tail quarter of row 0 is padding
        live = np.ones((b, s), bool)
        live[0, 3 * s // 4:] = False
        bias = jnp.where(jnp.asarray(live), 0.0, -1e9).astype(jnp.float32)
    scale = d ** -0.5

    def kernel(q, k, v):
        out = flash_attention(q, k, v, bias=bias, causal=causal)
        return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)), out

    def ref(q, k, v):
        mask = None if bias is None else bias[:, None, None, :]
        out = F._sdpa.raw(q, k, v, mask, scale, causal)
        return jnp.sum(out * w.astype(jnp.float32)), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        kernel, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (_, out_r), grads_r = jax.jit(jax.value_and_grad(
        ref, argnums=(0, 1, 2), has_aux=True))(*_f32(q, k, v))
    errs = {"out": _rel_err(out, out_r)}
    errs.update({n: _rel_err(g, r)
                 for n, g, r in zip(("dq", "dk", "dv"), grads, grads_r)})
    return errs


def _check_fused_ce(sizes):
    from paddle_tpu.nn import functional as F
    from paddle_tpu.ops.pallas.fused_ce import fused_linear_cross_entropy

    n, hidden, vocab = sizes.ce_shape
    ks = jax.random.split(jax.random.PRNGKey(SEED + 1), 4)
    h = jax.random.normal(ks[0], (n, hidden), jnp.float32).astype(DTYPE)
    w = (0.05 * jax.random.normal(ks[1], (vocab, hidden), jnp.float32)) \
        .astype(DTYPE)
    bvec = (0.1 * jax.random.normal(ks[2], (vocab,), jnp.float32)) \
        .astype(DTYPE)
    y = jax.random.randint(ks[3], (n,), 0, vocab, jnp.int32)
    y = jnp.where(jnp.arange(n) % 7 == 0, -100, y)  # MLM-style ignores

    def kernel(h, w, bvec):
        losses = fused_linear_cross_entropy(h, w, bvec, y)
        return jnp.sum(losses), losses

    def ref(h, w, bvec):
        losses = F._ce_head_fallback.raw(h, w, bvec, y, -100)
        return jnp.sum(losses), losses

    (_, loss), grads = jax.jit(jax.value_and_grad(
        kernel, argnums=(0, 1, 2), has_aux=True))(h, w, bvec)
    (_, loss_r), grads_r = jax.jit(jax.value_and_grad(
        ref, argnums=(0, 1, 2), has_aux=True))(*_f32(h, w, bvec))
    errs = {"loss": _rel_err(loss, loss_r)}
    errs.update({n_: _rel_err(g, r)
                 for n_, g, r in zip(("dh", "dw", "db"), grads, grads_r)})
    return errs


def _check_decode(sizes):
    from paddle_tpu.nn.layer.transformer import _static_cache_attention
    from paddle_tpu.ops.pallas.decode_attention import decode_attention

    b, h, L, d = sizes.decode_shape
    ks = jax.random.split(jax.random.PRNGKey(SEED + 2), 3)
    q = jax.random.normal(ks[0], (b, h, 1, d), jnp.float32).astype(DTYPE)
    kc, vc = (jax.random.normal(kk, (b, h, L, d), jnp.float32).astype(DTYPE)
              for kk in ks[1:])
    # ragged fills, from one token to the full cache
    index = jnp.asarray(np.linspace(0, L - 1, b).astype(np.int32))
    scale = d ** -0.5
    out = jax.jit(lambda q, kc, vc, i: decode_attention(q, kc, vc, i))(
        q, kc, vc, index)

    def one(q1, k1, v1, i):  # the reference takes one scalar index
        return _static_cache_attention(q1[None], k1[None], v1[None], i,
                                       scale, 0.0, False)[0]

    out_r = jax.jit(jax.vmap(one))(*_f32(q, kc, vc), index)
    return {"out": _rel_err(out, out_r)}


def _paged_operands(sizes, chunk, block_size=None, slots=None):
    """The arenas ServeLoop builds (KVBlockPool.arenas), shared by
    serve_max_active slots (or `slots`) of up to gpt.max_seq_len tokens,
    at the block
    size ServeLoop's own picker gives (or the one named): -> (q [b, h,
    chunk, d], K arena, V arena, tables, lengths as numpy, scale)."""
    from paddle_tpu.nn.kv_pool import KVBlockPool, pick_block_size
    cfg = sizes.gpt
    b, h = slots or sizes.serve_max_active, cfg.num_heads
    d = cfg.hidden_size // h
    if block_size is None:
        block_size = pick_block_size(cfg.max_seq_len, h, d,
                                     dtype=DTYPE)
    nb = cfg.max_seq_len // block_size       # block-table width
    ks = jax.random.split(jax.random.PRNGKey(SEED + 3), 3)
    shape = KVBlockPool(b * nb, block_size).arena_shape(h, d)
    ka, va = (jax.random.normal(kk, shape, jnp.float32).astype(DTYPE)
              for kk in ks[:2])
    q = jax.random.normal(ks[2], (b, h, chunk, d), jnp.float32).astype(DTYPE)
    rng = np.random.RandomState(SEED + 3)
    tables = (rng.permutation(b * nb) + 1).reshape(b, nb).astype(np.int32)
    lengths = np.linspace(0, cfg.max_seq_len - chunk, b).astype(np.int32)
    return q, ka, va, jnp.asarray(tables), lengths, d ** -0.5


def _check_paged(sizes, chunk, block_size=None):
    """The paged kernel over `_paged_operands` against
    `paged_attention_ref` in float32."""
    from paddle_tpu.nn.kv_pool import paged_attention_ref
    from paddle_tpu.ops.pallas.decode_attention import paged_decode_attention

    q, ka, va, tables, lengths, scale = _paged_operands(sizes, chunk,
                                                        block_size)
    lengths = jnp.asarray(lengths)
    out = jax.jit(lambda *a: paged_decode_attention(*a, scale))(
        q, ka, va, tables, lengths)
    out_r = jax.jit(lambda *a: paged_attention_ref(*a, scale))(
        *_f32(q, ka, va), tables, lengths)
    return {"out": _rel_err(out, out_r), "block_size": ka.shape[3]}


def _check_paged_write_attend(sizes):
    """The decode step's ONE call that writes the slots' tokens and
    attends (nn/kv_pool.paged_write_attend; PR 47) against the token
    writer twice and then the kernel, over `_paged_operands`, the first
    slots' fills at a block's last lane and its first: `out` and `arenas`
    are the share of elements whose BITS differ (the trash block left
    out), so anything but 0 fails."""
    q, ka, va, tables, lengths, scale = _paged_operands(sizes, 1)
    bs = ka.shape[3]
    edges = (bs - 1, bs, 0)[:len(lengths)]
    lengths[:len(edges)] = edges
    return _write_attend_bits(q, ka, va, tables, jnp.asarray(lengths), scale)


def _write_attend_bits(q, ka, va, tables, lengths, scale, live=slice(None)):
    """-> the share of elements whose bits differ between
    `paged_write_attend` and the pair: `out` (the slots `live` names) and
    `arenas` (the trash block left out)."""
    from paddle_tpu.core import monitor
    from paddle_tpu.nn.kv_pool import (paged_attention, paged_write_attend,
                                       write_kv)
    b, h, _, d = q.shape
    nk, nv = (jax.random.normal(kk, (b, 1, h, d), jnp.float32).astype(DTYPE)
              for kk in jax.random.split(jax.random.PRNGKey(SEED + 4)))

    def pair(ka, va):
        ka = write_kv(ka, tables, lengths, nk)
        va = write_kv(va, tables, lengths, nv)
        return paged_attention(q, ka, va, tables, lengths, scale), ka, va

    before = monitor.stat_get("pallas.hit.paged_write_attend")
    got = jax.jit(lambda ka, va: paged_write_attend(
        q, ka, va, tables, lengths, nk, nv, scale))(ka, va)
    if monitor.stat_get("pallas.hit.paged_write_attend") == before:
        raise RuntimeError("the gate left the serving shape on the pair")
    want = jax.jit(pair)(ka, va)

    def differ(x, y):
        x, y = (np.asarray(t).view(np.uint16 if t.dtype.itemsize == 2
                                   else np.uint32) for t in (x, y))
        return float(np.mean(x != y))

    return {"out": differ(got[0][live], want[0][live]),
            "arenas": max(differ(g[1:], w[1:])
                          for g, w in zip(got[1:], want[1:]))}


def _check_paged_work_list(sizes):
    """The multi-head kernel's grid ends at the live items of its work
    list (PR 48). Eight slots of `_paged_operands`: fills at a block's
    last lane and at its first, an idle slot (no token, an all-zero
    table), a full table, the rest spread. `items`: the list's live count
    as the device computes it against fill // block + 1 a slot (an idle
    one its one item); `out`: the kernel over the list against
    `paged_attention_ref` in float32, the live slots; `write_out` and
    `write_arenas`: the form that also writes the tokens against the
    pair, shares of differing bits (0 passes)."""
    from paddle_tpu.nn.kv_pool import paged_attention_ref
    from paddle_tpu.ops.pallas.decode_attention import (
        _paged_live_list, paged_decode_attention)
    b, idle = 8, 2
    q, ka, va, tables, lengths, scale = _paged_operands(sizes, 1, slots=b)
    bs, nb = ka.shape[3], tables.shape[1]
    tables = np.array(tables)
    lengths[:4] = bs - 1, bs, 0, nb * bs - 1
    tables[idle] = 0
    live = np.arange(b) != idle
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
    items = jax.jit(lambda t, n: _paged_live_list(t, n + 1, bs, b * nb)[3])(
        tables, lengths)
    out = jax.jit(lambda *a: paged_decode_attention(*a, scale))(
        q, ka, va, tables, lengths)
    out_r = jax.jit(lambda *a: paged_attention_ref(*a, scale))(
        *_f32(q, ka, va), tables, lengths)
    bits = _write_attend_bits(q, ka, va, tables, lengths, scale, live)
    want = int(np.sum(np.minimum(np.asarray(lengths) // bs, nb - 1) + 1))
    return {"items": abs(int(items[0]) - want),
            "out": _rel_err(out[live], out_r[live]),
            "write_out": bits["out"], "write_arenas": bits["arenas"]}


def _check_latent_paged(sizes):
    """The latent-attention kernel of the decode step at `sizes.latent`'s
    widths, `sizes.latent_serve`'s slots over tables of its width (the
    block rounded up to the lane tile the kernel takes): `out`, the
    kernel against `_latent_attn_paged` in float32 on the same operands,
    fills from one token to the whole table; `logits`, one decode step
    of the net's leading layer and head over the same cache, the kernel
    on against the kernel gated off."""
    import paddle_tpu as paddle
    from paddle_tpu.core import tape
    from paddle_tpu.nn.kv_pool import (KVBlockPool, _latent_attn_paged,
                                       cache_arenas, paged_caches)
    from paddle_tpu.ops.pallas.decode_attention import \
        latent_paged_decode_attention
    from paddle_tpu.text.models.kimi_k2 import KimiK2

    cfg = dataclasses.replace(sizes.latent, num_layers=1)
    slots, _, block, max_seq, _ = sizes.latent_serve
    block = -(-block // 128) * 128
    width = -(-max_seq // block)
    h, rank = cfg.num_heads, cfg.kv_lora_rank
    pool = KVBlockPool(slots * width, block)
    ks = jax.random.split(jax.random.PRNGKey(SEED + 4), 2)
    arena = jax.random.normal(
        ks[0], pool.arena_shape(1, rank + cfg.qk_rope_head_dim),
        jnp.float32).astype(DTYPE)
    q = jax.random.normal(ks[1], (slots, h, 1, arena.shape[2]),
                          jnp.float32).astype(DTYPE)
    rng = np.random.RandomState(SEED + 4)
    tables = jnp.asarray((rng.permutation(slots * width) + 1).reshape(
        slots, width).astype(np.int32))
    lengths = jnp.asarray(
        np.linspace(0, width * block - 1, slots).astype(np.int32))
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    out = jax.jit(lambda *a: latent_paged_decode_attention(
        *a, scale, rank))(q, arena, tables, lengths)
    out_r = jax.jit(lambda *a: _latent_attn_paged(
        *a, scale=scale, value_dim=rank))(*_f32(q, arena), tables, lengths)

    paddle.seed(SEED)
    net = KimiK2(cfg)
    net.eval()
    params, buffers = net.functional_state()
    spec = net.paged_cache_spec()
    tokens = jnp.asarray(rng.randint(1, cfg.vocab_size, (slots, 1)),
                         jnp.int32)

    def step(params, arenas):
        with tape.no_grad():
            net.load_functional_state(params, buffers)
            logits, caches, _ = net._forward_paged(
                tokens, paged_caches(spec, arenas, tables, lengths))
        return logits, cache_arenas(caches)

    logits = {}
    try:
        for kernel_on in (True, False):
            paddle.set_flags({"FLAGS_use_paged_attention": kernel_on})
            # the flag is read at trace time: a fresh lambda, a fresh trace
            logits[kernel_on] = jax.jit(lambda *a: step(*a))(
                params, [(arena,)])[0]
    finally:
        paddle.set_flags({"FLAGS_use_paged_attention": True})
        net.load_functional_state(params, buffers)
    return {"out": _rel_err(out, out_r),
            "logits": _rel_err(logits[True], logits[False]),
            "block_size": block}


def _paged_call_sites(serve, seed, full, ring, window, ring_block,
                      sink_init=None):
    """The grouped paged kernel at its two call sites, each against
    `paged_attention_ref` in float32 on the same operands: `full` =
    (query heads, key-value heads, key depth, value depth) over the pool's
    arenas under ragged tables, `ring` the same of a sliding layer over
    the slots' rings of `window` tokens in blocks of `ring_block`, some
    wrapped and some not, with a sink logit a head drawn N(*sink_init)
    where given. `serve` = (slots, blocks, block, max_seq)."""
    from paddle_tpu.nn.kv_pool import (KVBlockPool, _ring_arena,
                                       _ring_tables, paged_attention_ref,
                                       window_attention, window_ring_shape)
    from paddle_tpu.ops.pallas.decode_attention import paged_decode_attention

    slots, _, block, max_seq = serve
    width = -(-max_seq // block)
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    rng = np.random.RandomState(seed)

    def draw(key, shape):
        return jax.random.normal(key, shape, jnp.float32).astype(DTYPE)

    heads, kv, d, dv = full
    scale = d ** -0.5
    pool = KVBlockPool(slots * width // 4, block)
    ka, va = (draw(ks[0], pool.arena_shape(kv, d)),
              draw(ks[1], pool.arena_shape(kv, dv)))
    q = draw(ks[2], (slots, heads, 1, d))
    lengths = np.linspace(0, width * block // 4 - 1, slots).astype(np.int32)
    tables = np.zeros((slots, width), np.int32)
    free = iter(rng.permutation(ka.shape[0] - 1) + 1)
    for i, n in enumerate(lengths):          # the blocks a stream owns
        for j in range(n // block + 1):
            tables[i, j] = next(free)
    args = (jnp.asarray(tables), jnp.asarray(lengths))
    got = jax.jit(lambda *a: paged_decode_attention(*a, scale))(
        q, ka, va, *args)
    want = jax.jit(lambda *a: paged_attention_ref(*a, scale))(
        *_f32(q, ka, va), *args)
    errs = {"full": _rel_err(got, want)}

    heads, kv, d, dv = ring
    kr, vr = (draw(key, (slots,) + window_ring_shape(window, ring_block, kv,
                                                     depth))
              for key, depth in ((ks[3], d), (ks[4], dv)))
    q = draw(ks[5], (slots, heads, 1, d))
    sinks = None if sink_init is None else sink_init[0] + sink_init[1] \
        * jax.random.normal(ks[6], (heads,), jnp.float32)
    lengths = jnp.asarray(np.linspace(0, 3 * window, slots).astype(np.int32))
    got = jax.jit(lambda *a: window_attention(*a, scale, sinks))(
        q, kr, vr, lengths)
    want = jax.jit(lambda q, k, v, n: paged_attention_ref(
        q, _ring_arena(k), _ring_arena(v), _ring_tables(k),
        jnp.minimum(n, window - 1), scale, sinks))(*_f32(q, kr, vr), lengths)
    errs["ring"] = _rel_err(got, want)
    if sinks is not None:       # the same call without its sinks must differ
        bare = jax.jit(lambda *a: window_attention(*a, scale))(
            q, kr, vr, lengths)
        if _rel_err(bare, want) <= KERNEL_TOL:
            raise AssertionError("a call without its sinks agrees with the "
                                 "reference with them: the sinks do nothing")
    return errs


def _check_grouped_paged(sizes):
    """The grouped-query form of the paged kernel at `sizes.window`'s
    widths and `sizes.window_serve`'s slots (`_paged_call_sites`)."""
    cfg = sizes.window
    kv, d = cfg.num_kv_heads, cfg.head_dim
    heads = dict(zip(cfg.layer_types, cfg.num_attention_heads_per_layer))
    return _paged_call_sites(
        sizes.window_serve, SEED + 6, (heads["full_attention"], kv, d, d),
        (heads["sliding_attention"], kv, d, d), cfg.sliding_window,
        cfg.ring_block)


def _check_sink_paged(sizes):
    """Its sink / two-width form at `sizes.sink`'s widths and
    `sizes.sink_serve`'s slots: a K arena beside a shallower V arena, a
    sliding layer's one-block rings with a sink logit a head."""
    cfg = sizes.sink
    d, dv = cfg.head_dim, cfg.v_head_dim
    return _paged_call_sites(
        sizes.sink_serve, SEED + 7, (*cfg.heads("full_attention"), d, dv),
        (*cfg.heads("sliding_attention"), d, dv), cfg.sliding_window,
        cfg.ring_block, cfg.sink_init)


def _check_grouped_ffn(sizes):
    """The grouped expert kernel at the widths of `sizes.latent`'s and
    `sizes.scmoe`'s expert layers (rounded up to the lane tiles its gate
    asks for), a decode step of `*_serve`'s slots and a 256-token bucket
    each, a router's uniform draw over the whole width: the kernel's sum
    against the plain `while` form's on the same bf16 operands."""
    from paddle_tpu.nn.layer import experts

    errs = {}
    for name, cfg, serve in (("share", sizes.latent, sizes.latent_serve),
                             ("scmoe", sizes.scmoe, sizes.scmoe_serve)):
        H = -(-cfg.hidden_size // 128) * 128
        I = -(-cfg.moe_intermediate_size // 128) * 128
        first, n = cfg.experts_held
        width = cfg.num_experts + getattr(cfg, "zero_experts", 0)
        K = cfg.num_experts_per_tok
        ks = jax.random.split(jax.random.PRNGKey(SEED + 5), 4)
        gate, up, down = ((jax.random.normal(k, shape, jnp.float32) * 0.05)
                          .astype(DTYPE) for k, shape in zip(
            ks, ((n, H, I), (n, H, I), (n, I, H))))
        rng = np.random.RandomState(SEED + 5)
        for T in (serve[0], min(256, serve[4])):
            x = jax.random.normal(ks[3], (T, H), jnp.float32).astype(DTYPE)
            idx = jnp.asarray(np.stack([rng.permutation(width)[:K]
                                        for _ in range(T)]), jnp.int32)
            weights = jnp.asarray(rng.uniform(0.05, 0.5, (T, K)),
                                  jnp.float32)
            args = (x, idx, weights, jnp.ones((T,), bool), gate, up, down,
                    first)
            got, counts = experts._grouped_expert_ffn(*args)
            want, counts_r = experts._routed_expert_ffn(*args)
            if not (np.asarray(counts) == np.asarray(counts_r)).all():
                raise AssertionError(f"{name} t{T}: pairs an expert differ")
            errs[f"{name}_t{T}"] = _rel_err(got, want)
    return errs


def kernel_checks(sizes):
    """name -> thunk returning {tensor: normalized max error}."""
    checks = {
        "flash_causal": lambda: _check_flash(sizes, causal=True),
        "flash_padding_bias": lambda: _check_flash(sizes, causal=False),
        "fused_ce": lambda: _check_fused_ce(sizes),
        "decode": lambda: _check_decode(sizes),
    }
    for chunk, block in sizes.paged_checks:
        checks[f"paged_decode_s{chunk}_block{block or 'picked'}"] = \
            lambda c=chunk, b=block: _check_paged(sizes, c, b)
    checks["paged_write_attend"] = lambda: _check_paged_write_attend(sizes)
    checks["paged_work_list"] = lambda: _check_paged_work_list(sizes)
    checks["latent_paged_decode"] = lambda: _check_latent_paged(sizes)
    checks["grouped_expert_ffn"] = lambda: _check_grouped_ffn(sizes)
    checks["grouped_paged_decode"] = lambda: _check_grouped_paged(sizes)
    checks["sink_paged_decode"] = lambda: _check_sink_paged(sizes)
    return checks


def _default_blocks(sizes):
    """The static-heuristic block sizes the checks above run with: under
    jit autotune.lookup takes its default unless the table has a measured
    entry (printed per phase as autotuned_blocks)."""
    from paddle_tpu.ops.pallas import fused_ce
    from paddle_tpu.ops.pallas.flash_attention import _ceil_to, _pick_block
    s, (n, _, vocab), cache_len = (sizes.flash_shape[2], sizes.ce_shape,
                                   sizes.decode_shape[2])
    v_pad = _ceil_to(vocab, 128)
    return {"flash_bq_bk": [_pick_block(s), _pick_block(s)],
            "fused_ce_bn_bv": [fused_ce._pick(n, 512),
                               next(x for x in (512, 256, 128)
                                    if v_pad % x == 0)],
            "decode_bk": _pick_block(cache_len, 128)}


def kernels_phase(sizes):
    failures, errs = [], {}
    for name, check in kernel_checks(sizes).items():
        print(f"  kernels: START {name}", flush=True)
        try:
            got = check()
        except Exception as e:  # report every kernel, then fail
            traceback.print_exc()
            failures.append(f"{name}: {type(e).__name__}: {str(e)[:300]}")
            continue
        block_size = got.pop("block_size", None)
        errs[name] = {k: float(f"{v:.3g}") for k, v in got.items()}
        if block_size is not None:
            errs[name]["block_size"] = block_size
        # a kernel's result against its jnp form; `logits`, a model's
        # through the kernel against the same model's without
        if not all(err <= (LOGITS_TOL if key == "logits" else KERNEL_TOL)
                   for key, err in got.items()):
            failures.append(f"{name}: err {errs[name]} > {KERNEL_TOL} "
                            f"(logits {LOGITS_TOL})")
    return {"failures": failures, "errors_vs_jnp_reference": errs,
            "tolerance": KERNEL_TOL, "default_blocks": _default_blocks(sizes),
            "interpreted": jax.default_backend() != "tpu"}


# --------------------------------------------------------------------------
# multichip: the train path through fleet, sharded
# --------------------------------------------------------------------------

DROPOUT_COUNTERS = ("dropout.local_draw",
                    "dropout.local_draw_fallback.manual",
                    "dropout.local_draw_fallback.indivisible")


def dp4_dropout_fit(sizes, cfg, steps):
    """One dp=4 fit of `cfg` with BERT's published dropout 0.1 / 0.1 (the
    caller has declared the mesh). Not compared with one chip: on a `dp`
    mesh each shard draws the bits of its own rows from
    fold_in(key, dp index) (ops/norm_ops._keep_mask), so a row's mask
    depends on the mesh and the losses differ by more than rounding. What
    must hold: the draw went local at every site, one step variant, no
    late compile, finite loss. Returns (report keys, failures)."""
    from paddle_tpu.core import monitor

    def counts():
        return {k: int(monitor.stat_get(k)) for k in DROPOUT_COUNTERS}

    before = counts()
    model = _bert_model(dataclasses.replace(
        cfg, hidden_dropout_prob=0.1, attention_probs_dropout_prob=0.1),
        sizes)
    losses, late = _fit(model, sizes, steps)
    moved = {k: v - before[k] for k, v in counts().items()}
    variants = model._engine._train_fn._cache_size()
    failures = []
    if not moved["dropout.local_draw"]:
        failures.append("dp4_dropout: a dp mesh was active and no dropout "
                        f"site drew its rows locally: {moved}")
    if late or variants != 1:
        failures.append(f"dp4_dropout: {late} backend compiles after step "
                        f"1, {variants} step variants")
    if not np.isfinite(losses).all():
        failures.append(f"dp4_dropout: non-finite loss: {losses}")
    report = {"dp4_dropout_loss": round(float(losses[-1]), 4),
              "dp4_dropout_step_variants": variants}
    report.update({f"dp4_{k}": v for k, v in moved.items()})
    return report, failures


def multichip_phase(sizes):
    """dp=4 and dp=2 x tp=2 meshes under the hapi sharded step, and fleet's
    LocalSGD shard_map step, against a one-device run of the same global
    batches. Those parity runs keep dropout off: a row's dropout bits
    depend on the mesh (the hardware bit generator is not
    sharding-invariant, and since PR 44 each `dp` shard draws its own
    rows from a key folded with its `dp` index), so a sharded loss with
    dropout on is another sample, not the one-chip loss reordered. One
    more dp=4 fit runs with dropout on (`dp4_dropout_fit`) and is held
    to what does not depend on the bits."""
    from paddle_tpu import memory
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod

    devices = jax.devices()
    if len(devices) < 4:
        return {"failures": [], "skip": f"SKIP ({len(devices)} device"
                                        f"{'s' if len(devices) != 1 else ''})"}
    failures, report = [], {"loss_tol": MULTICHIP_LOSS_TOL}
    cfg = dataclasses.replace(
        sizes.bert, num_hidden_layers=sizes.multichip_layers,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    steps = sizes.multichip_steps

    try:
        mesh_mod.reset_mesh()
        ref = _fit(_bert_model(cfg, sizes), sizes, steps)[0][-1]
        report["one_chip_loss"] = round(ref, 4)

        for tag, shape in (("dp4", {"dp": 4}),
                           ("dp2_tp2", {"dp": 2, "tp": 2})):
            print(f"  multichip: START {tag}", flush=True)
            mesh_mod.reset_mesh()
            mesh_mod.init_mesh(shape)  # what fleet.init declares
            model = _bert_model(cfg, sizes)
            losses, late = _fit(model, sizes, steps)
            loss = losses[-1]
            report[f"{tag}_loss"] = round(loss, 4)
            if late or model._engine._train_fn._cache_size() != 1:
                failures.append(
                    f"{tag}: {late} backend compiles after step 1, "
                    f"{model._engine._train_fn._cache_size()} step variants")
            gap = abs(loss - ref) / abs(ref)
            if not gap <= MULTICHIP_LOSS_TOL:
                failures.append(f"{tag}: loss {loss:.4f} vs one chip "
                                f"{ref:.4f} (gap {gap:.3g})")
            params = [p._value for p in model.network.parameters()]
            spread = min(len(v.sharding.device_set) for v in params)
            if spread != 4:
                failures.append(f"{tag}: a parameter lives on {spread} "
                                "devices")
            split = sum(not v.sharding.is_fully_replicated for v in params)
            report[f"{tag}_sharded_params"] = split
            if "tp" in shape and not split:
                failures.append(f"{tag}: no parameter is tp-sharded")
            # PJRT's bytes_in_use on the chip; live-array bytes on the CPU
            in_use = [memory.memory_allocated(d) for d in devices[:4]]
            report[f"{tag}_bytes_in_use"] = in_use
            floor = sum(v.nbytes for v in params) // 8
            if not all(b > floor for b in in_use):
                failures.append(f"{tag}: a device holds under {floor} "
                                f"bytes: {in_use}")

        print("  multichip: START dp4_dropout", flush=True)
        mesh_mod.reset_mesh()
        mesh_mod.init_mesh({"dp": 4})
        drawn, failed = dp4_dropout_fit(sizes, cfg, steps)
        report.update(drawn)
        failures.extend(failed)

        # fit's shard_map path (hapi _build_localsgd_fn)
        print("  multichip: START localsgd_shard_map", flush=True)
        mesh_mod.reset_mesh()
        mesh_mod.init_mesh({"dp": 4})
        strategy = fleet.DistributedStrategy()
        strategy.localsgd = True
        strategy.localsgd_configs = {"k_steps": 2}
        # O2 through the strategy (its amp_configs default to pure bf16):
        # that is what seats f32 master weights in the wrapped optimizer
        strategy.amp = True
        loss = _fit(_bert_model(cfg, sizes, strategy), sizes, 4)[0][-1]
        report["localsgd_loss"] = round(loss, 4)
        if not np.isfinite(loss):
            failures.append("localsgd: non-finite loss")
    finally:
        mesh_mod.reset_mesh()
    report["failures"] = failures
    return report


# --------------------------------------------------------------------------

def main():
    import paddle_tpu  # noqa: F401 — places the compile cache
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"jax={jax.__version__} platform={dev.platform} "
          f"device_kind={dev.device_kind!r} device_count={device['count']}",
          flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: refusing to run on platform {dev.platform!r}: "
              "this script proves the program on a TPU and has no CPU mode "
              "(tests/test_chip_smoke.py runs the phases at toy size)",
              file=sys.stderr)
        return 2
    print(f"compile_cache_dir={jax.config.jax_compilation_cache_dir}",
          flush=True)
    sizes = Sizes.full()
    results = {name: run_phase(name, phase, sizes) for name, phase in (
        ("train", train_phase), ("serve", serve_phase),
        ("kernels", kernels_phase), ("multichip", multichip_phase))}
    ok = all(r["ok"] for r in results.values())
    if not ok:
        print("chip_smoke: FAILED phases: "
              + ", ".join(n for n, r in results.items() if not r["ok"]),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    faulthandler.enable()  # a Mosaic SIGABRT leaves a Python traceback
    sys.exit(main())
