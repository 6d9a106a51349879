"""Chip-free HLO evidence for the Pallas kernel tier: are the kernels in
the lowered graphs, and what do XLA's counts and the kernels' grid
arithmetic say they save? Counts, not speed — and lowering stops before
Mosaic compiles a kernel, so this is not proof that a kernel compiles or
runs (`python chip_smoke.py` on the chip is). The same optimize-inside-
the-compiler-stack / verify-at-the-HLO posture as EQuARX
(arXiv:2506.17615):

1. AOT-lowers the graphs below for a TPU target on any dev box
   (`jax.jit(f).trace(...).lower(lowering_platforms=("tpu",))` — Mosaic
   lowering needs no TPU, only *running* does; FLAGS_pallas_force_compile
   keeps the kernels out of interpreter mode off-TPU);
2. asserts the flash-attention / fused-CE / decode custom calls are
   present in the lowered StableHLO (`kernel_name = "..."` on the
   tpu_custom_call backend config);
3. records XLA cost-analysis FLOPs/bytes for each lowered step, plus an
   analytic per-step *attention* accounting for the decode step (the
   kernel's block-skip arithmetic vs the `_sdpa` full-cache stream —
   XLA's analysis can't see inside an opaque custom call, so the
   attention-specific comparison is derived from the kernel's own grid
   math and stated as such);
4. writes HLO_EVIDENCE.json.

Graphs lowered (the shapes at which kernel presence is asserted, nothing
more; framework_lint's TOOL_CROSS_CHECKS runs self_check() on them):

- bert_train_step   — BERT-base MLM fused-CE head, b32 s128 bf16
                      (fused-CE fwd+bwd custom calls; flash gated off by
                      FLAGS_flash_min_seq at s=128, recorded as such)
- gpt_longseq_train_step — GPT-124M s4096 causal train step (flash
                      fwd+bwd custom calls — the long-context regime the
                      kernel exists for)
- gpt_decode_step   — one GPT-124M StaticKVCache decode step at
                      DECODE_CFG (decode custom call), lowered
                      twice: kernel on vs FLAGS_use_decode_attention=0
                      (_sdpa full-cache path) for the cost comparison.

Usage:
  python tools/hlo_evidence.py [--out HLO_EVIDENCE.json] [--tiny]

--tiny swaps in toy configs (same graph structure, seconds instead of
minutes) — what tests/test_hlo_evidence.py runs in tier-1.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TOOLS_DIR)
if REPO not in sys.path:  # `python tools/hlo_evidence.py` from anywhere
    sys.path.insert(0, REPO)

# ---- canonical configs (self_check() runs the kernel gates at them) -------
BERT_CFG = {"batch": 32, "seq": 128, "dtype": "bfloat16"}
DECODE_CFG = {"batch": 8, "prompt": 32, "new": 128, "max_seq_len": 1024}
LONGSEQ_CFG = {"batch": 1, "seq": 4096}
# train-mode pipeline scan-megastep config (self_check(): FLAGS_executor_*)
PIPELINE_CFG = {"batch": 256, "hidden": 64, "steps": 200, "scan_k": 8,
                "inflight": 2}
TINY_PIPELINE_CFG = {"batch": 8, "hidden": 4, "steps": 8, "scan_k": 4,
                     "inflight": 2}

TINY_BERT_CFG = {"batch": 2, "seq": 16, "dtype": "float32"}
TINY_DECODE_CFG = {"batch": 2, "prompt": 4, "new": 8, "max_seq_len": 64}
TINY_LONGSEQ_CFG = {"batch": 1, "seq": 128}

# serving-tier fused decode step (inference/serving.py over the paged
# KV pool): slots/blocks mirror the FLAGS_serve_* defaults
# (serve_load_test.self_check pins the two)
SERVE_CFG = {"slots": 64, "blocks": 512, "block_size": 128,
             "max_seq_len": 1024, "prompt": 32, "new": 64}
TINY_SERVE_CFG = {"slots": 2, "blocks": 6, "block_size": 16,
                  "max_seq_len": 64, "prompt": 4, "new": 8}

# kernel function names as they appear in `kernel_name = "..."` in the
# TPU-lowered StableHLO custom calls
KERNEL_NAMES = {
    "flash_attention": ["_flash_fwd_kernel", "_flash_bwd_dq_kernel",
                        "_flash_bwd_dkv_kernel"],
    "fused_ce": ["_ce_fwd_kernel", "_ce_bwd_dh_kernel",
                 "_ce_bwd_dw_kernel"],
    "decode_attention": ["_decode_attn_kernel"],
    "paged_decode_attention": ["_paged_decode_attn_kernel"],
}

_KERNEL_RE = re.compile(r'kernel_name = "([^"]+)"')


def _lower_tpu(fn, *args):
    import jax
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))


def _with_big_stack(thunk, stack_bytes=512 * 1024 * 1024):
    """Run thunk on a thread with a large stack: Mosaic kernel lowering
    recurses inside the already-deep train-step trace, exhausting both
    the 1000-frame Python limit and (if only the limit is raised) the
    default 8 MB C stack — a 20000-frame limit on the main thread
    segfaults instead of raising."""
    import threading
    result = {}

    def target():
        try:
            result["value"] = thunk()
        except BaseException as e:  # re-raised on the caller thread
            result["error"] = e

    old = threading.stack_size(stack_bytes)
    try:
        t = threading.Thread(target=target)
        t.start()
        t.join()
    finally:
        threading.stack_size(old)
    if "error" in result:
        raise result["error"]
    return result["value"]


def _evidence_from_lowered(lowered):
    text = lowered.as_text()
    calls = {}
    for name in _KERNEL_RE.findall(text):
        calls[name] = calls.get(name, 0) + 1
    try:
        ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        cost = {"flops": float(ca.get("flops", -1)),
                "bytes_accessed": float(ca.get("bytes accessed", -1))}
    except Exception as e:  # cost analysis is evidence, not a gate
        cost = {"error": f"{type(e).__name__}: {e}"}
    return calls, cost


def _pallas_counters():
    from paddle_tpu.core import monitor
    return {k: int(v) for k, v in monitor.stats("pallas.").items()}


def _reset_counters():
    from paddle_tpu.core import monitor
    monitor.reset(prefix="pallas.")


# --------------------------------------------------------------------------
# graph builders
# --------------------------------------------------------------------------

def lower_bert_train(cfg):
    """A BERT MLM train step (fused-CE head), lowered for TPU."""
    from paddle_tpu.text.models.bert import Bert, BertConfig

    net = Bert(BertConfig.bert_base() if cfg["seq"] >= 128
               else BertConfig.tiny())
    return _lower_train_step(net, "masked_lm_labels", cfg,
                             cfg["dtype"] == "bfloat16")


def lower_gpt_longseq_train(cfg):
    """A long-sequence GPT train step (flash attention + fused-CE head)."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models.gpt import GPT, GPTConfig

    seq = cfg["seq"]
    gcfg = GPTConfig(max_seq_len=seq, dropout=0.0) if seq >= 1024 else \
        GPTConfig(vocab_size=1024, hidden_size=64, num_layers=2,
                  num_heads=2, intermediate_size=128, max_seq_len=seq,
                  dropout=0.0)
    paddle.seed(0)
    return _lower_train_step(GPT(gcfg), "labels", cfg, True)


def _lower_train_step(net, labels_kw, cfg, bf16):
    """AdamW train step on `net(ids, **{labels_kw: labels})`, lowered for
    TPU; `bf16`: bf16 params over f32 master weights (the O2 recipe)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import optimizer as opt_mod
    from paddle_tpu.core import rng as _rng
    from paddle_tpu.core import tape as _tape
    from paddle_tpu.core.tensor import Tensor

    seq, batch = cfg["seq"], cfg["batch"]
    net.train()
    optimizer = opt_mod.AdamW(learning_rate=1e-4,
                              parameters=net.parameters(),
                              multi_precision=bf16)
    params, buffers = net.functional_state()
    if bf16:
        params = {k: v.astype(jnp.bfloat16) if v.dtype == jnp.float32 else v
                  for k, v in params.items()}
    named = dict(net.named_parameters())
    optimizer._ensure_slots(params)
    slots = dict(optimizer._slots)
    meta = optimizer._param_meta(named)

    def train_step(params, slots, ids, labels, lr, t, key):
        with _rng.rng_state(key), _tape.no_grad():
            def loss_of(p):
                net.load_functional_state(p, buffers)
                loss = net(Tensor(ids, _internal=True),
                           **{labels_kw: Tensor(labels, _internal=True)})
                return loss._value.mean().astype(jnp.float32)

            loss, grads = jax.value_and_grad(loss_of)(params)
            new_params, new_slots = optimizer.apply_gradients_pure(
                params, grads, slots, lr, t, param_meta=meta)
        return loss, new_params, new_slots

    ids = jnp.zeros((batch, seq), jnp.int32)
    labels = jnp.zeros((batch, seq), jnp.int32)
    lr = jnp.asarray(1e-4, jnp.float32)
    t = jnp.asarray(1, jnp.int32)
    key = jax.random.PRNGKey(0)
    try:
        return _lower_tpu(train_step, params, slots, ids, labels, lr, t,
                          key)
    finally:
        net.load_functional_state(params, buffers)


def lower_gpt_decode_step(cfg, use_kernel):
    """ONE incremental decode step (s=1 against the StaticKVCache) at the
    DECODE_CFG shape — the body the generation scan repeats `new`
    times. Lowered with the decode kernel on or forced to the jnp _sdpa
    full-cache path."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core import tape as _tape
    from paddle_tpu.text.models.gpt import GPT, GPTConfig

    b, total = cfg["batch"], cfg["max_seq_len"]
    gcfg = GPTConfig(max_seq_len=total) if total >= 1024 else \
        GPTConfig(vocab_size=1024, hidden_size=64, num_layers=2,
                  num_heads=2, intermediate_size=128, max_seq_len=total)
    gcfg.dropout = 0.0
    paddle.seed(0)
    net = GPT(gcfg)
    net.eval()
    params, buffers = net.functional_state()
    caches = [blk.attn.gen_static_cache(b, total, jnp.float32)
              for blk in net.blocks]

    def decode_step(params, buffers, tok, caches, index):
        with _tape.no_grad():
            net.load_functional_state(params, buffers)
            logits, new_caches = net._forward_cached(tok, caches, index)
        return logits, new_caches

    tok = jnp.zeros((b, 1), jnp.int32)
    index = jnp.int32(cfg["prompt"])
    paddle.set_flags({"FLAGS_use_decode_attention": bool(use_kernel)})
    try:
        return _lower_tpu(decode_step, params, buffers, tok, caches, index)
    finally:
        paddle.set_flags({"FLAGS_use_decode_attention": True})
        net.load_functional_state(params, buffers)


def lower_serve_decode_step(cfg, use_kernel=True):
    """ONE fused continuous-batching decode step (inference/serving.py):
    every active slot advances one token against the shared paged KV
    arena through the block-table kernel. Lowers the PRODUCTION step
    builder (serving.build_decode_step), so the evidence cannot drift
    from the serve loop. Arenas/tables are passed as ShapeDtypeStructs —
    lowering needs avals, not the multi-GB buffers."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import build_decode_step
    from paddle_tpu.nn.kv_pool import KVBlockPool
    from paddle_tpu.text.models.gpt import GPT, GPTConfig

    A, bs = cfg["slots"], cfg["block_size"]
    total = cfg["max_seq_len"]
    nb = cfg["blocks"]
    mb = -(-total // bs)
    gcfg = GPTConfig(max_seq_len=total) if total >= 1024 else \
        GPTConfig(vocab_size=1024, hidden_size=64, num_layers=2,
                  num_heads=2, intermediate_size=128, max_seq_len=total)
    gcfg.dropout = 0.0
    paddle.seed(0)
    net = GPT(gcfg)
    net.eval()
    params, buffers = net.functional_state()
    heads = gcfg.num_heads
    hd = gcfg.hidden_size // heads
    arenas = jax.eval_shape(lambda: KVBlockPool(nb, bs).arenas(
        gcfg.num_layers, heads, hd, jnp.float32))
    bt = jax.ShapeDtypeStruct((A, mb), jnp.int32)
    lens = jax.ShapeDtypeStruct((A,), jnp.int32)
    toks = jax.ShapeDtypeStruct((A,), jnp.int32)
    keys = jax.ShapeDtypeStruct((A, 2), jnp.uint32)
    step = build_decode_step(net, temperature=0.0, top_k=None)
    paddle.set_flags({"FLAGS_use_paged_attention": bool(use_kernel)})
    try:
        return _lower_tpu(step, params, buffers, arenas, bt, lens, toks,
                          keys)
    finally:
        paddle.set_flags({"FLAGS_use_paged_attention": True})
        net.load_functional_state(params, buffers)


def serve_decode_bytes_model(cfg, heads, head_dim, layers,
                             dtype_bytes=4):
    """Per-step attention KV-read accounting for the PAGED kernel: the
    clamped block-table index map DMAs ceil(live/bs) physical blocks per
    slot, so per-step KV bytes are a function of each request's LIVE
    length — the full-cache jnp path (and a StaticKVCache sized to
    max_seq_len) streams max_seq_len columns per slot regardless. Stated
    at several fill levels to show the scaling law, plus the reduction
    at the serve config's typical fill (prompt + new/2)."""
    A, bs, L = cfg["slots"], cfg["block_size"], cfg["max_seq_len"]
    nb_req = -(-L // bs)

    def kv_bytes(cols):
        return 2.0 * A * heads * cols * head_dim * dtype_bytes * layers

    fills = sorted({1, max(nb_req // 4, 1), max(nb_req // 2, 1), nb_req})
    scaling = [{"live_blocks": n, "live_cols": n * bs,
                "kv_bytes_per_step": kv_bytes(n * bs)} for n in fills]
    typical = min(cfg["prompt"] + cfg["new"] // 2, L)
    typ_cols = min(-(-typical // bs), nb_req) * bs
    return {
        "model": "per-step KV reads: paged kernel = ceil(live/bs)*bs "
                 "cols per slot (clamped block-table index map skips "
                 "dead-block DMA); full-cache path = max_seq_len cols "
                 "per slot at any fill",
        "block_size": bs,
        "slots": A,
        "bytes_by_live_blocks": scaling,
        "full_cache_bytes_per_step": kv_bytes(L),
        "typical_fill_tokens": typical,
        "typical_live_cols": typ_cols,
        "typical_kv_bytes_per_step": kv_bytes(typ_cols),
        "bytes_reduction_x_at_typical_fill":
            round(kv_bytes(L) / kv_bytes(typ_cols), 2),
    }


def lower_pipeline_scan(cfg):
    """The scan-fused K-step executor megastep
    (static/pipeline_runner.py): lax.scan over the compiled train step.
    Returns (lowered, info) where info proves the fusion at the jaxpr
    level — ONE scan primitive of length K, i.e. one dispatched
    computation where the serial loop dispatches K."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import nn, ops, optimizer, static
    from paddle_tpu.core import rng as _rng

    batch, hidden, k = cfg["batch"], cfg["hidden"], cfg["scan_k"]
    paddle.enable_static()
    try:
        paddle.seed(0)
        prog = static.Program("hlo_pipeline")
        with static.program_guard(prog):
            x = static.data("x", [-1, hidden], "float32")
            y = static.data("y", [-1, 1], "float32")
            h = ops.relu(nn.Linear(hidden, hidden)(x))
            loss = ops.mse_loss(nn.Linear(hidden, 1)(h), y)
            optimizer.Adam(learning_rate=1e-3).minimize(loss)
        exe = static.Executor()
        feed = {"x": jnp.zeros((batch, hidden), jnp.float32),
                "y": jnp.zeros((batch, 1), jnp.float32)}
        entry = exe._prepare(prog, feed, [loss], False)
        # the PRODUCTION scan body, not a copy — evidence can't drift
        from paddle_tpu.static.executor import make_scan_step
        scan_fn = make_scan_step(entry.step_fn)

        scope = static.global_scope()
        scope_vals = {n: scope.get(n) for n in entry.read_names}
        entry.opt._ensure_slots(
            {n: scope_vals[n] for n in entry.opt_pnames})
        slots = {n: entry.opt._slots[n] for n in entry.opt_pnames}
        feeds = tuple(jnp.zeros((k,) + tuple(feed[n].shape), jnp.float32)
                      for n in entry.feed_names)
        lrs = jnp.full((k,), 1e-3, jnp.float32)
        ts = jnp.arange(1, k + 1, dtype=jnp.int32)
        keys = jnp.stack([_rng.next_key() for _ in range(k)])

        jaxpr = jax.make_jaxpr(scan_fn)(feeds, scope_vals, slots, lrs,
                                        ts, keys)
        scan_eqns = [e for e in jaxpr.jaxpr.eqns
                     if e.primitive.name == "scan"]
        info = {
            "scan_eqns": len(scan_eqns),
            "scan_length": int(scan_eqns[0].params["length"])
            if scan_eqns else 0,
            "k": k,
        }
        lowered = _lower_tpu(scan_fn, feeds, scope_vals, slots, lrs, ts,
                             keys)
        info["while_ops"] = lowered.as_text().count("stablehlo.while")
        return lowered, info
    finally:
        paddle.disable_static()


# --------------------------------------------------------------------------
# analytic decode-attention accounting
# --------------------------------------------------------------------------

def decode_attention_model(cfg, heads, head_dim, layers, bk,
                           dtype_bytes=4):
    """Per-step attention FLOPs/HBM-bytes, averaged over the `new`
    generated tokens: the _sdpa path streams all max_seq_len padded K/V
    columns every step; the kernel reads ceil(live/bk) blocks (clamped
    index map skips dead-block DMA) and computes only those columns.
    FLOPs are per live query row (both paths pad the single decode row to
    the 8-sublane tile in hardware); bytes count the K+V cache reads that
    dominate decode HBM traffic."""
    L, prompt, new = cfg["max_seq_len"], cfg["prompt"], cfg["new"]
    b = cfg["batch"]
    nk = -(-L // bk)

    def per_step(cols):
        return {
            "flops": 4.0 * b * heads * cols * head_dim * layers,
            "hbm_bytes": 2.0 * b * heads * cols * head_dim * dtype_bytes
                         * layers,
        }

    kern_cols = [min(-(-(prompt + i + 1) // bk), nk) * bk
                 for i in range(new)]
    avg_cols = sum(kern_cols) / max(len(kern_cols), 1)
    sdpa = per_step(L)
    kern = per_step(avg_cols)
    return {
        "model": "attention cols per decode step: sdpa=max_seq_len; "
                 "kernel=ceil((prompt+i+1)/bk)*bk averaged over i<new; "
                 "flops=4*b*h*cols*d per layer (QK^T + PV), "
                 "hbm_bytes=K+V cache reads",
        "block_k": bk,
        "avg_live_cols_kernel": round(avg_cols, 1),
        "sdpa_full_cache": sdpa,
        "decode_kernel": kern,
        "flops_reduction_x": round(sdpa["flops"] / kern["flops"], 2),
        "bytes_reduction_x": round(sdpa["hbm_bytes"] / kern["hbm_bytes"],
                                   2),
    }


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

def run(out_path="HLO_EVIDENCE.json", tiny=False):
    import paddle_tpu as paddle
    from paddle_tpu.core import flags as _flags

    # Mosaic kernel lowering runs nested inside the (already deep)
    # train-step trace stack; the default 1000-frame limit exhausts there
    if sys.getrecursionlimit() < 20000:
        sys.setrecursionlimit(20000)

    bert_cfg = TINY_BERT_CFG if tiny else BERT_CFG
    decode_cfg = TINY_DECODE_CFG if tiny else DECODE_CFG
    longseq_cfg = TINY_LONGSEQ_CFG if tiny else LONGSEQ_CFG

    saved = {k: _flags.flag(k) for k in
             ("FLAGS_pallas_force_compile", "FLAGS_pallas_autotune",
              "FLAGS_use_flash_attention", "FLAGS_use_fused_ce",
              "FLAGS_use_decode_attention", "FLAGS_flash_min_seq")}
    paddle.set_flags({
        "FLAGS_pallas_force_compile": True,   # Mosaic lowering off-TPU
        "FLAGS_pallas_autotune": False,       # lowering must not measure
        "FLAGS_use_flash_attention": True,
        "FLAGS_use_fused_ce": True,
        "FLAGS_use_decode_attention": True,
    })
    if tiny:
        paddle.set_flags({"FLAGS_flash_min_seq": 64})

    report = {"tool": "tools/hlo_evidence.py", "tiny": bool(tiny),
              "platform": "tpu", "graphs": {}, "assertions": []}

    def record(name, lowered, config, extra=None):
        calls, cost = _evidence_from_lowered(lowered)
        entry = {"config": config, "custom_calls": calls,
                 "cost_analysis": cost,
                 "pallas_counters": _pallas_counters()}
        entry.update(extra or {})
        report["graphs"][name] = entry
        return entry

    def check(name, ok, detail=""):
        report["assertions"].append(
            {"name": name, "ok": bool(ok), "detail": detail})

    try:
        # ---- BERT train step (fused CE) -------------------------------
        _reset_counters()
        bert = record("bert_train_step",
                      _with_big_stack(lambda: lower_bert_train(bert_cfg)),
                      bert_cfg)
        for kn in KERNEL_NAMES["fused_ce"]:
            check(f"bert_train_step has {kn}",
                  bert["custom_calls"].get(kn, 0) > 0)

        # ---- GPT long-seq train step (flash attention) ----------------
        _reset_counters()
        ls = record("gpt_longseq_train_step",
                    _with_big_stack(
                        lambda: lower_gpt_longseq_train(longseq_cfg)),
                    longseq_cfg)
        for kn in KERNEL_NAMES["flash_attention"]:
            check(f"gpt_longseq_train_step has {kn}",
                  ls["custom_calls"].get(kn, 0) > 0)

        # ---- GPT decode step: kernel vs _sdpa full cache --------------
        _reset_counters()
        dec = record("gpt_decode_step",
                     _with_big_stack(lambda: lower_gpt_decode_step(
                         decode_cfg, use_kernel=True)),
                     decode_cfg)
        kn = KERNEL_NAMES["decode_attention"][0]
        check(f"gpt_decode_step has {kn}",
              dec["custom_calls"].get(kn, 0) > 0)

        _reset_counters()
        sdpa_lowered = _with_big_stack(
            lambda: lower_gpt_decode_step(decode_cfg, use_kernel=False))
        sdpa_calls, sdpa_cost = _evidence_from_lowered(sdpa_lowered)
        dec["sdpa_custom_calls"] = sdpa_calls
        dec["sdpa_cost_analysis"] = sdpa_cost
        check("sdpa decode graph has no decode kernel",
              sdpa_calls.get(kn, 0) == 0)

        heads = 12 if not tiny else 2
        head_dim = 64 if not tiny else 32
        layers = 12 if not tiny else 2
        from paddle_tpu.core import flags as _f
        bk = int(_f.flag("FLAGS_decode_block_k") or 0) or \
            min(128, decode_cfg["max_seq_len"])
        dec["attention_per_step"] = decode_attention_model(
            decode_cfg, heads, head_dim, layers, bk)
        # the >=2x acceptance bar is about the DEFAULT bench config; its
        # model is pure arithmetic, so evaluate it even in --tiny (a
        # 64-slot tiny cache is a single block — no reduction to show)
        full = dec["attention_per_step"] if not tiny else \
            decode_attention_model(
                DECODE_CFG, 12, 64, 12,
                int(_f.flag("FLAGS_decode_block_k") or 0)
                or min(128, DECODE_CFG["max_seq_len"]))
        if tiny:
            dec["attention_per_step_full_config"] = full
        check("decode attention flops reduced >= 2x (default bench cfg)",
              full["flops_reduction_x"] >= 2.0,
              f"{full['flops_reduction_x']}x")
        check("decode attention bytes reduced >= 2x (default bench cfg)",
              full["bytes_reduction_x"] >= 2.0,
              f"{full['bytes_reduction_x']}x")

        # ---- serving: fused continuous-batching paged decode step -----
        scfg = TINY_SERVE_CFG if tiny else SERVE_CFG
        _reset_counters()
        srv = record("serve_decode",
                     _with_big_stack(
                         lambda: lower_serve_decode_step(scfg)),
                     scfg)
        pkn = KERNEL_NAMES["paged_decode_attention"][0]
        check(f"serve_decode has {pkn}",
              srv["custom_calls"].get(pkn, 0) > 0)
        s_heads = 12 if not tiny else 2
        s_hd = 64 if not tiny else 32
        s_layers = 12 if not tiny else 2
        srv["kv_bytes_per_step"] = serve_decode_bytes_model(
            scfg, s_heads, s_hd, s_layers)
        # the scaling bar is about the DEFAULT serve config; its model is
        # pure arithmetic, so evaluate it even in --tiny
        full_srv = srv["kv_bytes_per_step"] if not tiny else \
            serve_decode_bytes_model(SERVE_CFG, 12, 64, 12)
        if tiny:
            srv["kv_bytes_per_step_full_config"] = full_srv
        sc = full_srv["bytes_by_live_blocks"]
        linear = all(
            abs(e["kv_bytes_per_step"]
                - sc[0]["kv_bytes_per_step"] * e["live_blocks"]) < 1e-6
            for e in sc)
        check("serve decode per-step KV bytes scale with live blocks "
              "(default serve cfg)", linear,
              f"{[e['live_blocks'] for e in sc]} blocks -> "
              f"{[e['kv_bytes_per_step'] for e in sc]} bytes")
        check("serve decode KV bytes reduced >= 2x vs max_seq_len at "
              "typical fill (default serve cfg)",
              full_srv["bytes_reduction_x_at_typical_fill"] >= 2.0,
              f"{full_srv['bytes_reduction_x_at_typical_fill']}x")

        # ---- scan-fused executor megastep (async pipelined hot loop) --
        _reset_counters()  # the serve lowering's hits are not this graph's
        pcfg = TINY_PIPELINE_CFG if tiny else PIPELINE_CFG
        lowered, info = _with_big_stack(
            lambda: lower_pipeline_scan(pcfg))
        pipe = record("pipeline_scan_megastep", lowered, pcfg)
        pipe["scan"] = info
        # the serial loop dispatches K XLA executions per K steps; the
        # scan-fused megastep dispatches ONE (the scan body runs as K
        # iterations of a single compiled loop) — the dispatch model is
        # arithmetic, so state the DEFAULT bench config's number even in
        # --tiny
        k_full = PIPELINE_CFG["scan_k"]
        pipe["dispatch_model"] = {
            "model": "host dispatches per K train steps: serial "
                     "Executor.run = K; scan-fused megastep = 1 "
                     "(lax.scan compiles the step into one while loop)",
            "serial_dispatches_per_k": k_full,
            "scan_dispatches_per_k": 1,
            "dispatch_reduction_x": float(k_full),
        }
        check("scan-fused K-step lowers to ONE scan of K iterations",
              info["scan_eqns"] == 1
              and info["scan_length"] == pcfg["scan_k"],
              f"{info['scan_eqns']} scan eqn(s), length "
              f"{info['scan_length']} (want {pcfg['scan_k']})")
        check("scan-fused megastep lowers to a single fused loop "
              "computation", info["while_ops"] >= 1,
              f"{info['while_ops']} while op(s)")
        check("dispatches per K steps reduced >= 2x (default bench cfg)",
              k_full >= 2, f"{k_full}x")

        # ---- two-tier topology: hierarchical dp gradient sync ---------
        # analytic wire model (SpmdReport.hierarchical_sync over the
        # spmd_plan topology golden: outer 'pod' axis on the slow DCN
        # tier, inner 'dp' on ICI). Pure ring arithmetic over the planned
        # layout's gradient bytes — no lowering involved, so the DEFAULT
        # golden prices even in --tiny.
        if TOOLS_DIR not in sys.path:
            sys.path.insert(0, TOOLS_DIR)
        import importlib
        spmd_plan = importlib.import_module("spmd_plan")
        tplan, _, _ = spmd_plan.build_topology_plan()
        gs = dict(tplan.grad_sync or {})
        gs["model"] = (
            "per-device ring all-reduce of B grad bytes over s devices "
            "moves 2*B*(s-1)/s; flat crosses DCN with the full B while "
            "hierarchical reduce-scatters intra-pod first and ships only "
            "the B/n shard inter-pod (localsgd divides the whole sync "
            "by k steps); cost_us = bytes / (link_gbps * 1e3)")
        report["graphs"]["hierarchical_sync"] = {
            "config": {
                "mesh": {ax: ({"size": n, **tplan.mesh_tiers[ax]}
                              if ax in tplan.mesh_tiers else n)
                         for ax, n in tplan.mesh_axes.items()},
                "workload": "spmd_plan topology golden GPT "
                            "(build_topology_plan defaults)",
            },
            "wire_model": gs,
        }
        n_xtier = sum(d.code == "cross-tier"
                      for d in tplan.report.diagnostics)
        check("topology-planned golden keeps model parallelism "
              "intra-pod (zero cross-tier diagnostics)",
              n_xtier == 0 and not tplan.report.diagnostics,
              f"{len(tplan.report.diagnostics)} diagnostic(s), "
              f"{n_xtier} cross-tier")
        check("hierarchical dp sync cuts inter-pod wire bytes >= 2x "
              "vs flat", gs.get("inter_pod_reduction_x", 0.0) >= 2.0,
              f"{gs.get('inter_pod_reduction_x')}x, recommendation="
              f"{gs.get('recommendation')}")
    finally:
        paddle.set_flags({k: v for k, v in saved.items()})

    report["ok"] = all(a["ok"] for a in report["assertions"])
    # sections other tools own ride through a regeneration: the capacity
    # validation record (tools/capacity_plan.py --validate) is gated by
    # check_perf_floors, so dropping it here would fail the build
    try:
        with open(out_path) as f:
            prior = json.load(f)
        for key in ("capacity_validation",):
            if key in prior.get("graphs", {}):
                report["graphs"].setdefault(key, prior["graphs"][key])
    except (OSError, ValueError):
        pass
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    return report


# --------------------------------------------------------------------------
# framework_lint cross-check (TOOL_CROSS_CHECKS)
# --------------------------------------------------------------------------

def self_check():
    """Fast flag + gate lint (no lowering): PIPELINE_CFG must be the
    pipeline users get by default, and the kernel eligibility gates must
    pass for every canonical shape — otherwise the 'evidence' would be
    for graphs in which no kernel engages."""
    problems = []
    # flag DECLARED defaults (not live values — a test may have set them)
    from paddle_tpu.core.flags import _DEFS
    for flag, want in (
            ("FLAGS_executor_max_inflight", PIPELINE_CFG["inflight"]),
            ("FLAGS_executor_scan_steps", 0)):  # scan fusion is opt-in
        if int(_DEFS[flag][1]) != want:
            problems.append(f"hlo_evidence: {flag} default "
                            f"{_DEFS[flag][1]} != {want} (PIPELINE_CFG)")
    if PIPELINE_CFG["scan_k"] < 2:
        problems.append("hlo_evidence: scan_k must be >= 2 — the '>=2x "
                        "fewer dispatches per K steps' bar is vacuous")

    # eligibility gates for the canonical shapes (pure static predicates).
    # importlib by dotted path: the package __init__ shadows the
    # decode_attention/flash_attention module names with the functions
    try:
        import importlib
        fc = importlib.import_module("paddle_tpu.ops.pallas.fused_ce")
        fa = importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention")
        da = importlib.import_module(
            "paddle_tpu.ops.pallas.decode_attention")
    except Exception as e:
        return problems + [f"hlo_evidence: kernel imports failed: {e!r}"]

    n_tok = BERT_CFG["batch"] * BERT_CFG["seq"]
    if not fc.supported(n_tok, 768, 30522):
        problems.append("hlo_evidence: fused_ce gate rejects the BERT "
                        f"bench shape (n={n_tok}, H=768, V=30522)")
    s = LONGSEQ_CFG["seq"]
    if not fa.supported((LONGSEQ_CFG["batch"], 12, s, 64),
                        (LONGSEQ_CFG["batch"], 12, s, 64),
                        (LONGSEQ_CFG["batch"], 12, s, 64)):
        problems.append("hlo_evidence: flash gate rejects the longseq "
                        f"bench shape (s={s})")
    b, L = DECODE_CFG["batch"], DECODE_CFG["max_seq_len"]
    if not da.supported((b, 12, 1, 64), (b, 12, L, 64)):
        problems.append("hlo_evidence: decode gate rejects the decode "
                        f"bench shape (b={b}, L={L})")
    sA, sbs, snb = SERVE_CFG["slots"], SERVE_CFG["block_size"], \
        SERVE_CFG["blocks"]
    from paddle_tpu.nn.kv_pool import KVBlockPool
    if not da.paged_supported((sA, 12, 1, 64),
                              KVBlockPool(snb, sbs).arena_shape(12, 64)):
        problems.append("hlo_evidence: paged-decode gate rejects the "
                        f"serve config (slots={sA}, bs={sbs})")
    n_tok_gpt = LONGSEQ_CFG["batch"] * s
    if not fc.supported(n_tok_gpt, 768, 50304):
        problems.append("hlo_evidence: fused_ce gate rejects the GPT "
                        f"longseq loss shape (n={n_tok_gpt})")
    return problems


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--out", default=os.path.join(REPO, "HLO_EVIDENCE.json"))
    p.add_argument("--tiny", action="store_true",
                   help="toy configs (fast; used by tier-1 tests)")
    p.add_argument("--self-check", action="store_true",
                   help="config-drift lint only (what framework_lint runs)")
    args = p.parse_args(argv)
    if args.self_check:
        problems = self_check()
        for prob in problems:
            print(prob)
        print("hlo_evidence self-check:",
              "clean" if not problems else f"{len(problems)} problem(s)")
        return 1 if problems else 0
    report = run(args.out, tiny=args.tiny)
    for a in report["assertions"]:
        print(("PASS " if a["ok"] else "FAIL ") + a["name"]
              + (f" ({a['detail']})" if a["detail"] else ""))
    print(f"wrote {args.out}")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
