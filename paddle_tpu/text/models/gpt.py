"""GPT (decoder-only causal LM) — ERNIE/Transformer-XL-class model-parallel
workload (BASELINE.md config 5 territory). Built from the same encoder
blocks with causal masking via the fused attention core.
"""
from __future__ import annotations

import collections
import threading
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ... import nn, ops
from ...nn import functional as F


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_seq_len: int = 1024
    dropout: float = 0.1

    @staticmethod
    def tiny():
        return GPTConfig(vocab_size=1024, hidden_size=64, num_layers=2,
                         num_heads=2, intermediate_size=128, max_seq_len=128)


class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln1 = nn.LayerNorm(cfg.hidden_size)
        self.attn = nn.MultiHeadAttention(cfg.hidden_size, cfg.num_heads,
                                          dropout=cfg.dropout)
        self.ln2 = nn.LayerNorm(cfg.hidden_size)
        self.fc1 = nn.Linear(cfg.hidden_size, cfg.intermediate_size)
        self.fc2 = nn.Linear(cfg.intermediate_size, cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, x, cache=None):
        with jax.named_scope("attn"):
            h = self.ln1(x)
            if cache is not None:
                # StaticKVCache path: positions are tracked by the cache
                # index, masking happens against the cache — no is_causal
                # needed
                a, cache = self.attn(h, cache=cache)
                x = x + a
            else:
                # is_causal (not a materialized [s,s] mask) keeps the
                # Pallas flash kernel's in-kernel triangular masking +
                # block skipping eligible
                x = x + self.attn(h, is_causal=True)
        with jax.named_scope("ffn"):
            h = self.ln2(x)
            x = x + self.drop(self.fc2(F.gelu(self.fc1(h))))
        return x if cache is None else (x, cache)


class GPT(nn.Layer):
    def __init__(self, config: GPTConfig = None):
        super().__init__()
        cfg = config or GPTConfig()
        self.config = cfg
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = nn.Embedding(cfg.max_seq_len, cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)
        self.blocks = nn.LayerList([GPTBlock(cfg)
                                    for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size)
        from .bert import _bert_init
        _bert_init(self, std=0.02)

    def __getstate__(self):
        # the decode cache holds jitted executables and a lock — neither
        # pickles; they rebuild lazily on first generate() after load
        d = dict(self.__dict__)
        d.pop("_decode_cache", None)
        d.pop("_decode_lock", None)
        return d

    def forward(self, input_ids, labels=None):
        s = input_ids.shape[1]
        pos = ops.arange(s, dtype="int64")
        x = self.drop(self.wte(input_ids) + self.wpe(pos))
        for blk in self.blocks:
            x = blk(x)
        x = self.ln_f(x)
        if labels is not None:
            # fused tied-head LM loss: no [b*s, vocab] logits in HBM
            # (ops/pallas/fused_ce.py), ignore_index=-100
            return F.fused_linear_cross_entropy(
                x, self.wte.weight, None, labels, ignore_index=-100)
        # weight-tied LM head
        return ops.matmul(x, self.wte.weight, transpose_y=True)

    def _forward_cached(self, input_ids, caches, index):
        """One cached decode/prefill pass. input_ids [b, s_new] (Tensor or
        jnp), caches: list of StaticKVCache (one per block), index: i32
        tokens already in the cache. Returns (last-position logits [b, V]
        jnp, new caches)."""
        import jax.numpy as jnp

        from ...core.tensor import Tensor

        ids = input_ids if isinstance(input_ids, Tensor) \
            else Tensor(input_ids, _internal=True)
        s = ids.shape[1]
        pos = index + jnp.arange(s, dtype=jnp.int32)
        x = self.wte(ids) + self.wpe(Tensor(pos, _internal=True))
        x = self.drop(x)
        new_caches = []
        for blk, c in zip(self.blocks, caches):
            x, c = blk(x, cache=c)
            new_caches.append(c)
        x = self.ln_f(x)
        logits = ops.matmul(x[:, -1], self.wte.weight, transpose_y=True)
        return logits._value, new_caches

    def paged_cache_spec(self):
        """What ServeLoop's pool holds for this net, one `CacheSpec` a
        layer: a `PagedKVCache` over two arenas, keys and values, per
        head."""
        from ...nn.kv_pool import CacheSpec, PagedKVCache
        cfg = self.config
        per_head = (cfg.num_heads, cfg.hidden_size // cfg.num_heads)
        return [CacheSpec(PagedKVCache, (per_head, per_head))] \
            * cfg.num_layers

    def _forward_paged(self, input_ids, caches, last_index=None):
        """One paged decode/prefill pass over the serving tier's shared
        block arena (nn/kv_pool.py). input_ids [b, s] (Tensor or jnp);
        caches: list of PagedKVCache (one per block) whose `lengths`
        field carries each slot's fill count — per-slot positions, not
        the scalar index of `_forward_cached`. `last_index` [b] (or
        None = s-1) picks the position whose logits come back: a
        bucket-padded prefill reads the logits at the REAL last prompt
        token, not the pad tail. Returns (logits [b, V] jnp, new
        caches)."""
        import jax.numpy as jnp

        from ...core.tensor import Tensor

        ids = input_ids if isinstance(input_ids, Tensor) \
            else Tensor(input_ids, _internal=True)
        s = ids.shape[1]
        lens = jnp.asarray(caches[0].lengths, jnp.int32)
        pos = lens[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        # pad rows of a bucketed prefill can run past the cap; their
        # k/v writes already land in the trash block, so the position
        # embedding only needs to stay in range
        pos = jnp.clip(pos, 0, self.config.max_seq_len - 1)
        with jax.named_scope("embed"):
            x = self.wte(ids) + self.wpe(Tensor(pos, _internal=True))
            x = self.drop(x)
        new_caches = []
        for i, (blk, c) in enumerate(zip(self.blocks, caches)):
            with jax.named_scope(f"layer{i}"):
                x, c = blk(x, cache=c)
            new_caches.append(c)
        with jax.named_scope("head"):
            x = self.ln_f(x)
            h = x._value
            if last_index is not None:
                idx = jnp.asarray(last_index, jnp.int32).reshape(-1)
                h = jnp.take_along_axis(
                    h, idx[:, None, None].astype(jnp.int32),
                    axis=1)[:, 0]
            else:
                h = h[:, -1]
            logits = ops.matmul(Tensor(h, _internal=True), self.wte.weight,
                                transpose_y=True)
        return logits._value, new_caches

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                 top_k=None, eos_token_id=None, use_cache=True, seed=0):
        """Autoregressive sampling (reference generation utils; greedy at
        temperature=0). Returns [b, s + new] ids.

        use_cache=True (default): static-shape KV-cache decode — the whole
        generation (prefill + lax.scan over steps) is ONE jitted dispatch,
        O(1) work per token and no per-token retrace; re-traced only per
        (prompt_len, max_new_tokens, sampling-config). The reference's
        incremental decoding lives in its C++ predictor
        (inference/api/analysis_predictor.cc:306); here it is a compiled
        scan over a preallocated cache (nn/layer/transformer.py
        StaticKVCache). use_cache=False keeps the simple host loop that
        re-forwards the growing prefix (the equality oracle in tests)."""
        import numpy as np

        from ...core import tape as _tape

        if use_cache:
            with _tape.no_grad():
                return self._generate_cached(
                    input_ids, int(max_new_tokens), float(temperature),
                    None if top_k is None else int(top_k),
                    eos_token_id, int(seed))

        with _tape.no_grad():
            ids = input_ids
            finished = np.zeros(int(ids.shape[0]), bool)
            for _ in range(max_new_tokens):
                logits = self(ids)[:, -1]                 # [b, V]
                if temperature == 0:
                    nxt = ops.argmax(logits, axis=-1)
                else:
                    logits = logits / float(temperature)
                    if top_k is not None:
                        kth = ops.topk(logits, top_k, axis=-1)[0][:, -1:]
                        logits = ops.where(
                            logits < kth,
                            ops.full_like(logits, -1e9), logits)
                    from ...distribution import Categorical
                    nxt = Categorical(logits=logits._value).sample()
                nxt = ops.reshape(nxt, [-1, 1]).astype("int64")
                if eos_token_id is not None:
                    keep = np.asarray(~finished)[:, None]
                    from ... import to_tensor
                    nxt = ops.where(
                        to_tensor(keep), nxt,
                        ops.full_like(nxt, eos_token_id))
                    finished |= (
                        np.asarray(nxt._value)[:, 0] == eos_token_id)
                ids = ops.concat([ids, nxt], axis=1)
                if eos_token_id is not None and finished.all():
                    break
            return ids

    def _generate_cached(self, input_ids, max_new, temperature, top_k,
                         eos_id, seed):
        import numpy as np

        from ... import to_tensor
        from ...core.tensor import Tensor

        ids = input_ids if isinstance(input_ids, Tensor) \
            else to_tensor(input_ids)
        b, s = int(ids.shape[0]), int(ids.shape[1])
        total = s + max_new
        if total > self.config.max_seq_len:
            raise ValueError(
                f"generate: prompt {s} + max_new_tokens {max_new} exceeds "
                f"max_seq_len {self.config.max_seq_len}")

        params, buffers = self.functional_state()
        cache_dtype = jnp.bfloat16 if any(
            v.dtype == jnp.bfloat16 for v in params.values()) else jnp.float32

        fn = _decode_fn(self, max_new, temperature, top_k,
                        None if eos_id is None else int(eos_id),
                        total, jnp.dtype(cache_dtype).name, b, s)
        try:
            toks = fn(params, buffers, ids._value,
                      jax.random.PRNGKey(seed))
        finally:
            # tracing mutated the layers' parameters to tracers; restore
            # the real arrays so eager use of the net keeps working
            self.load_functional_state(params, buffers)
        out = np.concatenate([np.asarray(ids._value, np.int64),
                              np.asarray(toks, np.int64)], axis=1)
        return to_tensor(out)


_DECODE_CACHE_CAP = 64


def _decode_fn(net, max_new, temperature, top_k, eos_id, total, cache_dtype,
               b, s):
    """Build + jit the whole-generation program (prefill + lax.scan decode):
    ONE compiled dispatch per generate() call, O(1) work per token. The
    LRU-capped cache lives on the instance (net -> cache -> jitted fn ->
    net is a cycle the GC collects once the model is dropped — a global
    registry would pin the model forever, since the jitted fn closes over
    it; GPT.__getstate__ excludes the cache so pickling/deepcopy still
    work). The per-instance lock is held across lookup and build: tracing
    temporarily rebinds this layer's parameters to tracers, so concurrent
    builds on one model are unsafe, while unrelated models stay parallel;
    holding it for the lookup also keeps LRU eviction race-free."""
    key = (max_new, temperature, top_k, eos_id, total, cache_dtype, b, s)
    lock = net.__dict__.setdefault("_decode_lock", threading.Lock())
    with lock:
        cache = net.__dict__.setdefault("_decode_cache",
                                        collections.OrderedDict())
        if key in cache:
            cache.move_to_end(key)
            return cache[key]
        fn = _build_decode_fn(net, max_new, temperature, top_k, eos_id,
                              total, cache_dtype, b, s)
        cache[key] = fn
        while len(cache) > _DECODE_CACHE_CAP:
            cache.popitem(last=False)
        return fn


def _build_decode_fn(net, max_new, temperature, top_k, eos_id, total,
                     cache_dtype, b, s):
    import jax
    import jax.numpy as jnp

    from ...core import tape as _tape

    dt = jnp.dtype(cache_dtype)

    def run(params, buffers, ids_j, key):
        with _tape.no_grad():
            net.load_functional_state(params, buffers)
            caches = [blk.attn.gen_static_cache(b, total, dt)
                      for blk in net.blocks]
            logits, caches = net._forward_cached(ids_j, caches, jnp.int32(0))

            def sample(logits, k):
                if temperature == 0:
                    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
                lg = (logits / temperature).astype(jnp.float32)
                if top_k is not None:
                    kth = jax.lax.top_k(lg, top_k)[0][:, -1:]
                    lg = jnp.where(lg < kth, -1e9, lg)
                return jax.random.categorical(k, lg, axis=-1).astype(
                    jnp.int32)

            def body(carry, step_key):
                caches, logits, finished, index = carry
                nxt = sample(logits, step_key)
                if eos_id is not None:
                    # finished rows are frozen to eos (their sample is
                    # discarded), and once EVERY row is finished the
                    # whole forward is skipped: the scan still runs to
                    # max_new for shape stability, but the tail steps
                    # cost one all-reduce of `finished`, not a model
                    # pass — per-request EOS at batched-decode cost
                    nxt = jnp.where(finished, jnp.int32(eos_id), nxt)
                    finished = finished | (nxt == eos_id)

                    def _run(op):
                        c, _lg, nx, ix = op
                        return net._forward_cached(nx[:, None], c, ix)

                    def _skip(op):
                        c, lg, _nx, _ix = op
                        return lg, c

                    logits, caches = jax.lax.cond(
                        jnp.all(finished), _skip, _run,
                        (caches, logits, nxt, index))
                else:
                    logits, caches = net._forward_cached(nxt[:, None],
                                                         caches, index)
                return (caches, logits, finished, index + 1), nxt

            init = (caches, logits, jnp.zeros((b,), bool), jnp.int32(s))
            keys = jax.random.split(key, max_new)
            _, toks = jax.lax.scan(body, init, keys)       # [max_new, b]
        return toks.swapaxes(0, 1)                         # [b, max_new]

    return jax.jit(run)


def export_decode(net, path, batch_size, prompt_len, max_new_tokens,
                  temperature=0.0, top_k=None, eos_token_id=None):
    """Export the WHOLE generation program (prefill + scan decode over the
    StaticKVCache) as a StableHLO artifact the inference Predictor can
    run — the deployment form of incremental decoding (reference ships
    this inside the C++ AnalysisPredictor; here it is one exported XLA
    program). Inputs: input_ids [batch, prompt_len] int32, seed []
    int32. Output: generated tokens [batch, max_new_tokens] int32.

    Parameters are baked into the artifact as constants (same convention
    as jit.save). Writes {path}.stablehlo + {path}.pdinfer.json.
    """
    import json
    import os

    import jax.export as jexport

    params, buffers = net.functional_state()
    total = prompt_len + int(max_new_tokens)
    if total > net.config.max_seq_len:
        raise ValueError("prompt_len + max_new_tokens exceeds max_seq_len")
    cache_dtype = "bfloat16" if any(
        v.dtype == jnp.bfloat16 for v in params.values()) else "float32"
    fn = _decode_fn(net, int(max_new_tokens), float(temperature),
                    None if top_k is None else int(top_k),
                    None if eos_token_id is None else int(eos_token_id),
                    total, cache_dtype, int(batch_size), int(prompt_len))

    def run(ids, seed):
        key = jax.random.PRNGKey(seed.astype(jnp.int32))
        return fn(params, buffers, ids.astype(jnp.int64), key)

    ids_spec = jax.ShapeDtypeStruct((int(batch_size), int(prompt_len)),
                                    jnp.int32)
    seed_spec = jax.ShapeDtypeStruct((), jnp.int32)
    exported = jexport.export(jax.jit(run),
                              platforms=("cpu", "tpu"))(ids_spec, seed_spec)
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path + ".stablehlo", "wb") as f:
        f.write(bytes(exported.serialize()))
    with open(path + ".pdinfer.json", "w") as f:
        json.dump({"input_names": ["input_ids", "seed"],
                   "output_names": ["tokens"],
                   "input_dtypes": ["int32", "int32"],
                   "decode": {"batch_size": int(batch_size),
                              "prompt_len": int(prompt_len),
                              "max_new_tokens": int(max_new_tokens),
                              "temperature": float(temperature),
                              "top_k": top_k,
                              "eos_token_id": eos_token_id}}, f)
    return path
