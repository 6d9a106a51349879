"""Continuous-batching decode serving — the online inference tier.

`GPT.generate` is one-model-one-call: a fixed batch prefills together,
decodes together, and every row that finishes early (or never existed)
still burns a slot until the longest row is done. This module turns the
same kernel-fast decode path into a SERVER:

- **paged KV pool** (nn/kv_pool.py): all in-flight requests share one
  physical block arena per layer; per-request block tables make ragged
  lengths free and retiring requests return their blocks to the pool
  immediately;
- **prefill/decode split with admission scheduling**: new requests are
  admitted when a slot AND enough pool blocks are free, prefilled as a
  bucketed single-request pass (logits read at the real last prompt
  token), then join the ONE fused decode batch that advances every
  active stream one token per step through the block-table Pallas
  kernel (per-step KV reads scale with live blocks, not max_seq_len);
- **async pipeline**: decode steps dispatch through the PR 5
  `InflightDriver` (static/pipeline_runner.py), so dispatch of step N+1
  overlaps sampling/detokenization-side bookkeeping of step N; failures
  surface as `PipelineStepError` naming the step;
- **backpressure + preemption**: when the pool is exhausted, admissions
  queue; when an ACTIVE stream cannot grow into a new block, the
  youngest active stream is evicted (blocks freed, request re-queued
  with its generated prefix — greedy/fold-in sampling makes the replay
  deterministic) so the oldest stream always completes.

Per-request sampling keys fold `PRNGKey(seed)` with the absolute token
position, so a stream's tokens do not depend on which batch it rides in
or whether it was preempted. Greedy (temperature=0) continuous-batched
decode is token-identical to per-request sequential `GPT.generate`
(tests/test_serving.py proves it bitwise).

Two seams close the serve→train→serve loop (docs/online_learning.md):

- **completion records**: every request that finishes cleanly emits a
  structured record (id, prompt/generated ids, pinned snapshot version,
  ttft/per-token timings) through the `on_complete` hook at retire —
  the input contract of `dataset/streaming.StreamingDataset`. A hook
  error is counted (`serve.completion_log_errors`) and swallowed; a
  logging bug never fails serving.
- **zero-downtime hot-swap**: `publish_weights(version, updates)`
  stages a versioned weight swap; the scheduler applies it between
  decode beats once every in-flight stream has retired. While a swap
  is staged admission pauses — queued requests WAIT (nothing is ever
  dropped) and each in-flight stream finishes on the version pinned at
  its first admission.

Observability: one `serve/tick` span per scheduler beat (attributes
`beat`, `active`, `queued`) whose children are the beat's phases —
`serve/settle` (its child `serve/settle_wait` is the host blocked on the
device; the rest of `serve/settle` is token bookkeeping), `serve/admit`
> `serve/prefill`, `serve/grow`, `serve/upload` (this beat's lengths,
block tables and keys built and sent to the device), `serve/decode_step`
(the dispatch) — plus `serve/wait_work` while the scheduler thread has
nothing to do, and `serve/{retire,evict,hot_swap}`; request spans carry
`req=<rid>` and a per-request flow chain. Every span is also on the
timeline of any running `jax.profiler` session (core/trace.py). Stamps
`ServeRequest.{t_submit,t_admit,t_first,t_tokens,t_done}`. `stats()`
counts, cumulative: `steps`, `decode_tokens`, `prefill_dispatches`,
`prefill_tokens`, `prefill_rows` (the buckets dispatched),
`prefill_live_rows` (the rows computed: whole tiles of a net that cuts
its buckets into tiles, `net.prefill_tile`, else the bucket),
`admitted`, `queue_wait_s`, and whatever the served net
names (`net.serve_counters`: the `moe_*` counts of a net with expert
layers, the `linear_*` counts of one with recurrent layers, the `attn_*`
counts and the `window_ring_bytes` gauge of one with sliding-window
layers; a name in the net's `SERVE_GAUGES` is set, the others are added
up), all mirrored
as `serve.*` gauges beside `serve.{queue_depth,active_slots,
kv_pool_used_blocks,kv_pool_free_blocks,model_version,state_slots_used,
state_bytes,steps,paged_live_step_share}` (`state_*`: decode slots whose
per-slot state is owned, and the bytes of all of it; 0 for a net that
caches by token only; `paged_live_step_share`: the live (slot, block)
pairs over slots x the table's width, the share of a table-wide grid
that held a block, `_paged_live_step_share`).
Counters `serve.{preempted,
tokens_generated,requests_completed,requests_errored,hot_swaps,
completion_log_errors}`, histograms `serve/ttft_ms` and
`serve/token_ms` — rendered by tools/obs_report.py's serving section
and by `benchmark/`'s serve drivers.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from dataclasses import dataclass

import numpy as np

__all__ = ["ServeConfig", "ServeRequest", "ServeLoop",
           "build_decode_step"]

# The loop's own gauges. What a served net counts of its layers is the
# net's to name (its `SERVE_STATS`, added up by its `serve_counters`;
# text/models/kimi_k2.py: `MOE_STATS`, text/models/olmo_hybrid.py:
# `LINEAR_STATS`): each name is a key of `stats()` and a `serve.<name>`
# gauge.
GAUGES = ("serve.queue_depth", "serve.active_slots",
          "serve.kv_pool_used_blocks", "serve.kv_pool_free_blocks",
          "serve.model_version", "serve.decode_tokens",
          "serve.prefill_dispatches", "serve.prefill_tokens",
          "serve.prefill_rows", "serve.prefill_live_rows", "serve.admitted",
          "serve.queue_wait_s", "serve.state_slots_used", "serve.state_bytes",
          "serve.steps", "serve.paged_live_step_share")
COUNTERS = ("serve.preempted", "serve.tokens_generated",
            "serve.requests_completed", "serve.requests_errored",
            "serve.hot_swaps", "serve.completion_log_errors",
            "serve.backpressure_waits")

_REQ_IDS = itertools.count()


@dataclass
class ServeConfig:
    """Knobs for one ServeLoop. Zeros mean "take the FLAGS_serve_*
    default" (core/flags.py) so a deployment can be tuned per-job via
    env without touching code."""

    max_active: int = 0     # decode slots (FLAGS_serve_max_active)
    kv_blocks: int = 0      # pool blocks (FLAGS_serve_kv_blocks)
    block_size: int = 0     # tokens/block (FLAGS_serve_block_size / auto)
    max_seq_len: int = 0    # per-request cap (0 = model max_seq_len)
    temperature: float = 0.0
    top_k: int = None
    eos_token_id: int = None   # default; per-request override wins
    max_inflight: int = 0      # decode pipeline depth (0 = executor flag)

    def resolve(self, net, dtype):
        """`dtype` is the arena dtype: the auto block size is measured
        (and keyed) with it."""
        from ..core import flags as _flags
        cfg = net.config
        # the widest arena of what the net caches BY TOKEN sizes the
        # block; state indexed by slot (`CacheSpec.slots`) has no blocks
        heads, dim = max((a for layer in net.paged_cache_spec()
                          for a in layer.arenas),
                         key=lambda a: a[0] * a[1], default=(1, 1))
        max_active = int(self.max_active
                         or _flags.flag("FLAGS_serve_max_active"))
        kv_blocks = int(self.kv_blocks
                        or _flags.flag("FLAGS_serve_kv_blocks"))
        max_seq = int(self.max_seq_len or cfg.max_seq_len)
        max_seq = min(max_seq, cfg.max_seq_len)
        if self.block_size:
            block_size = int(self.block_size)
        else:
            from ..nn.kv_pool import pick_block_size
            block_size = pick_block_size(max_seq, heads, dim, dtype=dtype)
        max_inflight = int(self.max_inflight
                           or _flags.flag("FLAGS_executor_max_inflight"))
        return max_active, kv_blocks, block_size, max_seq, \
            max(1, max_inflight)


class ServeRequest:
    """One generate stream. Clients hold this as a future: `result()`
    blocks until the stream finishes (or raises its error)."""

    def __init__(self, prompt, max_new_tokens, eos_token_id, seed):
        self.rid = next(_REQ_IDS)
        self.prompt = np.asarray(prompt, np.int64).reshape(-1)
        if self.prompt.size < 1:
            raise ValueError("empty prompt")
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self.eos_token_id = eos_token_id
        self.seed = int(seed)
        self.out = []            # generated token ids (host ints)
        self.error = None
        self.preemptions = 0
        self.snapshot_version = None  # model version pinned at 1st admit
        self.t_submit = time.perf_counter()
        self.t_admit = None      # left the queue for a slot (first time)
        self.t_first = None      # first generated token materialized
        self.t_tokens = []       # one stamp per entry of `out`
        self.t_done = None
        self._done = threading.Event()

    # -- future API ---------------------------------------------------------
    @property
    def done(self):
        return self._done.is_set()

    def wait(self, timeout=None):
        if not self._done.wait(timeout):
            raise TimeoutError(f"request {self.rid} still in flight")
        return self

    def result(self, timeout=None):
        """Generated tokens [n] (prompt excluded). Raises the request's
        error if serving failed it."""
        self.wait(timeout)
        if self.error is not None:
            raise self.error
        return np.asarray(self.out, np.int64)

    # -- latency metrics ----------------------------------------------------
    @property
    def ttft_s(self):
        return None if self.t_first is None else self.t_first - self.t_submit

    @property
    def per_token_s(self):
        if self.t_done is None or self.t_first is None or len(self.out) < 2:
            return None
        return (self.t_done - self.t_first) / (len(self.out) - 1)

    # -- completion record ---------------------------------------------------
    def completion_record(self):
        """Structured retire-time record — the StreamingDataset input
        contract (docs/online_learning.md). Host ints/floats only, so
        records serialize/queue without holding device buffers."""
        return {
            "rid": int(self.rid),
            "prompt": [int(t) for t in self.prompt.tolist()],
            "tokens": [int(t) for t in self.out],
            "version": self.snapshot_version,
            "preemptions": int(self.preemptions),
            "t_submit": self.t_submit,
            "t_admit": self.t_admit,
            "t_first": self.t_first,
            "t_tokens": list(self.t_tokens),
            "t_done": self.t_done,
            "ttft_s": self.ttft_s,
            "per_token_s": self.per_token_s,
        }


def _sampler(temperature, top_k):
    """Per-row sampler: greedy at temperature=0, else categorical keyed
    by fold_in(request_key, absolute token position) — batch-composition
    independent and preemption-replay stable."""
    import jax
    import jax.numpy as jnp

    if temperature == 0:
        def greedy(logits, keys, positions):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return greedy

    def sample(logits, keys, positions):
        def one(lg, key, pos):
            k = jax.random.fold_in(key, pos)
            lg = lg.astype(jnp.float32) / temperature
            if top_k is not None:
                kth = jax.lax.top_k(lg, int(top_k))[0][-1]
                lg = jnp.where(lg < kth, -1e9, lg)
            return jax.random.categorical(k, lg).astype(jnp.int32)
        return jax.vmap(one)(logits, keys, positions)
    return sample


def build_decode_step(net, temperature=0.0, top_k=None):
    """The UN-jitted fused decode step: every active stream advances one
    token. (params, buffers, arenas, block_tables, lengths, tokens,
    keys) -> (new_arenas, next_tokens), then whatever the net's
    `_forward_paged` returns past its caches (a net with expert layers:
    the pairs each held expert got). `arenas` is what
    `KVBlockPool.arenas_for(net.paged_cache_spec())` lays out: each
    layer gets its own tuple. Exposed at module level so
    tools/hlo_evidence.py can AOT-lower the PRODUCTION step — the
    evidence cannot drift from the loop."""
    import jax
    import jax.numpy as jnp

    from ..core import tape as _tape
    from ..nn.kv_pool import cache_arenas, paged_caches

    samp = _sampler(temperature, top_k)
    spec = net.paged_cache_spec()

    def decode_step(params, buffers, arenas, block_tables, lengths,
                    tokens, keys):
        with _tape.no_grad():
            net.load_functional_state(params, buffers)
            logits, new_caches, *counted = net._forward_paged(
                tokens[:, None], paged_caches(spec, arenas, block_tables,
                                              lengths))
            with jax.named_scope("sample"):
                nxt = samp(logits, keys, lengths + jnp.int32(1))
        return (cache_arenas(new_caches), nxt, *counted)

    return decode_step


def _build_prefill(net, temperature, top_k):
    """The UN-jitted bucketed prefill: one request's (padded) prompt
    writes what each layer caches into the pool blocks and samples the
    first token, which is also spliced into the fused batch's token
    carry at `slot`. (params, buffers, arenas, tokens, bt_row, ids,
    real_len, key, slot) -> ((new_arenas, new_tokens), first_token),
    then what `_forward_paged` counted, as in `build_decode_step`."""
    import jax
    import jax.numpy as jnp

    from ..core import tape as _tape
    from ..nn.kv_pool import (cache_arenas, fresh_slot_rows, paged_caches,
                              put_slot_rows)

    samp = _sampler(temperature, top_k)
    spec = net.paged_cache_spec()

    def prefill(params, buffers, arenas, tokens, bt_row, ids, real_len,
                key, slot):
        with _tape.no_grad():
            net.load_functional_state(params, buffers)
            # state indexed by slot: the net sees one zeroed row (what the
            # slot's last owner left must not leak into this request) and
            # what it leaves, the state as of `real_len`, becomes row `slot`
            caches = paged_caches(spec, fresh_slot_rows(spec, arenas),
                                  bt_row, jnp.zeros((1,), jnp.int32))
            logits, new_caches, *counted = net._forward_paged(
                ids, caches, last_index=jnp.reshape(real_len, (1,)) - 1)
            with jax.named_scope("sample"):
                first = samp(logits, key[None], jnp.reshape(real_len,
                                                            (1,)))[0]
                tokens = tokens.at[slot].set(first)
            arenas = put_slot_rows(spec, arenas, cache_arenas(new_caches),
                                   slot)
        return ((arenas, tokens), first, *counted)

    return prefill


class _Slot:
    __slots__ = ("req", "length", "blocks", "version", "admit_seq",
                 "key")

    def __init__(self, req, blocks, version, admit_seq, key):
        self.req = req
        self.length = 0          # tokens written into the cache
        self.blocks = blocks     # physical block ids (pool-owned)
        self.version = version
        self.admit_seq = admit_seq
        self.key = key           # raw uint32[2] PRNGKey data


class ServeLoop:
    """Continuous-batching server over one (eval-mode) GPT-style model.

    Batch use:  `ServeLoop(net).serve(prompts)` drives the caller thread.
    Server use: `start()` spawns the scheduler thread; any number of
    client threads `submit(...).result()`. `stop()` drains and joins.
    """

    def __init__(self, net, config=None, on_complete=None, **overrides):
        import jax
        import jax.numpy as jnp

        from ..core import flags as _flags  # noqa: F401 (resolve below)
        from ..nn.kv_pool import KVBlockPool
        from ..static.pipeline_runner import _FLOW_NS

        self.net = net
        self.config = config or ServeConfig(**overrides)
        if overrides and config is not None:
            raise ValueError("pass either a ServeConfig or kwargs")
        self._params, self._buffers = net.functional_state()
        self._dtype = jnp.bfloat16 if any(
            v.dtype == jnp.bfloat16 for v in self._params.values()) \
            else jnp.float32
        (self._A, n_blocks, self._bs, self._cap,
         self._max_inflight) = self.config.resolve(net, self._dtype)
        if net.training:
            net.eval()  # decode kernels are eval-only; serving never drops
        self._pool = KVBlockPool(n_blocks, self._bs)
        self._MB = -(-self._cap // self._bs)     # block-table width
        self._fresh_device_state()
        self._flow_base = next(_FLOW_NS) << 42  # per-request flow chain

        step = build_decode_step(net, self.config.temperature,
                                 self.config.top_k)
        # donate the big arenas only: the [A] token carry is ALSO step
        # N's fetch, and donating it into step N+1 would delete the
        # buffer out from under the in-flight FetchHandle
        self._step_jit = jax.jit(step, donate_argnums=(2,))
        pf = _build_prefill(net, self.config.temperature,
                            self.config.top_k)
        self._prefill_jit = jax.jit(pf, donate_argnums=(2,))
        self._traced = set()   # (kind, bucket) keys already traced

        self._slots = [None] * self._A
        self._queue: deque = deque()
        self._pending: deque = deque()  # settle entries, driver order
        self._on_complete = on_complete  # completion-record hook
        self.model_version = 0           # published weight version
        self._staged_swap = None         # (version, {name: np rows})
        self._version = 0
        self._admit_seq = 0
        self._step_count = 0
        # cumulative counts of the scheduler's work, read through stats()
        self._decode_tokens = 0       # tokens appended from decode beats
        self._prefill_dispatches = 0  # re-prefill after preemption too
        self._prefill_tokens = 0      # prompt tokens sent to prefill
        self._prefill_rows = 0        # rows dispatched: the buckets' sizes
        self._prefill_live_rows = 0   # rows computed: the tiles with a token
        self._admitted = 0            # first admissions
        self._queue_wait_s = 0.0      # sum of t_admit - t_submit
        # what the net's layers count (`serve_counters`), by the names
        # the net declares (`SERVE_STATS`); a net that counts nothing: {}
        self._net_counts = dict.fromkeys(getattr(net, "SERVE_STATS", ()), 0)
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._thread = None
        self._stopping = False

    # -- public API ----------------------------------------------------------
    def submit(self, prompt, max_new_tokens=32, eos_token_id=None,
               seed=0):
        """Enqueue one generate stream; returns its ServeRequest
        future. Thread-safe."""
        eos = self.config.eos_token_id if eos_token_id is None \
            else eos_token_id
        req = ServeRequest(prompt, max_new_tokens, eos, seed)
        total = req.prompt.size + req.max_new_tokens
        if total > self._cap:
            raise ValueError(
                f"request needs {total} tokens > serving cap {self._cap}")
        if self._pool.blocks_for(total) > self._pool.n_blocks:
            raise ValueError(
                f"request needs {self._pool.blocks_for(total)} blocks > "
                f"pool size {self._pool.n_blocks}")
        with self._work:
            self._queue.append(req)
            self._work.notify_all()
        return req

    def serve(self, prompts, **kw):
        """Batch convenience: submit every prompt, drive the scheduler
        on the caller thread until idle, return the generated-token
        arrays in order."""
        if self._thread is not None:
            raise RuntimeError("serve() on a started loop; use submit()")
        reqs = [self.submit(p, **kw) for p in prompts]
        self.run_until_idle()
        return [r.result(timeout=0) for r in reqs]

    def run_until_idle(self):
        """Drive scheduler ticks on the caller thread until no queued,
        active, or in-flight work remains."""
        while self._has_work():
            self._tick()
        self._drain()

    def start(self):
        """Background-server mode: scheduler runs on its own thread."""
        if self._thread is not None:
            return self
        self._stopping = False
        self._thread = threading.Thread(target=self._serve_forever,
                                        daemon=True, name="serve-loop")
        self._thread.start()
        return self

    def stop(self, timeout=30):
        """Finish in-flight + queued work, then stop the thread. Raises
        on timeout instead of orphaning the scheduler — clearing
        `_thread` while it still runs would let a later start() race a
        second scheduler over the (single-owner) pool and slots."""
        t = self._thread
        if t is None:
            return
        with self._work:
            self._stopping = True
            self._work.notify_all()
        t.join(timeout)
        if t.is_alive():
            raise TimeoutError(
                f"serve loop did not drain within {timeout}s "
                f"({self.stats()})")
        self._thread = None

    def stats(self):
        return {
            "queue_depth": len(self._queue),
            "active_slots": sum(s is not None for s in self._slots),
            "kv_pool_used_blocks": self._pool.used_blocks,
            "kv_pool_free_blocks": self._pool.free_blocks,
            "steps": self._step_count,
            "decode_tokens": self._decode_tokens,
            "prefill_dispatches": self._prefill_dispatches,
            "prefill_tokens": self._prefill_tokens,
            "prefill_rows": self._prefill_rows,
            "prefill_live_rows": self._prefill_live_rows,
            "admitted": self._admitted,
            "queue_wait_s": self._queue_wait_s,
            "block_size": self._bs,
            "max_active": self._A,
            "model_version": self.model_version,
            "swap_staged": self._staged_swap is not None,
            "state_slots_used": self._state_slots_used(),
            "state_bytes": self._state_bytes,
            "paged_live_step_share": self._paged_live_step_share(),
            **self._net_counts,
        }

    def publish_weights(self, version, updates):
        """Stage a versioned weight hot-swap: `updates` maps functional-
        state param names (see `net.functional_state()`) to replacement
        arrays. Validated (name + shape) on the caller thread; APPLIED
        by the scheduler between decode beats once every in-flight
        stream has retired. While a swap is staged, admission pauses —
        queued requests wait (the pool never drops a request) and each
        in-flight stream finishes on the version pinned at its first
        admission. Staging a second swap before the first applies
        replaces it (last publish wins). Thread-safe."""
        staged = {}
        for name, arr in dict(updates).items():
            if name not in self._params:
                raise KeyError(f"unknown param {name!r} "
                               f"(not in functional_state)")
            arr = np.asarray(arr)
            want = tuple(self._params[name].shape)
            if tuple(arr.shape) != want:
                raise ValueError(f"shape {tuple(arr.shape)} for "
                                 f"{name!r} != served {want}")
            staged[name] = arr
        with self._work:
            self._staged_swap = (int(version), staged)
            self._work.notify_all()
        return self

    # -- scheduler ----------------------------------------------------------
    def _has_work(self):
        return bool(self._queue or self._pending
                    or self._staged_swap is not None
                    or any(s is not None for s in self._slots))

    def _serve_forever(self):
        from ..core import trace as _trace
        while True:
            with self._work:
                if not self._has_work() and not self._stopping:
                    # an idle device under this span is "no request"
                    with _trace.span("serve/wait_work"):
                        while not self._has_work() and not self._stopping:
                            self._work.wait(timeout=0.05)
                if self._stopping and not self._has_work():
                    return
            self._tick()

    def _tick(self):
        """One scheduler beat: settle enough of the pipeline to bound
        the window, admit, grow/preempt, dispatch the next fused decode
        step (N+1 overlapping the settle of step N). Every phase is a
        child span of the beat's `serve/tick`."""
        from ..core import trace as _trace
        with _trace.span("serve/tick", beat=self._step_count,
                         active=sum(s is not None for s in self._slots),
                         queued=len(self._queue)):
            self._tick_phases()

    def _tick_phases(self):
        # testing/faults.py ("serve", "beat") boundary: a scripted STALL
        # here models a hung scheduler beat (the latency fault the SLO
        # drill scripts a TTFT breach against). Transport-shaped chaos
        # (RESET/DROP) has no meaning at a scheduler beat and is
        # absorbed — the streaming deliver boundary does the same.
        try:
            from ..distributed.ps.rpc import _fault
            _fault("serve", "beat", "tick")
        except ConnectionError:
            pass
        while len(self._pending) >= self._max_inflight:
            self._settle_one()
        if self._staged_swap is not None:
            # drain barrier: no admission while a swap is staged —
            # active streams run to retirement on the pinned version,
            # then the swap applies and admission resumes
            if any(s is not None for s in self._slots):
                self._grow_or_preempt()
                self._dispatch_decode()
            elif self._pending:
                self._settle_one()
            else:
                self._apply_swap()
            self._publish_gauges()
            return
        self._admit()
        if any(s is not None for s in self._slots):
            self._grow_or_preempt()
            self._dispatch_decode()
        elif self._pending:
            self._settle_one()
        self._publish_gauges()

    def _drain(self):
        while self._pending:
            self._settle_one()
        self._publish_gauges()

    def _apply_swap(self):
        """The hot-swap itself, between beats with nothing in flight:
        rebind the published params in the functional state. No arena /
        block state is touched — the KV pool is version-agnostic (only
        FUTURE prefills/decodes read the new weights, and the drain
        barrier guarantees there are no other kind)."""
        import jax.numpy as jnp

        from ..core import monitor as _monitor
        from ..core import trace as _trace
        version, updates = self._staged_swap
        self._staged_swap = None
        with _trace.span("serve/hot_swap", version=version,
                         params=len(updates)):
            for name, arr in updates.items():
                self._params[name] = jnp.asarray(
                    arr, self._params[name].dtype)
            self.net.load_functional_state(self._params, self._buffers)
            self.model_version = int(version)
            _monitor.stat_add("serve.hot_swaps")

    # -- admission / prefill -------------------------------------------------
    def _free_slot(self):
        for i, s in enumerate(self._slots):
            if s is None:
                return i
        return None

    def _admit(self):
        from ..core import monitor
        from ..core import trace as _trace
        while True:
            with self._lock:
                req = self._queue[0] if self._queue else None
            if req is None:
                return
            idx = self._free_slot()
            if idx is None:
                monitor.stat_add("serve.backpressure_waits")
                return
            prompt = np.concatenate(
                [req.prompt, np.asarray(req.out, np.int64)]) \
                if req.out else req.prompt
            remaining = req.max_new_tokens - len(req.out)
            need_total = self._pool.blocks_for(prompt.size + remaining)
            # BACKPRESSURE: the head of the queue waits (FCFS — no
            # starvation of long requests) until retiring streams free
            # enough blocks for its whole worst case
            if not self._pool.can_alloc(need_total):
                monitor.stat_add("serve.backpressure_waits")
                return
            with self._lock:
                self._queue.popleft()
            blocks = self._pool.alloc(self._pool.blocks_for(prompt.size))
            with _trace.span("serve/admit", req=req.rid, slot=idx,
                             prompt_len=int(prompt.size),
                             blocks=len(blocks)) as sp:
                sp.flow(self._flow_base + req.rid, "s")
                import jax
                if req.t_admit is None:
                    req.t_admit = time.perf_counter()
                    self._admitted += 1
                    self._queue_wait_s += req.t_admit - req.t_submit
                if req.snapshot_version is None:
                    req.snapshot_version = self.model_version
                self._version += 1
                self._admit_seq += 1
                key = np.asarray(jax.random.PRNGKey(req.seed),
                                 np.uint32)
                slot = _Slot(req, blocks, self._version,
                             self._admit_seq, key)
                self._slots[idx] = slot
                self._dispatch_prefill(idx, slot, prompt)

    def _bucket(self, n):
        b = 8
        while b < n:
            b *= 2
        return b

    def _call_traced(self, fn, key, *args):
        """Call a jitted fn; after its FIRST trace (which rebinds the
        live layers' parameters to tracers) restore the real arrays so
        eager use of the net keeps working (same contract as
        GPT._generate_cached)."""
        if key in self._traced:
            return fn(*args)
        from ..core import program_map
        try:
            # the program's map of instruction -> scope, for whoever reads
            # a device trace: shapes before the call (the arenas are
            # donated), the executable the call made looked up after it
            arg_shapes = program_map.shapes(args)
            out = fn(*args)
            program_map.note("serve/" + "/".join(map(str, key)), fn,
                             arg_shapes)
            return out
        finally:
            self.net.load_functional_state(self._params, self._buffers)
            self._traced.add(key)

    def _dispatch_prefill(self, idx, slot, prompt):
        import jax.numpy as jnp

        from ..core import trace as _trace
        req = slot.req
        s_real = int(prompt.size)
        bucket = self._bucket(s_real)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :s_real] = prompt
        bt_row = np.zeros((1, self._MB), np.int32)
        bt_row[0, :len(slot.blocks)] = slot.blocks
        with _trace.span("serve/prefill", req=req.rid, slot=idx,
                         prompt_len=s_real, bucket=bucket) as sp:
            sp.flow(self._flow_base + req.rid, "t")

            def thunk():
                carry, first, *counted = self._call_traced(
                    self._prefill_jit, ("prefill", bucket),
                    self._params, self._buffers, self._arenas,
                    self._tokens, jnp.asarray(bt_row), jnp.asarray(ids),
                    jnp.int32(s_real), jnp.asarray(slot.key),
                    jnp.int32(idx))
                return carry, [first, *counted]

            carry, handles = self._driver.submit(thunk, kind="prefill",
                                                 req=req.rid)
        if carry is not None:
            self._arenas, self._tokens = carry
        self._prefill_dispatches += 1
        self._prefill_tokens += s_real
        self._prefill_rows += bucket
        # what the program works through row by row: a net that cuts this
        # bucket into tiles (`prefill_tile`) computes those with a token
        tile = getattr(self.net, "prefill_tile", lambda bucket: None)(bucket)
        self._prefill_live_rows += -(-s_real // tile) * tile if tile \
            else bucket
        slot.length = s_real
        self._pending.append(("prefill", handles, req, idx,
                              slot.version, s_real))

    # -- growth / preemption -------------------------------------------------
    def _youngest_active(self):
        best = None
        for i, s in enumerate(self._slots):
            if s is not None and (best is None
                                  or s.admit_seq
                                  > self._slots[best].admit_seq):
                best = i
        return best

    def _grow_or_preempt(self):
        """Every active slot writes its next token at position `length`
        this step; make sure the covering block exists, evicting the
        youngest stream when the pool is dry (oldest always wins)."""
        from ..core import trace as _trace
        with _trace.span("serve/grow"):
            order = sorted((i for i, s in enumerate(self._slots)
                            if s is not None),
                           key=lambda i: self._slots[i].admit_seq)
            for idx in order:
                slot = self._slots[idx]
                if slot is None:      # evicted by an earlier iteration
                    continue
                need_blk = slot.length // self._bs
                while need_blk >= len(slot.blocks):
                    got = self._pool.alloc(1)
                    if got is not None:
                        slot.blocks.extend(got)
                        continue
                    victim = self._youngest_active()
                    self._preempt(victim)
                    if victim == idx:
                        break         # preempted ourselves; slot is gone

    def _preempt(self, idx):
        from ..core import monitor as _monitor
        from ..core import trace as _trace
        slot = self._slots[idx]
        req = slot.req
        with _trace.span("serve/evict", req=req.rid, slot=idx,
                         generated=len(req.out),
                         blocks=len(slot.blocks)) as sp:
            sp.flow(self._flow_base + req.rid, "t")
            self._pool.free(slot.blocks)
            self._slots[idx] = None
            req.preemptions += 1
            _monitor.stat_add("serve.preempted")
            with self._lock:
                # back to the head: it is older than everything queued,
                # and its re-prefill (prompt + generated prefix) replays
                # the same token stream
                self._queue.appendleft(req)

    # -- decode dispatch -----------------------------------------------------
    def _dispatch_decode(self):
        import jax.numpy as jnp

        from ..core import trace as _trace
        A, MB = self._A, self._MB
        # this beat's host state, rebuilt and re-sent every beat: the
        # per-beat upload has its own span so its cost has its own number
        with _trace.span("serve/upload"):
            lengths = np.zeros((A,), np.int32)
            bt = np.zeros((A, MB), np.int32)
            keys = np.zeros((A, 2), np.uint32)
            snapshot = []
            for i, s in enumerate(self._slots):
                if s is None:
                    continue
                lengths[i] = s.length
                bt[i, :len(s.blocks)] = s.blocks
                keys[i] = s.key
                snapshot.append((i, s.req, s.version))
            bt_d, lengths_d, keys_d = (jnp.asarray(bt), jnp.asarray(lengths),
                                       jnp.asarray(keys))
        step_idx = self._step_count
        self._step_count += 1
        with _trace.span("serve/decode_step", step=step_idx,
                         active=len(snapshot)):

            def thunk():
                arenas, nxt, *counted = self._call_traced(
                    self._step_jit, ("decode",),
                    self._params, self._buffers, self._arenas,
                    bt_d, lengths_d, self._tokens, keys_d)
                return (arenas, nxt), [nxt, *counted]

            carry, handles = self._driver.submit(thunk, kind="decode",
                                                 active=len(snapshot))
        if carry is not None:
            self._arenas, self._tokens = carry
        for i, _req, _ver in snapshot:
            self._slots[i].length += 1
        self._pending.append(("decode", handles, snapshot))

    # -- settlement / retirement --------------------------------------------
    def _settle_one(self):
        """Materialise the oldest in-flight step and book its tokens.
        `serve/settle_wait` is the host blocked on the device; what is
        left of `serve/settle` is host work (appends, retirements)."""
        from ..core import trace as _trace
        from ..static.pipeline_runner import PipelineStepError
        entry = self._pending.popleft()
        with _trace.span("serve/settle", kind=entry[0]):
            try:
                with _trace.span("serve/settle_wait", kind=entry[0]):
                    toks = np.asarray(entry[1][0])
            except PipelineStepError as exc:
                self._fail_inflight(exc)
                return
            now = time.perf_counter()
            if entry[0] == "prefill":
                _kind, handles, req, idx, version, n_tokens = entry
                self._count_served("prefill", handles, n_tokens)
                slot = self._slots[idx]
                if slot is None or slot.version != version:
                    return           # preempted before its first token
                self._append_token(idx, slot, int(toks), now, first=True)
                return
            _kind, handles, snapshot = entry
            self._count_served("decode", handles, len(snapshot))
            for idx, req, version in snapshot:
                slot = self._slots[idx]
                if slot is None or slot.version != version \
                        or slot.req is not req:
                    continue             # retired/preempted mid-flight
                self._decode_tokens += 1
                self._append_token(idx, slot, int(toks[idx]), now)

    def _count_served(self, kind, handles, n_tokens):
        """Add up what a settled step's program returned past its tokens
        (what the net's layers counted), under the names the net gives
        them (`serve_counters`); read here, where the tokens have just
        been read, so the device is not waited for again."""
        if len(handles) < 2:
            return                       # a net that counts nothing
        counts = dict(self._net_counts)
        gauges = getattr(self.net, "SERVE_GAUGES", ())  # set, not added up
        for name, n in self.net.serve_counters(kind, handles[1:],
                                               n_tokens).items():
            counts[name] = n if name in gauges else counts.get(name, 0) + n
        self._net_counts = counts        # swapped whole: stats() may read

    def _append_token(self, idx, slot, token, now, first=False):
        from ..core import monitor as _monitor
        req = slot.req
        if first and req.t_first is None and not req.out:
            req.t_first = now
        req.out.append(token)
        req.t_tokens.append(now)
        _monitor.stat_add("serve.tokens_generated")
        if (req.eos_token_id is not None and token == req.eos_token_id) \
                or len(req.out) >= req.max_new_tokens:
            self._retire(idx, slot)

    def _retire(self, idx, slot):
        """Finished stream: free its blocks IMMEDIATELY (they are the
        admission currency for whoever is queued) and complete the
        future. In-flight steps that still carry this slot are ignored
        at settle via the slot version."""
        from ..core import monitor as _monitor
        from ..core import trace as _trace
        req = slot.req
        with _trace.span("serve/retire", req=req.rid, slot=idx,
                         generated=len(req.out),
                         blocks=len(slot.blocks)) as sp:
            sp.flow(self._flow_base + req.rid, "f")
            self._pool.free(slot.blocks)
            self._slots[idx] = None
            req.t_done = time.perf_counter()
            _monitor.stat_add("serve.requests_completed")
            if req.ttft_s is not None:
                _monitor.observe("serve/ttft_ms", req.ttft_s * 1e3)
            if req.per_token_s is not None:
                _monitor.observe("serve/token_ms", req.per_token_s * 1e3)
            if self._on_complete is not None:
                # the record is emitted BEFORE the future resolves, so
                # a client that saw result() knows its record was
                # offered; a hook error never fails serving
                try:
                    self._on_complete(req.completion_record())
                except Exception:
                    _monitor.stat_add("serve.completion_log_errors")
            req._done.set()

    def _fail_inflight(self, exc):
        """A decode/prefill step died (XLA-level, past run_guarded): the
        donated device chain is poisoned. Fail every in-flight stream,
        rebuild the device state, keep serving the queue."""
        from ..core import monitor as _monitor
        self._pending.clear()
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            slot.req.error = exc
            slot.req.t_done = time.perf_counter()
            slot.req._done.set()
            self._pool.free(slot.blocks)
            self._slots[i] = None
            _monitor.stat_add("serve.requests_errored")
        self._fresh_device_state()

    def _fresh_device_state(self):
        """Zeroed arenas of what the net says it caches, the token carry
        and a driver with nothing in flight."""
        import jax.numpy as jnp

        from ..static.pipeline_runner import InflightDriver
        spec = self.net.paged_cache_spec()
        self._arenas = self._pool.arenas_for(spec, self._dtype,
                                             slots=self._A)
        self._state_bytes = sum(
            x.nbytes for layer, a in zip(spec, self._arenas)
            for x in a[len(layer.arenas):])
        self._tokens = jnp.zeros((self._A,), jnp.int32)
        self._driver = InflightDriver("serve",
                                      max_inflight=self._max_inflight)

    # -- gauges --------------------------------------------------------------
    def _state_slots_used(self):
        """Decode slots whose per-slot state (`CacheSpec.slots`) a request
        owns; 0 for a net that caches by token only."""
        return sum(s is not None for s in self._slots) \
            if self._state_bytes else 0

    def _paged_live_step_share(self):
        """Of the slots' table entries, the share a decode step's paged
        kernel has a grid step for: the (slot, block) pairs of its work
        list, the pool's blocks in use and one item an idle slot, over
        slots x the table's width (what a grid over every table entry
        walked, dead steps and all). From the host's own books."""
        idle = sum(s is None for s in self._slots)
        return (self._pool.used_blocks + idle) / (self._A * self._MB)

    def _publish_gauges(self):
        from ..core import monitor as _monitor
        _monitor.stat_set_many({
            "serve.queue_depth": len(self._queue),
            "serve.active_slots": sum(s is not None
                                      for s in self._slots),
            "serve.kv_pool_used_blocks": self._pool.used_blocks,
            "serve.kv_pool_free_blocks": self._pool.free_blocks,
            "serve.model_version": self.model_version,
            "serve.decode_tokens": self._decode_tokens,
            "serve.prefill_dispatches": self._prefill_dispatches,
            "serve.prefill_tokens": self._prefill_tokens,
            "serve.prefill_rows": self._prefill_rows,
            "serve.prefill_live_rows": self._prefill_live_rows,
            "serve.admitted": self._admitted,
            "serve.queue_wait_s": self._queue_wait_s,
            "serve.state_slots_used": self._state_slots_used(),
            "serve.state_bytes": self._state_bytes,
            "serve.steps": self._step_count,
            "serve.paged_live_step_share": self._paged_live_step_share(),
            **{f"serve.{k}": v for k, v in self._net_counts.items()},
        })
