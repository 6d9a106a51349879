"""Shape-keyed block-size autotuning for the Pallas kernel tier.

Replaces the static largest-divisor heuristics (`_pick_block` in
flash_attention.py, `_pick` in fused_ce.py) with a measured table: the
first call at a new (kernel, shape-bucket, dtype, backend) key times the
candidate block configurations on the real inputs and records the winner.
This is the TPU analog of the reference's runtime kernel selection
(operators/jit/gen_base.cc JitCodeCreator picks an implementation per
shape-key and caches it in a per-op map) — except the "implementations"
here are grid/block parametrizations of one Pallas kernel, and the cost
model is a wall-clock measurement instead of a heuristic table.

Resolution order at a call site (all kernels follow it):

1. explicit `FLAGS_*_block_*` flag overrides — always win, never measured;
2. in-process table hit;
3. disk cache hit (`PADDLE_TPU_PALLAS_AUTOTUNE_CACHE=<path>.json`), so a
   fleet job pays the measurement once per shape family, not once per
   process;
4. measure-and-record — only when measuring is meaningful (compiled TPU
   backend, or `FLAGS_pallas_autotune_force` for interpreter-mode tests)
   and the call is eager: under a trace the heuristic default is taken;
5. otherwise the caller's heuristic default (what `_pick_block` chose
   before this module existed).

Shape keys are *bucketed* (next power of two) so s=1000 and s=1024 share
an entry — the measured optimum is a property of the magnitude, not the
exact length, and an exact-shape table would re-measure every ragged
batch.
"""
from __future__ import annotations

import json
import os
import threading
import time

__all__ = ["bucket", "lookup", "clear", "table_snapshot", "cache_path"]

_LOCK = threading.RLock()
_TABLE = {}          # key tuple -> params tuple (measured winners only)
_LOADED_PATH = None  # disk cache file already merged into _TABLE


def bucket(n: int) -> int:
    """Next power of two >= n (shape-family key, not the exact length)."""
    n = int(n)
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def cache_path():
    return os.environ.get("PADDLE_TPU_PALLAS_AUTOTUNE_CACHE") or None


def _key(kernel, shape_key, dtype):
    import jax
    return (str(kernel), tuple(int(x) for x in shape_key), str(dtype),
            jax.default_backend())


def _key_str(key):
    kernel, shape_key, dtype, backend = key
    return "|".join([kernel, ",".join(str(x) for x in shape_key), dtype,
                     backend])


def _load_disk_locked():
    """Merge the disk cache into the in-process table (once per path)."""
    global _LOADED_PATH
    path = cache_path()
    if path is None or path == _LOADED_PATH:
        return
    _LOADED_PATH = path
    try:
        with open(path) as f:
            data = json.load(f)
    except FileNotFoundError:
        return
    except Exception:
        return  # a corrupt cache is a missed optimization, never an error
    for ks, entry in data.get("entries", {}).items():
        parts = ks.split("|")
        if len(parts) != 4:
            continue
        kernel, shape_s, dtype, backend = parts
        shape_key = tuple(int(x) for x in shape_s.split(",") if x)
        _TABLE.setdefault((kernel, shape_key, dtype, backend),
                          tuple(entry["params"]))


def _save_disk_locked(key, params, seconds):
    path = cache_path()
    if path is None:
        return
    # serialize concurrent fleet writers on a sidecar lock: without it the
    # read-modify-write below is last-writer-wins and a simultaneously
    # measured entry from another process is silently dropped (that
    # process' measurement gets re-paid by everyone else forever)
    lock_f = None
    try:
        try:
            import fcntl
            lock_f = open(f"{path}.lock", "w")
            fcntl.flock(lock_f, fcntl.LOCK_EX)
        except Exception:
            lock_f = None  # locking is best-effort (e.g. non-POSIX fs)
        try:
            with open(path) as f:
                data = json.load(f)
        except Exception:
            data = {"version": 1, "entries": {}}
        data.setdefault("entries", {})[_key_str(key)] = {
            "params": list(params), "seconds": seconds}
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump(data, f, indent=1, sort_keys=True)
            os.replace(tmp, path)  # atomic: readers see old or new
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
    finally:
        if lock_f is not None:
            lock_f.close()


def _should_measure():
    import jax

    from ...core import flags as _flags
    if not _flags.flag("FLAGS_pallas_autotune"):
        return False
    if _flags.flag("FLAGS_pallas_autotune_force"):
        return True  # tests: exercise the measuring path off-TPU
    # off-TPU the kernels run interpreted — timings there say nothing
    # about MXU/VMEM behavior, so the heuristic default wins
    return jax.default_backend() == "tpu"


def _tracing():
    """Under jit/scan every jnp value is a tracer — including the
    "concrete" zeros a measure() builds — so timing there would time
    nested tracing (block_until_ready on a tracer is a no-op) and pick
    block sizes by trace-time noise."""
    import jax
    import jax.numpy as jnp
    return isinstance(jnp.zeros(()), jax.core.Tracer)


def lookup(kernel, shape_key, dtype, candidates, measure, default):
    """Resolve block params for one kernel call.

    kernel: short name ("flash_fwd", "fused_ce", "decode_attention");
    shape_key: tuple of *bucketed* ints describing the shape family;
    candidates: list of param tuples worth trying (caller guarantees each
    is legal for the real — unbucketed — shape); measure: params ->
    seconds (compile + run; exceptions disqualify the candidate);
    default: params returned when measuring is off.
    """
    from ...core import monitor
    key = _key(kernel, shape_key, dtype)
    with _LOCK:
        _load_disk_locked()
        hit = _TABLE.get(key)
    if hit is not None:
        # the disk cache may hold a candidate the current call can't use
        # (different divisibility inside one bucket): fall back if so
        if hit in [tuple(c) for c in candidates]:
            return hit
        return default
    if not _should_measure() or measure is None or len(candidates) <= 1 \
            or _tracing():
        return default
    best, best_t = None, None
    for cand in candidates:
        try:
            t = measure(tuple(cand))
        except Exception:
            monitor.stat_add(f"pallas.autotune.failed_candidate.{kernel}")
            continue
        if t is not None and (best_t is None or t < best_t):
            best, best_t = tuple(cand), float(t)
    if best is None:
        return default
    with _LOCK:
        _TABLE[key] = best
        _save_disk_locked(key, best, best_t)
    monitor.stat_add(f"pallas.autotune.measured.{kernel}")
    return best


def time_thunk(thunk, repeats=3):
    """Measure a jitted thunk: one untimed call (compile + warmup), then
    best-of-`repeats` wall clock. Returns seconds."""
    import jax
    jax.block_until_ready(thunk())
    best = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(thunk())
        dt = time.perf_counter() - t0
        best = dt if best is None or dt < best else best
    return best


def clear():
    """Drop the in-process table (tests)."""
    global _LOADED_PATH
    with _LOCK:
        _TABLE.clear()
        _LOADED_PATH = None


def table_snapshot():
    with _LOCK:
        return dict(_TABLE)
