"""Host/device profiler.

TPU-native analog of the reference profiler stack:
- `RecordEvent` scoped host annotations — reference platform/profiler.h:127
  (RAII RecordEvent inserted around the op loop, framework/operator.cc:1074).
- `profiler`/`start_profiler`/`stop_profiler` context + summary tables —
  reference python/paddle/fluid/profiler.py.
- Chrome-trace export — reference platform/profiler.proto + device_tracer.
- Device-side capture: the reference correlates CUPTI kernel records
  (platform/device_tracer.h:43); the TPU equivalent is XLA's xplane
  profiler, exposed here as `xplane_trace` (view in TensorBoard/XProf) —
  compiler-scheduled device activity replaces per-kernel correlation ids.
- `cost_analysis` — achieved-FLOPs accounting from the compiled
  executable, the analog of the reference's per-op cost model
  (platform/monitor.h StatRegistry + op_handle events).

Design delta: ops under `jit` execute as one XLA program, so per-op *host*
events measure Python trace/dispatch (still the right tool for finding
host-side stalls — the reference's RecordEvent measures the same thing);
device time lives in the xplane capture and in whole-step wall clock.
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
from collections import defaultdict
from typing import Optional

from ..core import trace as _trace

__all__ = ["RecordEvent", "profiler", "start_profiler", "stop_profiler",
           "reset_profiler", "summary", "events", "export_chrome_trace",
           "xplane_trace", "start_xplane", "stop_xplane", "cost_analysis",
           "is_profiler_enabled"]

_lock = threading.Lock()
_events: list = []          # (name, t0, t1, tid)
_enabled = False
_t_origin = _trace._t_origin  # shared clock origin with the span tracer


def is_profiler_enabled() -> bool:
    return _enabled


def _trace_sink(sp):
    """Installed into core/trace: while the host profiler is enabled,
    every finished span (RecordEvent or first-class trace.span site —
    pipeline runner, PS rpc, Pallas dispatch, dataloader) also lands in
    the profiler's aggregate event table, so summary() covers the whole
    runtime without double instrumentation."""
    if _enabled:
        with _lock:
            _events.append((sp.name, sp.t0, sp.t1, sp.tid))


_trace._profiler_sink = _trace_sink


class RecordEvent:
    """Scoped host annotation (reference platform/profiler.h:127), now a
    thin wrapper over a core/trace span: it nests under the ambient span
    and shows up in Chrome-trace exports with ids/parents. Usable as a
    context manager or via explicit begin()/end(). Cheap no-op while the
    profiler is disabled (per-op sites in core/tape.py stay free); use
    core.trace.span directly for always-on (flight-recorded) sites.
    """

    __slots__ = ("name", "_span")

    def __init__(self, name: str):
        self.name = name
        self._span = None

    def begin(self):
        if _enabled:
            # detached: legacy callers (core/tape.py per-op annotations)
            # skip end() on exception — a stack-attached span would then
            # corrupt every later span's parentage on this thread
            self._span = _trace.begin(self.name, _attach=False)
        return self

    def end(self):
        if self._span is not None:
            _trace.end(self._span)  # the sink mirrors it into _events
            self._span = None

    __enter__ = begin

    def __exit__(self, *exc):
        self.end()
        return False


def start_profiler(state: str = "All", tracer_option: str = "Default"):
    """reference fluid/profiler.py start_profiler. `state`/`tracer_option`
    kept for API parity (host events are always captured; use xplane_trace
    for device activity)."""
    global _enabled
    from ..core import flags as _flags
    _flags.set_flags({"FLAGS_enable_profiler": True})
    _enabled = True


def stop_profiler(sorted_key: Optional[str] = None,
                  profile_path: Optional[str] = None):
    """Stop, optionally print a summary table and write a chrome trace."""
    global _enabled
    _enabled = False
    from ..core import flags as _flags
    _flags.set_flags({"FLAGS_enable_profiler": False})
    if profile_path:
        export_chrome_trace(profile_path)
    if sorted_key is not None:
        print(summary(sorted_key=sorted_key))


def reset_profiler():
    with _lock:
        _events.clear()


def events():
    with _lock:
        return list(_events)


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: str = "total",
             profile_path: Optional[str] = None):
    """reference fluid/profiler.py profiler() context manager."""
    reset_profiler()
    start_profiler(state)
    try:
        yield
    finally:
        stop_profiler(sorted_key=sorted_key, profile_path=profile_path)


def summary(sorted_key: str = "total") -> str:
    """Aggregate event table (reference profiler summary printing)."""
    agg = defaultdict(lambda: [0, 0.0, float("inf"), 0.0])  # n, tot, mn, mx
    for name, t0, t1, _tid in events():
        dt = (t1 - t0) * 1e3
        a = agg[name]
        a[0] += 1
        a[1] += dt
        a[2] = min(a[2], dt)
        a[3] = max(a[3], dt)
    if not agg:
        return "(no profiler events)"
    total_all = sum(a[1] for a in agg.values())
    keyfn = {"total": lambda kv: kv[1][1], "calls": lambda kv: kv[1][0],
             "max": lambda kv: kv[1][3], "min": lambda kv: kv[1][2],
             "ave": lambda kv: kv[1][1] / kv[1][0]}.get(
                 sorted_key, lambda kv: kv[1][1])
    rows = sorted(agg.items(), key=keyfn, reverse=True)
    w = max(len(n) for n in agg) + 2
    out = [f"{'Event':<{w}}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>10}"
           f"{'Min(ms)':>10}{'Max(ms)':>10}{'Ratio':>8}"]
    for name, (n, tot, mn, mx) in rows:
        out.append(f"{name:<{w}}{n:>8}{tot:>12.3f}{tot / n:>10.3f}"
                   f"{mn:>10.3f}{mx:>10.3f}{tot / total_all:>8.2%}")
    return "\n".join(out)


def export_chrome_trace(path: str):
    """chrome://tracing JSON (analog of the reference's chrome-trace
    protobuf output, platform/profiler.proto)."""
    trace = [{"name": name, "ph": "X", "pid": 0, "tid": tid,
              "ts": (t0 - _t_origin) * 1e6, "dur": (t1 - t0) * 1e6}
             for name, t0, t1, tid in events()]
    with open(path, "w") as f:
        json.dump({"traceEvents": trace}, f)


# -- device-side capture (XLA xplane; view with TensorBoard/XProf) ---------

def start_xplane(log_dir: str):
    import jax
    jax.profiler.start_trace(log_dir)


def stop_xplane():
    import jax
    jax.profiler.stop_trace()


@contextlib.contextmanager
def xplane_trace(log_dir: str):
    """Capture an XLA device trace (the CUPTI-correlation analog,
    reference platform/device_tracer.h:43). On exit the compiled serve
    programs' maps from instruction to scope (core/program_map.py) are
    written beside it as `program_map.json`, so that another process can
    tell the timeline's `fusion.123` by the work it belongs to
    (benchmark/inspect_scopes.py)."""
    from ..core import program_map
    start_xplane(log_dir)
    try:
        yield
    finally:
        stop_xplane()
        program_map.dump(log_dir)


# -- achieved-FLOPs accounting ---------------------------------------------

def cost_analysis(jitted_fn, *args, **kwargs):
    """XLA cost analysis of a jitted callable on example args: returns
    {'flops': ..., 'bytes accessed': ..., ...} summed over the module.
    The analog of the reference's per-op cost model feeding its graph
    passes (details/op_handle_base events + monitor StatRegistry)."""
    lowered = jitted_fn.lower(*args, **kwargs)
    compiled = lowered.compile()
    return dict(compiled.cost_analysis() or {})
