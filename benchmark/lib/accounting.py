"""Compile and persistent-cache accounting from jax's own monitoring events
(copied from chip_smoke.py, PR 22): "a compile" is what jax says it is."""
from __future__ import annotations

import threading

import jax

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_acct = {"backend_compiles": 0, "backend_compile_s": 0.0, "cache_hits": 0,
         "cache_misses": 0}
_lock = threading.Lock()
_listening = False


def _on_duration(event, duration, **_):
    if event == _BACKEND_COMPILE:
        with _lock:
            _acct["backend_compiles"] += 1
            _acct["backend_compile_s"] += duration


def _on_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        with _lock:
            _acct["cache_hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        with _lock:
            _acct["cache_misses"] += 1


def listen():
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True


def snapshot():
    """backend_compiles counts every program jax handed to the backend,
    whether the persistent cache then served it (a hit) or XLA compiled it
    (a miss); inside a measured window both must be 0."""
    with _lock:
        return dict(_acct)
