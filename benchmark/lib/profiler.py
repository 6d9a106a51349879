"""Start and stop the JAX profiler for a few seconds of a window. The
Python tracer is off and the host tracer low: the device's lines are what
the reduction reads, traces are large, and tracing slows the host."""
from __future__ import annotations

import shutil


def start(trace_dir):
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)  # one trace per directory
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop():
    import jax
    jax.profiler.stop_trace()
