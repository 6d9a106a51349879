"""Host work per scheduler beat: mean over the traced `serve/tick` spans of
their duration minus the time inside them in which the host was blocked on
the device (`serve/settle_wait`, and `serve/retire_wait` where the in-flight
window blocks a dispatch), from the program's spans in the profiler's
trace. What is left is the scheduler's own Python and the per-beat uploads:
the part of a beat that a faster device would not shorten. Nothing is
reported without a trace or from a program that puts no `serve/tick` there."""
from benchmark.lib import host_spans

LAYER, UNIT, SOURCE, MOVES = ("serve scheduler", "ms", "program_span",
                              "serve_tokens_per_s")
WAITS = ("serve/settle_wait", "serve/retire_wait")


def read(obs, xplane=None):
    if "trace_modules" not in obs:
        return None
    phases = host_spans.phase_ms(host_spans.this_run_lines(xplane),
                                 "serve/tick")
    host_spans.print_phases("serve/tick", phases)
    return host_spans.work_ms(phases, WAITS)
