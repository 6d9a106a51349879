"""Host time per training step: mean over the traced `fit/step` spans (those
that dispatched a step) of their duration minus the time inside them spent
in `fit/drain`, where the host is blocked on the device. What is left is
the loader, the callbacks, the dispatch and fit's own bookkeeping: it stays
hidden while it is shorter than the device's step. Nothing is reported
without a trace or from a program that puts no `fit/step` there."""
from benchmark.lib import host_spans

LAYER, UNIT, SOURCE, MOVES = ("fit loop", "ms", "program_span",
                              "train_tokens_per_s_chip")
WAITS = ("fit/drain",)


def read(obs, xplane=None):
    if "trace_modules" not in obs:
        return None
    phases = host_spans.phase_ms(host_spans.this_run_lines(xplane),
                                 "fit/step", must_hold="fit/dispatch")
    host_spans.print_phases("fit/step", phases)
    return host_spans.work_ms(phases, WAITS)
