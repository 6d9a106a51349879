"""Share of the first device's idle time between train steps that lies
under one of the program's own spans in the trace, and (printed, ten
longest) which span each gap lies under: `trace: gap 5.1 ms under
fit/callbacks`. lib/host_spans.py:attribute_gaps."""
from benchmark.lib import host_spans

LAYER, UNIT, SOURCE, MOVES = ("device", "%", "device_trace",
                              "train_tokens_per_s_chip")


def read(obs, xplane=None):
    return host_spans.idle_named_share(obs, xplane)
