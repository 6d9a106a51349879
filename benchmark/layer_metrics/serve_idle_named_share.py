"""Share of the device's idle time between programs that lies under one of
the program's own spans in the trace, and (printed, ten longest) which span
each gap lies under: `trace: gap 5.4 ms under serve/upload`. An idle gap
under `serve/wait_work` is "no request"; one under `serve/admit` or
`serve/upload` is the scheduler. lib/host_spans.py:attribute_gaps."""
from benchmark.lib import host_spans

LAYER, UNIT, SOURCE, MOVES = ("device", "%", "device_trace",
                              "serve_tokens_per_s")


def read(obs, xplane=None):
    return host_spans.idle_named_share(obs, xplane)
