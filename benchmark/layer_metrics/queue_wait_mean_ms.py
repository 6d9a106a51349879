"""Mean time a request waited in the queue for a slot, over the requests
admitted in the window: the scheduler's `queue_wait_s` (sum of `t_admit -
t_submit`) over `admitted`, both as differences between the first and the
last `loop.stats()` sample of the window. It is the part of time to first
token that is waiting, not prefill. Like every per-layer metric PR 25 added
it is reported by the traced run only; nothing is reported from a program
whose `stats()` has no such counts, or when nothing was admitted."""
from benchmark.lib.stats import queue_wait_ms

LAYER, UNIT, SOURCE, MOVES = ("serve entry", "ms", "program_counter",
                              "serve_tokens_per_s")


def read(obs):
    if "trace_modules" not in obs:
        return None
    return queue_wait_ms(obs.get("samples"))
