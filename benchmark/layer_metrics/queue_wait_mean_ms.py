"""Mean time a request waited in the queue for a slot, over the requests
admitted in the window: the scheduler's `queue_wait_s` (sum of `t_admit -
t_submit`) over `admitted`, both as differences between the first and the
last `loop.stats()` sample of the window. It is the part of time to first
token that is waiting, not prefill. Like every per-layer metric PR 25 added
it is reported by the traced run only; nothing is reported from a program
whose `stats()` has no such counts, or when nothing was admitted."""
LAYER, UNIT, SOURCE, MOVES = ("serve entry", "ms", "program_counter",
                              "serve_tokens_per_s")


def read(obs):
    samples = obs.get("samples")
    if ("trace_modules" not in obs or not samples
            or "queue_wait_s" not in samples[0]):
        return None
    first, last = samples[0], samples[-1]
    admitted = last["admitted"] - first["admitted"]
    if admitted <= 0:
        return None
    return 1e3 * (last["queue_wait_s"] - first["queue_wait_s"]) / admitted
