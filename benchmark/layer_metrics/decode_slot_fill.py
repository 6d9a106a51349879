"""Share of decode slots that produced a token, counted where it happens:
the scheduler's own `decode_tokens` (tokens appended from decode beats) over
`steps` x `max_active`, both as differences between the first and the last
`loop.stats()` sample of the window. `decode_occupancy` infers the same
from completions. Like every per-layer metric PR 25 added it is reported by
the traced run only; nothing is reported from a program whose `stats()` has
no `decode_tokens`."""
LAYER, UNIT, SOURCE, MOVES = ("serve scheduler", "%", "program_counter",
                              "serve_tokens_per_s")


def read(obs):
    samples = obs.get("samples")
    if ("trace_modules" not in obs or not samples
            or "decode_tokens" not in samples[0]):
        return None
    first, last = samples[0], samples[-1]
    beats = last["steps"] - first["steps"]
    if beats <= 0:
        return None
    return (100.0 * (last["decode_tokens"] - first["decode_tokens"])
            / (beats * obs["max_active"]))
