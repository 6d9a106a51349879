"""Share of decode slots that produced a token, counted where it happens:
the scheduler's own `decode_tokens` (tokens appended from decode beats) over
`steps` x `max_active`, both as differences between the first and the last
`loop.stats()` sample of the window. It is the number the re-rating rule
reads (README: Re-rate a saturating mix). Like every per-layer metric PR 25
added it is reported by the traced run only; nothing is reported from a
program whose `stats()` has no `decode_tokens`."""
from benchmark.lib.stats import slot_fill

LAYER, UNIT, SOURCE, MOVES = ("serve scheduler", "%", "program_counter",
                              "serve_tokens_per_s")


def read(obs):
    if "trace_modules" not in obs:
        return None
    fill = slot_fill(obs.get("samples"), obs["max_active"])
    return None if fill is None else 100.0 * fill
