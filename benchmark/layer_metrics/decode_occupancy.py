"""Share of decode slots that produced a token: tokens that came from
decode beats (all tokens minus one prefill token per completed request)
over beats x max_active."""
LAYER, UNIT, SOURCE, MOVES = ("serve scheduler", "%", "program_counter",
                              "serve_tokens_per_s")


def read(obs):
    if not obs.get("steps") or "counters" not in obs:
        return None
    c = obs["counters"]
    decoded = c["serve.tokens_generated"] - c["serve.requests_completed"]
    return 100.0 * decoded / (obs["steps"] * obs["max_active"])
