"""Window seconds per dispatched decode beat (delta of
`loop.stats()["steps"]`): prefills that stall the beat are inside it."""
LAYER, UNIT, SOURCE, MOVES = ("serve scheduler", "ms", "program_counter",
                              "serve_tokens_per_s")


def read(obs):
    if not obs.get("steps") or "counters" not in obs:
        return None
    return obs["window_s"] / obs["steps"] * 1e3
