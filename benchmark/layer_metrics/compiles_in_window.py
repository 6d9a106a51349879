"""Programs jax handed to the backend inside the window (its own
backend-compile events, cache hits included). Must be 0."""
LAYER, UNIT, SOURCE, MOVES = ("compile", "count", "program_counter",
                              "setup_s")


def read(obs):
    return obs.get("compiles_in_window")
