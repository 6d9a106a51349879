"""Mean share of the pool's blocks in use, from `loop.stats()` sampled
through the window."""
LAYER, UNIT, SOURCE, MOVES = ("KV pool", "%", "program_counter",
                              "serve_tokens_per_s")


def read(obs):
    samples = obs.get("samples")
    if not samples:
        return None
    used = [s["kv_pool_used_blocks"] for s in samples]
    return 100.0 * sum(used) / len(used) / obs["kv_blocks"]
