"""Of the bytes that paging EVERY layer by token would hold for the live
tokens, the share that is not held because the sliding-window layers keep
a ring a slot: 1 - (the full layers' pool blocks in use + all the rings) /
(`num_hidden_layers` x live tokens x a token's keys and values), as a
mean over the window's `loop.stats()` samples (benchmark/lib/
bytes_laguna.saved_share). The live tokens are the pool's blocks in use
less one a slot for the blocks half full; the rings count whole, used or
not (`window_ring_bytes`, a gauge of the program). Streams shorter than
the window would make it negative: a ring is then a page that is never
freed. Nothing is reported from a program without that gauge."""
from benchmark.lib import bytes_laguna as nbytes

LAYER, UNIT, SOURCE, MOVES = ("KV pool", "%", "program_counter",
                              "serve_tokens_per_s")


def read(obs):
    samples = obs.get("samples")
    if not samples or not samples[-1].get("window_ring_bytes"):
        return None
    c, bs = obs["config"], obs["block_size"]
    used = sum(s["kv_pool_used_blocks"] for s in samples) / len(samples)
    slots = sum(s["active_slots"] for s in samples) / len(samples)
    live = max(0.0, used - slots / 2) * bs
    if live <= 0:
        return None
    rings = samples[-1]["window_ring_bytes"]
    held, every = nbytes.saved_share(c, live, used, bs, rings)
    print(f"window cache: {live:.0f} live tokens in {used:.1f} blocks, "
          f"{held / 1e9:.3f} GB held ({rings / 1e9:.3f} GB of rings) where "
          f"{c['num_hidden_layers']} paged layers would hold "
          f"{every / 1e9:.3f} GB", flush=True)
    return 100.0 * (1.0 - held / every)
