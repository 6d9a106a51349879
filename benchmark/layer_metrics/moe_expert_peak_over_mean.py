"""The straggler factor of routing in decode steps: per expert layer and
step, the most (token, expert) pairs that fell on ONE held expert over
the mean a held expert got, `moe_decode_peak_pairs x held /
moe_decode_pairs_held`, both as differences between the first and the
last `loop.stats()` sample of the window. 1 is perfectly even routing;
on a deployment the fullest expert is what the others wait for. The log
line also says how many pairs a token left on this chip's experts a
layer (expected top_k x held / router width). Nothing is reported from a
program whose `stats()` has no `moe_*` counters."""
LAYER, UNIT, SOURCE, MOVES = ("expert layer", "ratio", "program_counter",
                              "serve_tokens_per_s")


def window(samples, key):
    return samples[-1][key] - samples[0][key]


def read(obs):
    samples = obs.get("samples")
    if not samples or "moe_decode_pairs_held" not in samples[0] \
            or "moe_decode_pairs_held" not in samples[-1]:
        return None
    pairs = window(samples, "moe_decode_pairs_held")
    tokens = window(samples, "moe_decode_tokens")
    layer_steps = window(samples, "moe_decode_layer_steps")
    steps = samples[-1]["steps"] - samples[0]["steps"]
    if pairs <= 0 or tokens <= 0 or steps <= 0:
        return None
    held = int(obs["config"]["n_routed_experts"])
    layers = layer_steps / steps
    print(f"moe: decode: {pairs / (tokens * layers):.4f} pairs held a "
          f"token a layer, "
          f"{window(samples, 'moe_decode_experts_touched') / layer_steps:.3f}"
          f" of {held} experts touched a layer-step, "
          f"{window(samples, 'moe_decode_peak_pairs') / layer_steps:.3f} "
          f"pairs on the fullest", flush=True)
    return window(samples, "moe_decode_peak_pairs") * held / pairs
