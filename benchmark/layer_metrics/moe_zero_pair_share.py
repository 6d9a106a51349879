"""The share of the decode steps' (token, expert) pairs that fell on
zero-compute experts: `moe_decode_pairs_zero / (moe_decode_pairs_zero +
moe_decode_pairs_real)`, both as differences between the first and the
last `loop.stats()` sample of the window. A zero pair costs one
multiply-add; a real pair costs an expert's three matrix products on the
chip that holds it, so this share is the compute the router saved, and a
token's count of real pairs is what varies inside one batch. With
`zero_expert_num` of the router's width zero experts and a seeded router,
their share of the width (256 / 768 = 33 %). The log line gives the mean
and the standard deviation of real pairs a token a layer (the second
from `moe_decode_pairs_real_sq`, where the program counts it). Nothing is
reported from a program whose `stats()` does not count zero pairs."""
LAYER, UNIT, SOURCE, MOVES = ("expert layer", "%", "program_counter",
                              "serve_tokens_per_s")


def window(samples, key):
    return samples[-1][key] - samples[0][key]


def read(obs):
    samples = obs.get("samples")
    if not samples or "moe_decode_pairs_zero" not in samples[0] \
            or "moe_decode_pairs_zero" not in samples[-1]:
        return None
    zero = window(samples, "moe_decode_pairs_zero")
    real = window(samples, "moe_decode_pairs_real")
    tokens = window(samples, "moe_decode_tokens")
    steps = samples[-1]["steps"] - samples[0]["steps"]
    if zero + real <= 0 or tokens <= 0 or steps <= 0:
        return None
    layers = window(samples, "moe_decode_layer_steps") / steps
    mean = real / (tokens * layers)
    said = (f"moe: decode: {mean:.4f} real and "
            f"{zero / (tokens * layers):.4f} zero pairs a token a layer")
    if "moe_decode_pairs_real_sq" in samples[0]:
        second = window(samples, "moe_decode_pairs_real_sq") \
            / (tokens * layers)
        sd = max(0.0, second - mean * mean) ** 0.5
        said += f", real pairs a token sd {sd:.4f}"
    print(said, flush=True)
    return 100.0 * zero / (zero + real)
