"""Memory-bandwidth utilisation of a hybrid net's decode step: the bytes
one step must move (benchmark/lib/bytes_olmo_hybrid.decode_step_bytes:
every layer's parameters and the head once, the cached keys and values of
the live tokens — pool blocks in use less one a slot for the blocks half
full, from the `stats()` samples — and each live slot's recurrent state
read and written, the window's mean of `active_slots`) over the mean
device time of the decode program's events on the trace's "XLA Modules"
line (`module_patterns.decode`) times the chip's peak HBM bandwidth.
Bytes are what the algorithm needs, so the share cannot pass 100 %. Read
only from a program whose `stats()` counts `linear_decode_layer_steps`."""
import re

from benchmark.lib import bytes_olmo_hybrid as nbytes
from benchmark.lib.peaks import peak

LAYER, UNIT, SOURCE, MOVES = ("decode step", "%", "device_trace",
                              "serve_tokens_per_s")


def read(obs):
    pattern = obs.get("module_patterns", {}).get("decode")
    modules, samples = obs.get("trace_modules"), obs.get("samples")
    if not pattern or not modules or not samples \
            or "linear_decode_layer_steps" not in samples[0]:
        return None
    rx = re.compile(pattern)
    steps = [e for e in modules[min(modules)] if rx.search(e[0])]
    if not steps:
        return None
    live = sum(max(0, s["kv_pool_used_blocks"] - s["active_slots"])
               for s in samples) / len(samples) * obs["block_size"]
    slots = sum(s["active_slots"] for s in samples) / len(samples)
    step_s = sum(d for _, _, d in steps) * 1e-9 / len(steps)
    need = nbytes.decode_step_bytes(obs["config"], live, slots)
    print(f"hybrid decode step: {len(steps)} steps of {step_s * 1e3:.3f} ms "
          f"on the device, {need / 1e9:.3f} GB to move ({live:.0f} live "
          f"tokens, {slots:.2f} slots' state)", flush=True)
    return 100.0 * need / (step_s * peak(obs["device_kind"],
                                         "hbm_bytes_per_s"))
