"""Memory-bandwidth utilisation of the decode step: the bytes one step
must read (benchmark/lib/bytes_kimi_k2.py: the dense layer, per expert
layer attention, shared expert, router and the experts that got a pair —
the window's `moe_decode_experts_touched / moe_decode_layer_steps` — the
head, and the cached latents of the live tokens, from the `stats()`
samples: pool blocks in use, less one a slot for the blocks half full)
over the mean device time of the decode program's events on the trace's
"XLA Modules" line (`module_patterns.decode`) times the chip's peak HBM
bandwidth. Bytes are what the algorithm needs, so the share cannot pass
100 %; a decode step is bound by this stream of weights."""
import re

from benchmark.lib import bytes_kimi_k2 as nbytes
from benchmark.lib.peaks import peak

LAYER, UNIT, SOURCE, MOVES = ("decode step", "%", "device_trace",
                              "serve_tokens_per_s")


def read(obs):
    pattern = obs.get("module_patterns", {}).get("decode")
    modules, samples = obs.get("trace_modules"), obs.get("samples")
    if not pattern or not modules or not samples \
            or "moe_decode_layer_steps" not in samples[0]:
        return None
    rx = re.compile(pattern)
    steps = [e for e in modules[min(modules)] if rx.search(e[0])]
    layer_steps = (samples[-1]["moe_decode_layer_steps"]
                   - samples[0]["moe_decode_layer_steps"])
    if not steps or layer_steps <= 0:
        return None
    touched = (samples[-1]["moe_decode_experts_touched"]
               - samples[0]["moe_decode_experts_touched"]) / layer_steps
    live = sum(max(0, s["kv_pool_used_blocks"] - s["active_slots"])
               for s in samples) / len(samples) * obs["block_size"]
    step_s = sum(d for _, _, d in steps) * 1e-9 / len(steps)
    need = nbytes.decode_step_bytes(obs["config"], touched, live,
                                    obs["max_active"])
    print(f"decode step: {len(steps)} steps of {step_s * 1e3:.3f} ms on the "
          f"device, {need / 1e9:.3f} GB to read ({touched:.2f} experts "
          f"touched a layer, {live:.0f} live tokens)", flush=True)
    return 100.0 * need / (step_s * peak(obs["device_kind"],
                                         "hbm_bytes_per_s"))
