"""Memory-bandwidth utilisation of the decode step of a shortcut-connected
expert block: the bytes one step must read (benchmark/lib/
bytes_longcat_flash.py: per layer two latent attentions, two dense FFNs,
the router and the held experts that got a pair — the window's
`moe_decode_experts_touched / moe_decode_layer_steps` — then the head, and
the cached latents of the live tokens, two a layer, from the `stats()`
samples: pool blocks in use, less one a slot for the blocks half full)
over the mean device time of the decode program's events on the trace's
"XLA Modules" line (`module_patterns.decode`) times the chip's peak HBM
bandwidth. Bytes are what the algorithm needs, so the share cannot pass
100 %; a decode step is bound by this stream of weights. Only a program
that counts zero-compute pairs (`moe_decode_pairs_zero`) is read: the
parent of the PR that brought this block reports nothing."""
import re

from benchmark.lib import bytes_longcat_flash as nbytes
from benchmark.lib.peaks import peak

LAYER, UNIT, SOURCE, MOVES = ("decode step", "%", "device_trace",
                              "serve_tokens_per_s")


def read(obs):
    pattern = obs.get("module_patterns", {}).get("decode")
    modules, samples = obs.get("trace_modules"), obs.get("samples")
    if not pattern or not modules or not samples \
            or "moe_decode_pairs_zero" not in samples[0]:
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
    print(f"scmoe decode step: {len(steps)} steps of {step_s * 1e3:.3f} ms "
          f"on the device, {need / 1e9:.3f} GB to read ({touched:.2f} "
          f"experts touched a layer, {live:.0f} live tokens of "
          f"{nbytes.latent_bytes_per_token(obs['config'])} B)", flush=True)
    return 100.0 * need / (step_s * peak(obs["device_kind"],
                                         "hbm_bytes_per_s"))
