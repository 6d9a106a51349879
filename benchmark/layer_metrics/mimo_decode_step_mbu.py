"""Memory-bandwidth utilisation of the decode step of the MiMo-V2-Flash
share: the bytes one step must read
(benchmark/lib/bytes_mimo_v2.decode_step_bytes: every layer's attention
and router, the held experts that got a pair — the window's
`moe_decode_experts_touched / moe_decode_layer_steps` — the dense layer's
FFN, the head, and the cached keys and values attended to, the full
layers' whole streams at 2 560 B a token and the sliding layers' rings up
to the window at 5 120 B, as the program counts them:
`attn_full_decode_tokens_read`, `attn_window_decode_tokens_read`, the
window's difference over its steps) over the mean device time of the
decode program's events on the trace's "XLA Modules" line
(`module_patterns.decode`) times the chip's peak HBM bandwidth. Bytes are
what the algorithm needs, so the share cannot pass 100 %: the share of the
whole step that bounds any later claim in the cell. Nothing is reported
from a program that does not count what its attention reads, or from a
configuration whose values are as deep as its keys."""
import re

from benchmark.layer_metrics.gqa_paged_attn_roofline import step_reads
from benchmark.lib import bytes_mimo_v2 as nbytes
from benchmark.lib.peaks import peak

LAYER, UNIT, SOURCE, MOVES = ("decode step", "%", "device_trace",
                              "serve_tokens_per_s")


def read(obs):
    pattern = obs.get("module_patterns", {}).get("decode")
    modules, samples = obs.get("trace_modules"), obs.get("samples")
    reads = step_reads(samples)
    c = obs.get("config") or {}
    if not pattern or not modules or reads is None or "v_head_dim" not in c:
        return None
    rx = re.compile(pattern)
    steps = [e for e in modules[min(modules)] if rx.search(e[0])]
    layer_steps = (samples[-1]["moe_decode_layer_steps"]
                   - samples[0]["moe_decode_layer_steps"])
    if not steps or layer_steps <= 0:
        return None
    touched = (samples[-1]["moe_decode_experts_touched"]
               - samples[0]["moe_decode_experts_touched"]) / layer_steps
    full, window, _ = reads
    step_s = sum(d for _, _, d in steps) * 1e-9 / len(steps)
    need = nbytes.decode_step_bytes(c, touched, full, window,
                                    obs["max_active"])
    cache = full * nbytes.token_bytes(c, False) \
        + window * nbytes.token_bytes(c, True)
    print(f"mimo decode step: {len(steps)} steps of {step_s * 1e3:.3f} ms on "
          f"the device, {need / 1e9:.3f} GB to read ({touched:.2f} experts "
          f"touched a layer, {cache / 1e9:.3f} GB of cached keys and "
          f"values: {full:.0f} token-layers paged, {window:.0f} in rings)",
          flush=True)
    return 100.0 * need / (step_s * peak(obs["device_kind"],
                                         "hbm_bytes_per_s"))
