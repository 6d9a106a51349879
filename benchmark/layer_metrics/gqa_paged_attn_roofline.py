"""The grouped-query paged kernel's share of its roofline: the least time
the chip could take for a call, the larger of its operations over the
peak bf16 rate and its bytes over the peak HBM bandwidth
(benchmark/lib/bytes_laguna.gqa_call_cost: the keys and values of the
cached tokens a call attends to, read once for their whole group of query
heads, the queries in and the outputs out), over the mean device time of
the kernel's events in the trace (`kernel_patterns.paged_attn`). A decode
step calls the kernel once a layer, the full layers over the pool's
pages (each slot's whole stream) and the sliding layers over the rings
(at most the window a slot): what a step's calls read comes from the
program's own counts of cached tokens attended to, per kind of layer
(`attn_full_decode_tokens_read`, `attn_window_decode_tokens_read`: the
window's difference over its steps), each kind's calls at their own
lengths; the roofline time is the mean over a step's calls as the event
time is. The call is bound by the bytes: 6 or 9 query heads make 12 or 18
operations a byte. Nothing is reported from a program that does not
count what its attention reads."""
from benchmark.layer_metrics.gdn_chunk_roofline import (mean_event_s,
                                                        roofline_s)
from benchmark.lib import bytes_laguna as cost

LAYER, UNIT, SOURCE, MOVES = ("kernels", "%", "device_trace",
                              "serve_tokens_per_s")


def step_reads(samples):
    """(cached tokens a decode step read in its full layers, in its
    sliding layers, slots live) as means over the window; None where the
    program does not count them or no step ran."""
    if not samples or "attn_full_decode_tokens_read" not in samples[0]:
        return None
    steps = samples[-1]["steps"] - samples[0]["steps"]
    if steps <= 0:
        return None
    full, window = ((samples[-1][k] - samples[0][k]) / steps for k in (
        "attn_full_decode_tokens_read", "attn_window_decode_tokens_read"))
    slots = sum(s["active_slots"] for s in samples) / len(samples)
    return full, window, slots


def read(obs):
    call_s, calls = mean_event_s(obs, "paged_attn")
    reads = step_reads(obs.get("samples"))
    if call_s is None or reads is None:
        return None
    c = obs["config"]
    full, window, slots = reads
    need, layers = 0.0, 0
    for kind, tokens in ((cost.FULL, full), (cost.SLIDING, window)):
        n = cost.layers_of(c, kind)
        if not n:
            continue
        heads = next(h for k, h, _ in cost.layer_list(c) if k == kind)
        need += n * roofline_s(*cost.gqa_call_cost(c, heads, slots,
                                                   tokens / n),
                               obs["device_kind"])
        layers += n
    need /= layers
    print(f"gqa paged attention: {calls} calls of {call_s * 1e6:.1f} us on "
          f"the device, a step's {layers} calls read {full:.0f} cached "
          f"tokens in the full layers and {window:.0f} in the sliding "
          f"layers ({slots:.1f} slots live), roofline time "
          f"{need * 1e6:.1f} us a call", flush=True)
    return 100.0 * need / call_s
