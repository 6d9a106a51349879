"""The grouped-query paged kernel's share of its roofline in its sink /
two-width form (keys deeper than values, a sink logit a query head in the
sliding layers): the least time the chip could take for a call, the
larger of its operations over the peak bf16 rate and its bytes over the
peak HBM bandwidth (benchmark/lib/bytes_mimo_v2.sink_gqa_call_cost: the
keys and values of the cached tokens a call attends to, read once for
their whole group of query heads, 2 560 B a token in a full layer and
5 120 B in a sliding one at the published widths; the queries in, the
outputs out, the sinks), over the mean device time of the kernel's events
in the trace (`kernel_patterns.paged_attn`). A decode step calls the
kernel once a layer, the full layers over the pool's pages (each slot's
whole stream, G = 16) and the sliding layers over one-block rings (at most
the window a slot, G = 8): what a step's calls read comes from the
program's own counts of cached tokens attended to, per kind of layer
(`attn_full_decode_tokens_read`, `attn_window_decode_tokens_read`: the
window's difference over its steps), each kind's calls at their own
lengths and their own bytes a token; the roofline time is the mean over a
step's calls as the event time is. The call is bound by the bytes.
Nothing is reported from a program that does not count what its attention
reads, or from a configuration whose values are as deep as its keys."""
from benchmark.layer_metrics.gdn_chunk_roofline import (mean_event_s,
                                                        roofline_s)
from benchmark.layer_metrics.gqa_paged_attn_roofline import step_reads
from benchmark.lib import bytes_mimo_v2 as cost

LAYER, UNIT, SOURCE, MOVES = ("kernels", "%", "device_trace",
                              "serve_tokens_per_s")


def read(obs):
    call_s, calls = mean_event_s(obs, "paged_attn")
    reads = step_reads(obs.get("samples"))
    c = obs.get("config") or {}
    if call_s is None or reads is None or "v_head_dim" not in c:
        return None
    full, window, slots = reads
    need, layers = 0.0, 0
    for sliding, tokens in ((False, full), (True, window)):
        n = cost.layers_of(c, sliding)
        if n:
            need += n * roofline_s(*cost.sink_gqa_call_cost(
                c, sliding, slots, tokens / n), obs["device_kind"])
            layers += n
    need /= layers
    print(f"sink gqa paged attention: {calls} calls of {call_s * 1e6:.1f} us "
          f"on the device, a step's {layers} calls read {full:.0f} cached "
          f"tokens in the full layers and {window:.0f} in the sliding "
          f"layers ({slots:.1f} slots live), roofline time "
          f"{need * 1e6:.1f} us a call", flush=True)
    return 100.0 * need / call_s
