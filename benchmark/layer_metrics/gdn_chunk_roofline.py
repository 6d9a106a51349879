"""The chunked-scan kernel's share of its roofline: the least time the chip
could take for a call, the larger of its operations over the peak bf16
rate and its bytes over the peak HBM bandwidth (benchmark/lib/
bytes_olmo_hybrid.gdn_chunk_cost: what the algorithm needs for the
prompt's own tokens in whole chunks, not for the bucket's padding), over
the mean device time of the kernel's events in the trace
(`kernel_patterns.gdn_chunk`). Which prompts the traced calls served is
read from the program's `serve/prefill` spans in the same trace
(`prompt_len`): the mean over those prefills of a call's roofline time
against the mean over the events of a call's time, so that a prefill cut
by the trace's edge moves neither. The kernel multiplies float32 operands
at full precision (six passes of the MXU); the peak is the bf16 one."""
import re

from benchmark.lib import bytes_olmo_hybrid as cost
from benchmark.lib import host_spans
from benchmark.lib.peaks import peak

LAYER, UNIT, SOURCE, MOVES = ("kernels", "%", "device_trace",
                              "serve_tokens_per_s")


def mean_event_s(obs, name):
    """Mean device seconds of the events `kernel_patterns[name]` matches,
    and how many; (None, 0) without the pattern, a trace or a match."""
    pattern = obs.get("kernel_patterns", {}).get(name)
    ops = obs.get("trace_ops")
    if not pattern or not ops:
        return None, 0
    rx = re.compile(pattern)
    hit = [d for n, _, d in ops[min(ops)] if rx.search(n)]
    if not hit:
        return None, 0
    return sum(hit) * 1e-9 / len(hit), len(hit)


def roofline_s(ops, moved, device_kind):
    return max(ops / peak(device_kind, "bf16_flops_per_s"),
               moved / peak(device_kind, "hbm_bytes_per_s"))


def read(obs, xplane=None):
    call_s, calls = mean_event_s(obs, "gdn_chunk")
    if call_s is None:
        return None
    prompts = [int(e[3]["prompt_len"])
               for line in host_spans.this_run_lines(xplane).values()
               for e in line if e[0] == "serve/prefill"
               and "prompt_len" in e[3]]
    if not prompts:
        return None
    least = [roofline_s(*cost.gdn_chunk_cost(obs["config"], n),
                        obs["device_kind"]) for n in prompts]
    need = sum(least) / len(least)
    print(f"gdn chunk scan: {calls} calls of {call_s * 1e3:.3f} ms on the "
          f"device, {len(prompts)} prefills of mean "
          f"{sum(prompts) / len(prompts):.0f} tokens in the trace, roofline "
          f"time {need * 1e3:.4f} ms a call", flush=True)
    return 100.0 * need / call_s
