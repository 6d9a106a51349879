"""The state-update kernel's share of its roofline: the least time the
chip could take for a call, the larger of its operations over the peak
bf16 rate and its bytes over the peak HBM bandwidth (benchmark/lib/
bytes_olmo_hybrid.gdn_step_cost: each live slot's state read and written
once, its q, k, v in and its output out; the slots that held a request,
the window's mean of `active_slots`, not the ones the step computes
beside them), over the mean device time of the kernel's events in the
trace (`kernel_patterns.gdn_step`). The call is bound by the bytes: 7
operations an element of state against 8 bytes."""
from benchmark.layer_metrics.gdn_chunk_roofline import (mean_event_s,
                                                        roofline_s)
from benchmark.lib import bytes_olmo_hybrid as cost

LAYER, UNIT, SOURCE, MOVES = ("kernels", "%", "device_trace",
                              "serve_tokens_per_s")


def read(obs):
    call_s, calls = mean_event_s(obs, "gdn_step")
    samples = obs.get("samples")
    if call_s is None or not samples:
        return None
    slots = sum(s["active_slots"] for s in samples) / len(samples)
    ops, moved = cost.gdn_step_cost(obs["config"], slots)
    need = roofline_s(ops, moved, obs["device_kind"])
    print(f"gdn step: {calls} calls of {call_s * 1e6:.1f} us on the device, "
          f"{slots:.2f} slots live, {moved / 1e6:.2f} MB and "
          f"{ops / 1e6:.2f} MFLOP a call, roofline time {need * 1e6:.1f} us",
          flush=True)
    return 100.0 * need / call_s
