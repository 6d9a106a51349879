"""Hand-written Pallas TPU kernels.

TPU-native analog of the reference's hand-written kernel tiers —
operators/math/ (~30k LoC of CPU/CUDA primitives) and operators/jit/
(runtime x86 codegen, reference jit/gen/jitcode.h:23). Where the reference
drops to CUDA/xbyak for the ops XLA-era compilers couldn't fuse, we drop to
Pallas for the ops XLA *still* can't schedule optimally: flash attention
(O(s) memory online-softmax attention), the fused linear+CE loss head, and
the single-query decode-attention kernel over StaticKVCache; train-path
kernels own their backward passes via jax.custom_vjp (the analog of
hand-written *_grad kernels).

Kernels run compiled on TPU and in Pallas interpreter mode elsewhere, so the
same code paths are testable on the CPU mesh (tests/conftest.py).

Every dispatch site goes through `run_guarded`, which counts the
engagement (`pallas.hit.{kernel}`) and leaves a span; a kernel whose gate
admitted the call runs or raises — a demotion to the jnp reference would
let a run measure a different program than it meant to and still exit 0.
The eligibility gates are the one way onto the
reference path: each rejection bumps `pallas.gate_reject.{kernel}.{reason}`
so output can report *why* a kernel didn't engage. The counters count
call-site engagements (once per trace under jit), not per-step executions.
Under jit a Mosaic refusal surfaces when the OUTER step compiles, outside
this function, as that step's error.
"""
from __future__ import annotations

from .flash_attention import flash_attention  # noqa: F401
from .decode_attention import decode_attention  # noqa: F401

__all__ = ["flash_attention", "decode_attention", "run_guarded",
           "gate_reject"]


def gate_reject(kernel: str, reason: str):
    """Record one eligibility-gate rejection (and return False so gates
    can `return gate_reject(k, r)`)."""
    from ...core import monitor, trace
    monitor.stat_add(f"pallas.gate_reject.{kernel}.{reason}")
    trace.instant("pallas/gate_reject", kernel=kernel, reason=reason)
    return False


def run_guarded(kernel: str, thunk, **attrs):
    """Run a Pallas kernel thunk, bumping pallas.hit.{kernel}; whatever it
    raises propagates. Every dispatch leaves a span with its outcome
    (hit / error+reason) and the caller's `attrs` in the trace ring."""
    from ...core import monitor, trace
    sp = trace.begin(f"pallas/{kernel}", **attrs)
    try:
        out = thunk()
    except Exception as e:
        sp.attrs["outcome"] = "error"
        sp.attrs["reason"] = type(e).__name__
        trace.end(sp)
        raise
    sp.attrs["outcome"] = "hit"
    trace.end(sp)
    monitor.stat_add(f"pallas.hit.{kernel}")
    return out
