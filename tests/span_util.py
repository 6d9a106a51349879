"""Shared by the span tests (test_serving.py, test_hapi_model.py)."""


def self_times(spans):
    """{span_id: self seconds} for core/trace spans of ONE thread: each
    span's duration minus what the spans directly beneath it cover
    (nesting read from the clock, as a profiler's timeline shows it)."""
    out = {sp.span_id: sp.t1 - sp.t0 for sp in spans}
    stack = []
    for sp in sorted(spans, key=lambda sp: (sp.t0, -(sp.t1 - sp.t0))):
        while stack and stack[-1].t1 <= sp.t0:
            stack.pop()
        if stack:
            out[stack[-1].span_id] -= sp.t1 - sp.t0
        stack.append(sp)
    return out
