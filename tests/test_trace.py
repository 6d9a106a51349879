"""Span tracer (core/trace.py): ids/parenting, cross-thread attach,
always-on ring, capture buffer, Chrome export with flow events, and the
profiler.RecordEvent absorption. See docs/observability.md."""
import json
import threading

import pytest

import paddle_tpu as paddle  # noqa: F401 — flags registered
from paddle_tpu.core import trace


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.reset()
    yield
    if trace.enabled():
        trace.stop()
    trace.reset()


def test_span_nesting_and_ids():
    with trace.span("outer", kind="test") as outer:
        assert trace.current() == (outer.trace_id, outer.span_id)
        with trace.span("inner") as inner:
            assert inner.parent_id == outer.span_id
            assert inner.trace_id == outer.trace_id
        with trace.span("inner2") as inner2:
            assert inner2.parent_id == outer.span_id
    assert trace.current() is None
    assert outer.t1 is not None and outer.t1 >= outer.t0
    assert outer.attrs["kind"] == "test"
    # separate roots get separate traces
    with trace.span("other") as other:
        assert other.trace_id != outer.trace_id
        assert other.parent_id is None


def test_span_exception_records_error_and_reraises():
    with pytest.raises(ValueError):
        with trace.span("boom") as sp:
            raise ValueError("x")
    assert sp.attrs["error"] == "ValueError"
    assert sp.t1 is not None  # finished despite the exception


def test_ring_is_bounded_and_always_on():
    trace.set_ring_size(8)
    try:
        assert not trace.enabled()  # ring records even without capture
        for i in range(20):
            trace.instant(f"e{i}")
        recent = trace.recent()
        assert len(recent) == 8
        assert recent[-1].name == "e19"  # newest last
        assert trace.recent(3)[0].name == "e17"
    finally:
        trace.set_ring_size(4096)


def test_capture_buffer_only_between_start_stop():
    trace.instant("before")
    trace.start()
    trace.instant("during")
    spans = trace.stop()
    trace.instant("after")
    assert [s.name for s in spans] == ["during"]
    assert {s.name for s in trace.recent()} >= {"before", "during",
                                                "after"}


def test_attach_joins_worker_thread_to_trace():
    out = {}
    with trace.span("driver") as sp:
        ctx = trace.current()

        def worker():
            with trace.attach(ctx):
                with trace.span("work") as w:
                    out["w"] = w
            out["after"] = trace.current()

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert out["w"].trace_id == sp.trace_id
    assert out["w"].parent_id == sp.span_id
    assert out["after"] is None          # attach scope fully popped
    assert out["w"].tid != sp.tid        # genuinely another thread


def test_remote_parent_tuple_propagates_trace_id():
    # the PS server resolves the client-shipped (trace_id, span_id)
    with trace.span("handler", parent=("cafe-1", "cafe-2")) as sp:
        assert sp.trace_id == "cafe-1"
        assert sp.parent_id == "cafe-2"


def test_chrome_export_slices_flows_and_thread_names(tmp_path):
    trace.start()
    with trace.span("dispatch", step=0) as d:
        d.flow(41, "s")
    with trace.span("retire") as r:
        r.flow(41, "t")
    with trace.span("materialize") as m:
        m.flow(41, "f")
    trace.stop()
    path = str(tmp_path / "trace.json")
    trace.export_chrome_trace(path, spans=[d, r, m])
    data = json.load(open(path))
    ev = data["traceEvents"]
    slices = [e for e in ev if e["ph"] == "X"]
    flows = [e for e in ev if e.get("cat") == "flow"]
    metas = [e for e in ev if e["ph"] == "M"]
    assert {e["name"] for e in slices} == {"dispatch", "retire",
                                           "materialize"}
    assert [e["ph"] for e in flows] == ["s", "t", "f"]
    assert all(e["id"] == 41 for e in flows)
    assert flows[-1]["bp"] == "e"
    assert metas and metas[0]["args"]["name"]
    # slice args carry span identity + attrs
    disp = next(e for e in slices if e["name"] == "dispatch")
    assert disp["args"]["step"] == 0
    assert disp["args"]["trace_id"] == d.trace_id
    # flow ts binds inside its slice
    assert disp["ts"] <= flows[0]["ts"] <= disp["ts"] + disp["dur"]


def test_record_event_missed_end_cannot_corrupt_parentage():
    """Legacy begin()/end() callers (tape.py per-op annotations) skip
    end() when the op raises; the RecordEvent span is detached, so the
    leak costs one sample — NOT a dead ancestor for every later span."""
    from paddle_tpu import profiler as prof
    prof.start_profiler()
    try:
        prof.RecordEvent("op/leaky").begin()   # end() never called
        assert trace.current() is None          # ambient stack untouched
        with trace.span("after") as sp:
            assert sp.parent_id is None         # fresh root, not 'leaky'
    finally:
        prof.stop_profiler()
    prof.reset_profiler()


def test_record_event_absorbed_into_tracer():
    from paddle_tpu import profiler as prof
    prof.reset_profiler()
    ring_before = len(trace.recent())
    rec = prof.RecordEvent("cheap")
    rec.begin()
    rec.end()
    # disabled profiler: RecordEvent stays a no-op (hot per-op sites)
    assert len(trace.recent()) == ring_before
    assert prof.events() == []
    prof.start_profiler()
    try:
        with trace.span("outer") as outer:
            with prof.RecordEvent("annotated"):
                pass
        names = [e[0] for e in prof.events()]
        # RecordEvent became a span nested under the ambient one...
        sp = next(s for s in trace.recent() if s.name == "annotated")
        assert sp.parent_id == outer.span_id
        # ...and first-class trace spans reach the profiler table too
        assert "annotated" in names and "outer" in names
        assert "annotated" in prof.summary()
    finally:
        prof.stop_profiler()
    prof.reset_profiler()


# --------------------------------------------------------------------------
# the bridge: every span is also a jax.profiler.TraceAnnotation
# --------------------------------------------------------------------------

def _host_events(trace_dir):
    """{name: [(start_ns, end_ns, {stat: value}, line index)]} of the
    xplane's /host:CPU plane under `trace_dir`."""
    import glob

    from jax.profiler import ProfileData
    (path,) = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats), k))
    return out


@pytest.fixture
def profiler_session(tmp_path):
    """A jax.profiler session with the options the benchmark uses
    (benchmark/lib/profiler.py); yields a function that stops it and
    returns the host plane's events."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    stopped = []

    def stop():
        jax.profiler.stop_trace()
        stopped.append(True)
        return _host_events(tmp_path)

    yield stop
    if not stopped:
        jax.profiler.stop_trace()


def test_span_lands_on_the_profiler_timeline(profiler_session):
    """Inside a profiler session a span is an event of /host:CPU with
    its name, start, duration and scalar attributes; a child lies inside
    its parent on the same thread's line."""
    import time
    with trace.span("bridge/outer", beat=7, share=0.5, kind="decode",
                    blob=[1, 2], long="x" * 65) as outer:
        time.sleep(0.002)
        with trace.span("bridge/inner", req=3):
            time.sleep(0.001)
    events = profiler_session()
    (o0, o1, ostats, oline), = events["bridge/outer"]
    (i0, i1, istats, iline), = events["bridge/inner"]
    assert ostats == {"beat": 7, "share": 0.5, "kind": "decode"}
    assert istats == {"req": 3}
    assert oline == iline and o0 <= i0 and i1 <= o1
    assert i1 - i0 >= 1e6 and o1 - o0 >= 3e6       # ns: the sleeps
    # the two clocks agree on the duration to well under a millisecond
    assert abs((o1 - o0) * 1e-6 - outer.duration_ms) < 0.5
    # and the ring holds the same spans, attributes whole
    ring = {s.name: s for s in trace.recent()}
    assert ring["bridge/outer"].attrs["blob"] == [1, 2]
    assert ring["bridge/inner"].parent_id == outer.span_id


def test_bridge_spans_of_two_threads_sit_on_two_lines(profiler_session):
    def worker():
        with trace.span("bridge/worker"):
            pass

    with trace.span("bridge/main"):
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    events = profiler_session()
    assert events["bridge/main"][0][3] != events["bridge/worker"][0][3]


def test_discard_double_end_and_cross_order_close_the_annotation(
        profiler_session):
    """Every way end() tolerates leaves no annotation open: the profiler
    gets one closed event per span, the discarded one included (the ring
    is what `discard` spares)."""
    a = trace.begin("bridge/a")
    b = trace.begin("bridge/b")
    trace.end(a)                     # out of order: a before its child b
    trace.end(b)
    trace.end(b)                     # second end: a no-op
    d = trace.begin("bridge/discarded")
    trace.end(d, discard=True)
    det = trace.begin("bridge/detached", _attach=False)
    trace.end(det)
    assert all(sp._annotation is None for sp in (a, b, d, det))
    assert trace.open_spans() == [] and trace.current() is None
    events = profiler_session()
    for name in ("bridge/a", "bridge/b", "bridge/discarded",
                 "bridge/detached"):
        assert len(events[name]) == 1, name
    assert "bridge/discarded" not in {s.name for s in trace.recent()}


def test_without_a_session_the_ring_behaves_as_before():
    """No profiler running: the annotation is a no-op and spans reach
    the ring, the capture buffer and recent() exactly as they did."""
    trace.start()
    with trace.span("quiet/outer", n=1) as outer:
        with trace.span("quiet/inner") as inner:
            pass
    spans = trace.stop()
    assert [s.name for s in spans] == ["quiet/inner", "quiet/outer"]
    assert [s.name for s in trace.recent(2)] == ["quiet/inner",
                                                 "quiet/outer"]
    assert inner.parent_id == outer.span_id and outer.attrs == {"n": 1}
    assert outer._annotation is None and inner._annotation is None


def test_core_trace_is_the_only_span_api_of_the_package():
    """No direct TraceAnnotation (or a second tracer) elsewhere in
    paddle_tpu/: the bridge in core/trace.py is the one place."""
    import os
    import re
    root = os.path.dirname(os.path.abspath(paddle.__file__))
    hits = []
    for dirpath, _dirs, files in os.walk(root):
        for f in files:
            path = os.path.join(dirpath, f)
            if f.endswith(".py") and not path.endswith(
                    os.path.join("core", "trace.py")):
                with open(path) as fh:
                    if re.search(r"TraceAnnotation\(|TraceMe\(", fh.read()):
                        hits.append(os.path.relpath(path, root))
    assert hits == []
