"""repro.obs.trace units: span recording, nesting, export, module scoping."""

import json

from repro.obs import trace


def test_null_tracer_is_inert():
    t = trace.NullTracer()
    assert not t.enabled
    with t.span("anything", foo=1):
        pass
    t.add_span("x", 0.0, 1.0)
    t.instant("y")
    assert t.export("/nonexistent/should/never/be/written.json") is None


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records entries."""

    log = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs

    def __enter__(self):
        self.log.append(("enter", self.name, self.kwargs))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, self.kwargs))
        return False


def test_null_tracer_enters_trace_annotation(monkeypatch):
    """With no tracer installed a span still reaches the profiler, by name
    alone: its args are neither formatted nor recorded."""
    monkeypatch.setattr(trace, "TraceAnnotation", _Recorder)
    _Recorder.log = []
    t = trace.NullTracer()
    with t.span("sim.engine", chunk=3) as args:
        assert args is None
        assert _Recorder.log == [("enter", "sim.engine", {})]
    assert _Recorder.log[-1] == ("exit", "sim.engine", {})


def test_span_tracer_annotates_and_records_extended_args(monkeypatch):
    monkeypatch.setattr(trace, "TraceAnnotation", _Recorder)
    _Recorder.log = []
    t = trace.SpanTracer()
    with t.span("sim.step", chunk=1) as args:
        args["events"] = 126
    assert [e[:2] for e in _Recorder.log] == [("enter", "sim.step"),
                                             ("exit", "sim.step")]
    (ev,) = t.events
    assert ev["args"] == {"chunk": 1, "events": 126}


def test_span_records_complete_event():
    t = trace.SpanTracer()
    with t.span("outer", key="v"):
        pass
    (ev,) = t.events
    assert ev["name"] == "outer" and ev["ph"] == "X"
    assert ev["dur"] >= 0.001 and ev["args"] == {"key": "v"}


def test_nested_spans_contained_in_time():
    t = trace.SpanTracer()
    with t.span("outer"):
        with t.span("inner"):
            pass
    by = {e["name"]: e for e in t.events}
    outer, inner = by["outer"], by["inner"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def test_add_span_synthetic_and_instant():
    """Explicit-timestamp spans keep their args; instants are markers."""
    t = trace.SpanTracer()
    t.add_span("event", 10.0, 5.0, args={"chunk": 2})
    t.add_span("degenerate", 0.0, 0.0)  # dur clamped to a visible sliver
    t.instant("marker", n=3)
    by = {e["name"]: e for e in t.events}
    assert by["event"]["args"] == {"chunk": 2}
    assert by["degenerate"]["dur"] == 0.001
    assert by["marker"]["ph"] == "i"


def test_export_chrome_trace_json(tmp_path):
    t = trace.SpanTracer()
    t.add_span("b", 5.0, 1.0)
    t.add_span("a", 1.0, 10.0)
    path = t.export(str(tmp_path / "sub" / "trace.json"))  # creates parents
    doc = json.load(open(path))
    assert doc["otherData"]["schema_version"] == trace.TRACE_SCHEMA_VERSION
    assert doc["otherData"]["producer"] == "repro.obs.trace"
    evs = doc["traceEvents"]
    # sorted by (tid, ts) as Perfetto's importer expects
    assert [e["name"] for e in evs] == ["a", "b"]
    for e in evs:
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)


def test_module_tracer_scoping(tmp_path):
    assert not trace.get_tracer().enabled  # default is the null tracer
    out = tmp_path / "t.json"
    with trace.tracing(str(out)) as t:
        assert trace.get_tracer() is t
        with trace.get_tracer().span("scoped"):
            pass
    assert not trace.get_tracer().enabled  # restored on exit
    assert json.load(open(out))["traceEvents"][0]["name"] == "scoped"


def test_set_tracer_returns_previous():
    live = trace.SpanTracer()
    prev = trace.set_tracer(live)
    try:
        assert trace.get_tracer() is live
    finally:
        trace.set_tracer(prev)
    assert trace.get_tracer() is prev
