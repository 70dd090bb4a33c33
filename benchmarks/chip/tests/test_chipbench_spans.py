"""Idle time put down to the program's `ame.` spans, checked by hand on a
made-up trace, and the span reader on a real trace made on the CPU."""
import jax
import pytest

import tiny  # also puts the benchmark's package on the path
from chipbench import spans as sp
from chipbench import trace
from chipbench.trace import WINDOW, Ev

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _trace():
    """Window [1000, 11000] ns; the device runs [2000, 3000] and [5000,
    7000], so it idles [1000, 2000], [3000, 5000] and [7000, 11000]."""
    events = [Ev(HOST, "python3", WINDOW, 1000, 10000),
              Ev(DEV, "XLA Ops", "fusion.1", 2000, 1000),
              Ev(DEV, "XLA Ops", "copy.2", 5000, 2000)]
    spans = [
        # a query: submitted on a caller, queued [1200, 1400], run on A
        sp.Span("B", "ame.submit", 1100, 100, {"req": 1, "kind": "query", "rows": 1}),
        sp.Span("A", "ame.task", 1400, 2100,
                {"req": 1, "kind": "query", "backend": "latency", "queued_us": 0.2}),
        sp.Span("A", "ame.device", 1650, 1550, {"req": 1, "module": "jit_query_probed"}),
        # an insert: queued [3100, 3600], run on C
        sp.Span("B", "ame.submit", 3000, 100, {"req": 2, "kind": "insert", "rows": 4}),
        sp.Span("C", "ame.task", 3600, 2400,
                {"req": 2, "kind": "insert", "backend": "throughput", "queued_us": 0.5}),
        sp.Span("C", "ame.writer_wait", 3600, 100, {"req": 2}),
        sp.Span("C", "ame.device", 3700, 1800, {"req": 2, "module": "jit__insert"}),
        sp.Span("C", "ame.publish", 5500, 400, {"req": 2}),
        # maintenance submits a rebuild that runs after the window
        sp.Span("M", "ame.maintenance.trigger", 8000, 500,
                {"req": 3, "kind": "rebuild", "tombstones": 9, "spilled": 0}),
        sp.Span("M", "ame.submit", 8100, 300, {"req": 3, "kind": "rebuild", "rows": 1}),
    ]
    for s in spans:
        events.append(Ev(HOST, s.thread, s.name, s.start, s.dur))
    return events, spans


def test_idle_named_by_the_latest_active_span_or_the_queue():
    a = sp.attribute(*_trace())
    ns = {k: round(v * 1e9) for k, v in a.idle_by_span.items()}
    assert ns == {
        sp.NO_REQUEST: 3600,
        "ame.submit:query": 100, "ame.queued:query": 200,
        "ame.task:query": 550, "ame.device:query": 450,
        "ame.submit:insert": 100, "ame.queued:insert": 100,
        # the writer wait and its task start together: the shorter is inner
        "ame.writer_wait:insert": 100, "ame.device:insert": 1300,
        "ame.maintenance.trigger:rebuild": 200, "ame.submit:rebuild": 300}
    assert sum(a.idle_by_span.values()) == pytest.approx(a.idle_s)
    assert a.idle_s == pytest.approx(7000e-9)
    assert [(name, round(s * 1e9)) for name, s in a.gaps] == [
        (sp.NO_REQUEST, 4000), ("ame.device:insert", 2000), ("ame.device:query", 1000)]


def test_inflight_idle_counts_queued_and_running_requests():
    a = sp.attribute(*_trace())
    # in flight: [1200, 3500] and [3100, 6000]; idle inside: [1200, 2000], [3000, 5000]
    assert a.inflight_idle_s == pytest.approx(2800e-9)
    assert a.window_s == pytest.approx(10000e-9)


def test_span_self_time_and_device_wait():
    a = sp.attribute(*_trace())
    assert a.spans["ame.task"] == (2, pytest.approx(4500e-9), pytest.approx(650e-9))
    assert a.spans["ame.device"] == (2, pytest.approx(3350e-9), pytest.approx(3350e-9))
    assert a.spans["ame.maintenance.trigger"] == (
        1, pytest.approx(500e-9), pytest.approx(200e-9))
    assert a.spans["ame.submit"][0] == 3
    assert a.device_wait_ms == {"query": pytest.approx(1550e-6),
                                "insert": pytest.approx(1800e-6)}


def test_no_spans_attribute_nothing():
    events, _ = _trace()
    assert sp.attribute(events, []) is None


def test_reader_matches_the_trace_reduction(tmp_path):
    """On a real trace the reader gives `trace.events_from_file`'s events,
    so `SpanTracer` returns the `Summary` that `trace.Tracer` would."""
    f = jax.jit(lambda x: x * 2)
    x = jax.numpy.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(WINDOW):
            with jax.profiler.TraceAnnotation("ame.task", req=5, kind="query",
                                              backend="latency", queued_us=3.0):
                f(x).block_until_ready()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    events, spans = sp.read(path)
    assert events == trace.events_from_file(path)
    assert [(s.name, s.args) for s in spans] == [
        ("ame.task", {"req": 5, "kind": "query", "backend": "latency", "queued_us": 3.0})]
    assert sp.attribute(events, spans).spans["ame.task"][0] == 1


def test_span_tracer_reduces_as_the_tracer_does():
    tracer = sp.SpanTracer()
    tracer.start()
    with tracer.window():
        with jax.profiler.TraceAnnotation("ame.submit", req=1, kind="query", rows=1):
            pass
    summary = tracer.stop_and_reduce()
    assert isinstance(summary, trace.Summary) and summary.window_s > 0
    assert tracer.attribution.spans["ame.submit"][0] == 1


def test_a_traced_tiny_run_attributes_the_program_spans(monkeypatch):
    """The program's own spans, from a traced run of a tiny agent cell on
    the CPU (which has no device plane, so no idle time to name)."""
    tracers = []

    class Kept(sp.SpanTracer):
        def __init__(self):
            super().__init__()
            tracers.append(self)

    monkeypatch.setattr(trace, "Tracer", Kept)
    out = tiny.run(tiny.cell(tiny.TOPICS, tiny.AGENT), seconds=1.5, trace=True)
    assert out["correct"]
    a = tracers[-1].attribution
    assert {"ame.submit", "ame.task", "ame.writer_wait", "ame.device",
            "ame.publish"} <= set(a.spans)
    assert set(a.device_wait_ms) == {"query", "insert"}
    assert a.idle_s == a.inflight_idle_s == 0 and a.idle_by_span == {}
