"""`repro.telemetry` core — recorder / sinks / export unit tests (ISSUE 8).

Host-side, no mesh, deterministic via an injected clock. What's pinned:

* the FIXED per-kind event schema (`EVENT_KEYS`) — every emitted event
  carries exactly those keys, nothing else (the JSONL stream is a contract
  the offline report and the Perfetto exporter both parse);
* counter totals per labeled series, span marks/attrs, and span emission
  on ``__exit__`` even when an exception propagates (transition spans must
  survive a `DeadReplicaError` raised mid-apply);
* the NULL recorder off path: ``enabled`` False, every method a no-op
  but ``span``, which is only a profiler trace annotation;
* every span, recorder on or off, lands in a ``jax.profiler`` trace under
  its own name, with its labels, while the JSONL event keeps its schema;
* scoped activation (`recording`) restore, exception-safe;
* `JsonlSink` lazy open + `load_jsonl` round-trip (+ corrupt-line error
  with a line number), `MemorySink` ring bounds and label-subset queries;
* the Chrome-trace mapping (span → ph "X" µs rows, gauge/counter →
  ph "C" tracks on the running total, hist skipped, one tid per dotted
  subsystem prefix).
"""
import json

import pytest

from repro import telemetry
from repro.telemetry import (
    EVENT_KEYS, EVENT_KINDS, JsonlSink, MemorySink, NULL, NullRecorder,
    Recorder, chrome_trace, load_jsonl, summarize_hist, write_chrome_trace,
)


class FakeClock:
    """Deterministic monotonic clock: advances only when told to."""

    def __init__(self):
        self.t = 100.0  # nonzero start: t0-relative timestamps must subtract

    def __call__(self):
        return self.t

    def tick(self, dt=1.0):
        self.t += dt


def make_rec():
    clock = FakeClock()
    sink = MemorySink()
    return Recorder(sinks=[sink], clock=clock), sink, clock


# ---------------------------------------------------------------------------
# event schema

def test_event_schema_is_fixed():
    rec, sink, clock = make_rec()
    rec.counter("c", 2, a="x")
    rec.gauge("g", 0.5, b="y")
    rec.hist("h", 3.0)
    with rec.span("s", c="z") as sp:
        clock.tick()
        sp.set(k=1).mark("phase")
    evs = {e["kind"]: e for e in sink.events()}
    assert set(evs) == set(EVENT_KINDS)
    for kind, ev in evs.items():
        assert tuple(sorted(ev)) == tuple(sorted(EVENT_KEYS[kind])), kind


def test_timestamps_are_recorder_relative():
    rec, sink, clock = make_rec()
    clock.tick(5.0)
    rec.gauge("g", 1.0)
    assert sink.events()[0]["t"] == 5.0   # not the raw clock's 105.0


def test_counter_totals_per_labeled_series():
    rec, sink, _ = make_rec()
    assert rec.counter("n", a="x") == 1
    assert rec.counter("n", 2, a="x") == 3
    assert rec.counter("n", a="y") == 1           # distinct series
    assert rec.total("n", a="x") == 3
    assert rec.total("n", a="y") == 1
    assert rec.total("never") == 0
    # every increment carries the running total (stream cut-anywhere safety)
    assert [e["total"] for e in sink.events(name="n", a="x")] == [1, 3]


def test_span_marks_attrs_and_duration():
    rec, sink, clock = make_rec()
    with rec.span("work", stage="0") as sp:
        clock.tick(2.0)
        sp.mark("planned")
        clock.tick(3.0)
        sp.set(bytes_moved=1024)
    (ev,) = sink.spans("work")
    assert ev["dur"] == 5.0
    assert ev["labels"] == {"stage": "0"}
    assert ev["attrs"]["bytes_moved"] == 1024
    assert ev["attrs"]["marks"] == {"planned": 2.0}


def test_span_emits_when_exception_propagates():
    """A transition span must land in the stream even when apply() raises
    (DeadReplicaError mid-span is the 'rejected' bucket in the report)."""
    rec, sink, clock = make_rec()
    with pytest.raises(RuntimeError):
        with rec.span("session.transition", kind="failure") as sp:
            sp.mark("planned")
            clock.tick()
            raise RuntimeError("replica dead")
    (ev,) = sink.spans("session.transition")
    assert ev["dur"] == 1.0
    assert "changed" not in ev["attrs"]  # never finished -> rejected bucket


# ---------------------------------------------------------------------------
# null recorder (the off path)

def test_null_recorder_is_inert():
    assert NULL.enabled is False
    assert isinstance(NULL, NullRecorder)
    assert NULL.counter("x") == 0
    assert NULL.gauge("x", 1.0) is None
    assert NULL.hist("x", 1.0) is None
    assert NULL.total("x") == 0
    # the off-path span records nothing: set/mark return the span itself,
    # and no sink exists to receive an event
    with NULL.span("x", k="v") as sp:
        assert sp.set(a=1) is sp
        assert sp.mark("p") is sp
    assert NULL.sinks == [] and not hasattr(sp, "attrs")


def test_spans_annotate_the_profiler_trace(tmp_path):
    """Spans reach the profiler's host plane, on the device ops' clock,
    with the recorder off (the NULL span) and on; the recorded JSONL event
    is unchanged by the annotation."""
    import jax
    from jax.profiler import ProfileData

    rec, sink, _ = make_rec()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with NULL.span("test.off", k="v") as sp:
            sp.set(a=1)
        with rec.span("test.on", n=3):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    host = [pl for pl in ProfileData.from_file(str(path)).planes
            if pl.name == "/host:CPU"]
    seen = {ev.name: dict(ev.stats) for pl in host for ln in pl.lines
            for ev in ln.events if ev.name.startswith("test.")}
    assert seen == {"test.off": {"k": "v"}, "test.on": {"n": 3}}
    (ev,) = sink.spans("test.on")
    assert tuple(ev) == EVENT_KEYS["span"]
    assert ev["labels"] == {"n": 3} and ev["attrs"] == {}


def test_get_defaults_to_null_and_recording_restores():
    assert telemetry.get() is NULL
    rec, sink, _ = make_rec()
    with telemetry.recording(rec):
        assert telemetry.get() is rec
        telemetry.get().gauge("g", 1.0)
    assert telemetry.get() is NULL
    assert len(sink) == 1
    # exception-safe restore
    with pytest.raises(ValueError):
        with telemetry.recording(rec):
            raise ValueError
    assert telemetry.get() is NULL
    # recording(None) scopes telemetry OFF
    with telemetry.recording(rec):
        with telemetry.recording(None):
            assert telemetry.get() is NULL
        assert telemetry.get() is rec


def test_configure_and_shutdown(tmp_path):
    path = str(tmp_path / "t.jsonl")
    rec = telemetry.configure(jsonl=path, memory=True)
    try:
        assert telemetry.get() is rec
        rec.gauge("g", 2.0)
    finally:
        telemetry.shutdown()
    assert telemetry.get() is NULL
    evs = load_jsonl(path)
    assert [e["value"] for e in evs] == [2.0]


# ---------------------------------------------------------------------------
# sinks

def test_jsonl_sink_lazy_open_and_roundtrip(tmp_path):
    path = tmp_path / "out.jsonl"
    rec = Recorder(sinks=[JsonlSink(str(path))], clock=FakeClock())
    assert not path.exists()          # configuring never creates empty files
    rec.counter("c", a="x")
    with rec.span("s"):
        pass
    rec.close()
    evs = load_jsonl(str(path))
    assert [e["kind"] for e in evs] == ["counter", "span"]
    assert evs[0]["labels"] == {"a": "x"}
    # compact separators: no spaces after , or :
    raw = path.read_text().splitlines()[0]
    assert ", " not in raw and ": " not in raw


def test_load_jsonl_names_corrupt_line(tmp_path):
    path = tmp_path / "bad.jsonl"
    path.write_text('{"kind":"gauge"}\n\nnot json\n')
    with pytest.raises(ValueError, match=r"bad\.jsonl:3"):
        load_jsonl(str(path))


def test_memory_sink_ring_and_queries():
    sink = MemorySink(maxlen=3)
    rec = Recorder(sinks=[sink], clock=FakeClock())
    for i in range(5):
        rec.gauge("g", float(i), run="a" if i % 2 == 0 else "b")
    assert len(sink) == 3             # oldest dropped
    assert sink.values("g") == [2.0, 3.0, 4.0]
    assert sink.values("g", run="b") == [3.0]   # label-subset match
    assert sink.values("missing") == []
    with rec.span("sp", run="a"):
        pass
    assert sink.durations("sp", run="a") == [0.0]
    sink.clear()
    assert len(sink) == 0


def test_recorder_queries_require_memory_sink():
    rec = Recorder(sinks=[])
    with pytest.raises(LookupError, match="MemorySink"):
        rec.values("g")


# ---------------------------------------------------------------------------
# export

def test_chrome_trace_mapping():
    rec, sink, clock = make_rec()
    with rec.span("session.step", pp=1) as sp:
        clock.tick(0.002)
        sp.set(bytes_moved=64)
    rec.counter("kernels.dispatch", kernel="rmsnorm")
    rec.counter("kernels.dispatch", kernel="rmsnorm")
    rec.gauge("train.goodput", 0.75, policy="ntp")
    rec.hist("serve.ttft", 3.0)
    doc = chrome_trace(sink.events())
    rows = doc["traceEvents"]
    meta = [r for r in rows if r["ph"] == "M"]
    spans = [r for r in rows if r["ph"] == "X"]
    counters = [r for r in rows if r["ph"] == "C"]
    # one swimlane per dotted subsystem prefix
    assert {m["args"]["name"] for m in meta} == {"session"}
    (sp_row,) = spans
    assert sp_row["name"] == "session.step"
    assert sp_row["dur"] == pytest.approx(2000.0)       # µs
    assert sp_row["args"] == {"pp": 1, "bytes_moved": 64}
    # counters plot the RUNNING TOTAL; labels fold into the track name
    tracks = {r["name"]: r["args"]["value"] for r in counters}
    assert tracks["kernels.dispatch{kernel=rmsnorm}"] == 2  # last total wins
    assert tracks["train.goodput{policy=ntp}"] == 0.75
    # hist events have no Chrome-trace counterpart
    assert not any("ttft" in r["name"] for r in rows)


def test_write_chrome_trace_is_loadable(tmp_path):
    rec, sink, clock = make_rec()
    with rec.span("a.b"):
        clock.tick()
    path = tmp_path / "trace.json"
    doc = write_chrome_trace(str(path), sink.events())
    with open(path) as f:
        loaded = json.load(f)
    assert loaded == doc
    assert loaded["displayTimeUnit"] == "ms"


def test_summarize_hist():
    assert summarize_hist([]) is None
    s = summarize_hist([1.0, 2.0, 3.0, 4.0])
    assert s["count"] == 4 and s["mean"] == 2.5 and s["max"] == 4.0
    assert s["p50"] == 2.5
