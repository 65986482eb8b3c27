"""`repro.telemetry` — the unified tracing/metrics spine (DESIGN.md §2.9).

Zero-dependency structured observability for every layer of the runtime:
`Recorder` (counters / gauges / histograms with labeled series), `span()`
context-manager tracing on monotonic clocks, and pluggable sinks (JSONL
stream, in-memory ring, Chrome-trace/Perfetto export). Instrumentation
sites across session / orchestrator / serve / cluster / kernels call
``telemetry.get()`` — the active recorder, or the no-op `NULL` recorder
when telemetry is off, which keeps the off path bit-identical to
uninstrumented code. Every span, on or off, is also a
``jax.profiler.TraceAnnotation``: program spans appear in profiler traces.

Typical wiring (the launchers' ``--telemetry out.jsonl``)::

    from repro import telemetry
    rec = telemetry.configure(jsonl="run.jsonl")   # becomes the active
    ... run ...                                    # recorder process-wide
    telemetry.shutdown()                           # flush + deactivate

    # offline: fold the stream into the goodput table + a Perfetto trace
    #   python -m repro.launch.telemetry_report run.jsonl --perfetto t.json

Scoped activation for tests/benchmarks::

    rec = Recorder(sinks=[MemorySink()])
    with telemetry.recording(rec):
        ...                           # instrumented code records into rec
    rec.spans("session.step")         # query the ring
"""
from __future__ import annotations

import atexit
from contextlib import contextmanager
from typing import Optional

from repro.telemetry.export import (
    chrome_trace, load_jsonl, summarize_hist, write_chrome_trace,
)
from repro.telemetry.recorder import (
    EVENT_KEYS, EVENT_KINDS, NULL, NullRecorder, Recorder, Span,
)
from repro.telemetry.sinks import JsonlSink, MemorySink

__all__ = [
    "Recorder", "NullRecorder", "Span", "NULL", "EVENT_KEYS", "EVENT_KINDS",
    "JsonlSink", "MemorySink",
    "chrome_trace", "write_chrome_trace", "load_jsonl", "summarize_hist",
    "get", "set_active", "configure", "recording", "shutdown",
]

_active = NULL
_atexit_registered = False


def get():
    """The active recorder (`NULL` when telemetry is off). Instrumentation
    sites call this per use — activation is dynamic, never cached."""
    return _active


def set_active(rec) -> None:
    """Install ``rec`` as the process-wide active recorder (None → off)."""
    global _active
    _active = NULL if rec is None else rec


def configure(*, jsonl: Optional[str] = None, memory: bool = False,
              memory_maxlen: Optional[int] = 65536, clock=None) -> Recorder:
    """Build a `Recorder` with the requested sinks, make it active, and
    flush it at interpreter exit. ``jsonl`` adds a `JsonlSink` at that path;
    ``memory=True`` adds a `MemorySink` ring (for in-process queries)."""
    global _atexit_registered
    sinks = []
    if jsonl is not None:
        sinks.append(JsonlSink(jsonl))
    if memory:
        sinks.append(MemorySink(maxlen=memory_maxlen))
    kw = {} if clock is None else {"clock": clock}
    rec = Recorder(sinks=sinks, **kw)
    set_active(rec)
    if not _atexit_registered:
        atexit.register(shutdown)
        _atexit_registered = True
    return rec


def shutdown() -> None:
    """Flush + close the active recorder's sinks and deactivate it."""
    global _active
    rec, _active = _active, NULL
    rec.close()


@contextmanager
def recording(rec):
    """Scoped activation: ``rec`` is active inside the block, the previous
    recorder is restored on exit (exception-safe)."""
    global _active
    prev = _active
    _active = NULL if rec is None else rec
    try:
        yield rec
    finally:
        _active = prev
