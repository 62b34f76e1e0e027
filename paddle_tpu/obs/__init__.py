"""Unified telemetry plane: trace spans, always on, and the operator
plane (metric registry, event log, flight recorder, SLO engine, httpd,
``obs.perf``) gated by ``PT_OBS={off,on}``.

**Spans.**  One process-wide :class:`Tracer` (:func:`tracer`) exists
whatever ``PT_OBS`` says; :func:`span` and :func:`instant` always
record into its bounded in-memory ring, on ``time.perf_counter``, with
the id of the span that was open when they began (``parent``).  A span
is two clock reads, one small object and one ``deque.append``; the
budget is a handful per ``ServingEngine.step()`` / train step and none
per token, page or eager op.  ``obs.tracer().export_chrome(path)``
writes them out.

**Operator plane.**  One process-wide bundle (:func:`handle`) holds the
other surfaces; it is OFF by default and the off path is one cached
``None`` check per producer site — bit-identical behavior (asserted by
tests/test_obs.py's parity test).

Producer idiom (hot paths cache the handle)::

    from paddle_tpu import obs

    with obs.span("train.step", cat="train"):   # always recorded
        ...

    h = obs.handle()
    if h is not None:
        h.recorder.record("serve.preempt", rid=req.rid)
        h.registry.counter("serve_preemptions_total").inc()

Export surfaces:

- ``obs.tracer().export_chrome(path)`` — Perfetto-viewable
- ``obs.handle().registry.prometheus_text()`` / ``.snapshot()``
- ``obs.dump(path)`` — flight-recorder JSON lines; crash paths
  (``GuardianAbort``, request failure) call :func:`auto_dump`, which
  also writes a file per dump under ``$PT_OBS_DUMP_DIR`` when set.

Tests swap the layer on/off in-process via :func:`configure`
(optionally with a deterministic :class:`LogicalClock`); ``reset()``
returns to the environment-driven default.
"""
from __future__ import annotations

import os
import threading
import time

from .events import EventLog
from .flight import FlightRecorder
from .registry import MetricRegistry
from .trace import LogicalClock, Span, Tracer

__all__ = [
    "EventLog", "FlightRecorder", "LogicalClock", "MetricRegistry",
    "Span", "Tracer", "auto_dump", "beat", "configure", "dump",
    "enabled", "event", "handle", "instant", "perf", "reset", "span",
    "tracer",
]

_MODES = ("off", "on")

_lock = threading.Lock()
_handle = None        # _Obs | None (None = operator plane off)
_initialized = False  # PT_OBS read yet?
_tracer = Tracer()    # the one tracer: records with PT_OBS off too
_tracer.listen_for_compiles()


def tracer():
    """The process-wide :class:`Tracer` every span and instant goes to,
    with ``PT_OBS`` off too."""
    return _tracer


class _Obs:
    """The live operator plane: one clock feeding one registry, one
    flight recorder, and one structured event log (the flight ring tees
    into the log), plus the health-plane state (heartbeats, SLO
    engines, ``/statusz`` providers, HTTP server).  ``tracer`` is the
    process-wide one, on the same clock."""

    def __init__(self, clock=None, flight_capacity=512, events_path=None,
                 events_max_bytes=262144, events_max_files=3,
                 events_capacity=4096):
        self.clock = clock if clock is not None else time.perf_counter
        self.registry = MetricRegistry()
        self.tracer = _tracer
        if events_path is None:
            events_path = os.environ.get("PT_OBS_EVENT_LOG") or None
        self.events = EventLog(clock=self.clock, path=events_path,
                               max_bytes=events_max_bytes,
                               max_files=events_max_files,
                               capacity=events_capacity)
        self.recorder = FlightRecorder(clock=self.clock,
                                       capacity=flight_capacity,
                                       sink=self.events.from_flight)
        self.heartbeats = {}    # component -> last-beat timestamp
        self.slo_engines = []   # live health.SLOEngine instances
        self.statusz = {}       # provider name -> payload callable
        self.httpd = None
        port = os.environ.get("PT_OBS_HTTP")
        if port:
            from . import httpd as _httpd

            self.httpd = _httpd.ObsHTTPServer(port=int(port))

    def close(self):
        if self.httpd is not None:
            self.httpd.stop()
            self.httpd = None
        self.events.close()


def _env_mode():
    mode = os.environ.get("PT_OBS", "off").lower()
    if mode not in _MODES:
        raise ValueError(f"PT_OBS={mode!r}: expected off|on")
    return mode


def handle():
    """The live :class:`_Obs` bundle, or ``None`` when the operator
    plane is off — the single branch every producer pays on the off
    path."""
    global _handle, _initialized
    if not _initialized:
        with _lock:
            if not _initialized:
                _handle = _Obs() if _env_mode() == "on" else None
                _initialized = True
    return _handle


def enabled():
    return handle() is not None


def configure(mode="on", clock=None, flight_capacity=512,
              trace_capacity=65536, annotate=True, events_path=None,
              events_max_bytes=262144, events_max_files=3,
              events_capacity=4096):
    """Programmatic gate (tests): rebuild the bundle
    regardless of ``PT_OBS``, and put the tracer on ``clock`` with an
    empty ring in either mode.  Returns the new handle (None for
    ``mode="off"``).  Producers that cached a handle at construction
    (EngineMetrics, Scheduler) keep the old one — reconfigure BEFORE
    building the objects under test."""
    global _handle, _initialized
    if mode not in _MODES:
        raise ValueError(f"obs.configure mode={mode!r}: expected off|on")
    with _lock:
        old = _handle
        _tracer.configure(clock=clock or time.perf_counter,
                          capacity=trace_capacity, annotate=annotate)
        _handle = (_Obs(clock=clock, flight_capacity=flight_capacity,
                        events_path=events_path,
                        events_max_bytes=events_max_bytes,
                        events_max_files=events_max_files,
                        events_capacity=events_capacity)
                   if mode == "on" else None)
        _initialized = True
    if old is not None:
        old.close()
    return _handle


def reset():
    """Drop all telemetry state (the tracer's ring too, back on the
    wall clock); the next :func:`handle` re-reads ``PT_OBS``."""
    global _handle, _initialized
    with _lock:
        old = _handle
        _handle = None
        _initialized = False
        _tracer.configure()
    if old is not None:
        old.close()
    perf.reset()


# -- thin producer helpers: spans always; the rest no-ops when off ------

span = _tracer.span
instant = _tracer.instant


def event(kind, **fields):
    h = handle()
    if h is not None:
        h.recorder.record(kind, **fields)


def beat(name, now=None):
    """Heartbeat for ``/healthz`` staleness: stamp component ``name``
    as alive.  Hot loops pass ``now`` (a timestamp they already read)
    to avoid an extra clock read."""
    h = handle()
    if h is not None:
        h.heartbeats[name] = h.clock() if now is None else now


def dump(path=None, reason="manual"):
    """Explicit flight-recorder dump; returns the JSON-lines text, or
    ``None`` when telemetry is off."""
    h = handle()
    if h is None:
        return None
    return h.recorder.dump(path=path, reason=reason)


def auto_dump(reason, extra=None):
    """Crash-path dump (GuardianAbort, request failure).  Keeps the
    text on ``recorder.last_dump``; additionally writes one file per
    dump under ``$PT_OBS_DUMP_DIR`` when that is set."""
    h = handle()
    if h is None:
        return None
    path = None
    dump_dir = os.environ.get("PT_OBS_DUMP_DIR")
    if dump_dir:
        os.makedirs(dump_dir, exist_ok=True)
        safe = "".join(c if c.isalnum() or c in "-_." else "-"
                       for c in reason)
        path = os.path.join(dump_dir,
                            f"flight-{h.recorder.dumps}-{safe}.jsonl")
    return h.recorder.dump(path=path, reason=reason, extra=extra)


from . import perf  # noqa: E402,F401  (imports obs lazily; keep last)
