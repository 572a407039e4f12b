"""Span tracing with a Chrome-trace-event (Perfetto) exporter.

``Tracer.span("prep")`` wraps a host-side phase of the serving loop in
a context manager that records one complete ("ph": "X") event: name,
thread id, start timestamp and duration in microseconds, the span open
beneath it on its thread (its ``parent``) and the window index ``t``,
inherited from the nearest ancestor that has one.  Spans are recorded
from ANY thread - the streaming driver's prefetch worker and serving
thread land on separate tracks, which is what makes the overlap/stall
story visible in a trace viewer - and recording is a single
``list.append`` (atomic under the GIL), so the prefetch queue is never
blocked by telemetry.

Open spans sit on a per-thread stack: ``open_span()`` is the innermost
one on the calling thread, which is how a component built without its
own bundle finds the one it is working for (``repro.obs.current``).
Parent and ``t`` tie the spans of one window together across threads:
the prefetch thread's ``prep`` of window t and the serving thread's
``serve``/``h2d``/``dispatch``/``dual_update`` of window t carry the
same ``t``, and a span's self time is its duration minus what the spans
naming it as parent cover.

``chrome_trace()``/``write()`` export the standard Chrome trace-event
JSON object format: load the file in Perfetto (https://ui.perfetto.dev)
or chrome://tracing and every run opens as one timeline, threads named
via ``thread_name`` metadata events.

``Tracer(annotate=True)`` additionally enters a
``jax.profiler.TraceAnnotation`` for every span: that is how the spans
reach the device clock.  When the driver also runs
``jax.profiler.trace`` (``launch/serve.py --profile-dir``, the
benchmark's traced runs) the host spans line up against XLA device
events in the same profile.

A disabled tracer (``Tracer(enabled=False)``, or the shared
``NULL_TRACER``) hands back ONE stateless no-op context manager:
``span`` costs a method call, allocates nothing, takes no locks.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation


class _NullSpan:
    """Shared no-op span of a disabled tracer (stateless, reentrant)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class SpanEvent(NamedTuple):
    """One recorded span (``Tracer.events``)."""

    name: str
    tid: int
    thread: str
    t0_ns: int
    dur_ns: int
    args: dict | None
    parent: str | None  # the span open beneath it on its thread
    t: int | None  # window index, its own or its nearest ancestor's


_open = threading.local()  # .stack: the spans open on this thread


def open_span():
    """The innermost span open on the calling thread, or None."""
    stack = getattr(_open, "stack", None)
    return stack[-1] if stack else None


class _Span:
    __slots__ = ("tracer", "name", "args", "t0", "annotation", "parent",
                 "t")

    def __init__(self, tracer, name, args):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.annotation = None

    def __enter__(self):
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        self.parent = stack[-1] if stack else None
        t = None if self.args is None else self.args.get("t")
        if t is None and self.parent is not None:
            t = self.parent.t
        self.t = t
        stack.append(self)
        if self.tracer.annotate:
            self.annotation = TraceAnnotation(self.name)
            self.annotation.__enter__()
        self.t0 = self.tracer.clock_ns()
        return self

    def __exit__(self, *exc):
        t1 = self.tracer.clock_ns()
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        _open.stack.pop()
        th = threading.current_thread()
        # one append; CPython list.append is atomic, no lock needed
        self.tracer.events.append(SpanEvent(
            self.name, th.ident, th.name, self.t0, t1 - self.t0,
            self.args, None if self.parent is None else self.parent.name,
            self.t))
        return False


class Tracer:
    """Collects host spans; exports Chrome trace-event JSON."""

    def __init__(self, enabled: bool = True, *, annotate: bool = False,
                 process_label: str | None = None,
                 clock_ns=time.perf_counter_ns):
        self.enabled = bool(enabled)
        self.annotate = bool(annotate)
        self.process_label = process_label
        self.clock_ns = clock_ns
        self.events: list[SpanEvent] = []
        self.owner = None  # the repro.obs.Obs this tracer records for

    def span(self, name: str, **args):
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, args or None)

    # -- export ----------------------------------------------------------

    def chrome_trace(self) -> dict:
        """The Chrome trace-event JSON object: ``traceEvents`` complete
        ("X") events in microseconds, each with its span's arguments
        plus ``args.parent`` and ``args.t`` where it has them, and
        ``thread_name`` metadata so Perfetto labels the serving and
        prefetch tracks."""
        pid = os.getpid()
        events = list(self.events)  # snapshot (other threads may append)
        out = []
        tids: dict[int, str] = {}
        for e in events:
            tids.setdefault(e.tid, e.thread)
            ev = {"name": e.name, "ph": "X", "pid": pid, "tid": e.tid,
                  "ts": e.t0_ns / 1e3, "dur": e.dur_ns / 1e3,
                  "cat": "host"}
            args = {k: _jsonable(v) for k, v in (e.args or {}).items()}
            if e.parent is not None:
                args["parent"] = e.parent
            if e.t is not None:
                args["t"] = _jsonable(e.t)
            if args:
                ev["args"] = args
            out.append(ev)
        meta = [{"name": "thread_name", "ph": "M", "pid": pid,
                 "tid": tid, "args": {"name": tname}}
                for tid, tname in sorted(tids.items())]
        if self.process_label:  # one named track group per host
            meta.insert(0, {"name": "process_name", "ph": "M",
                            "pid": pid,
                            "args": {"name": self.process_label}})
        return {"traceEvents": meta + out, "displayTimeUnit": "ms"}

    def write(self, path: str) -> str:
        """Write the trace JSON; open the file in ui.perfetto.dev."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


def _jsonable(v):
    try:
        json.dumps(v)
        return v
    except TypeError:
        return str(v)


def merge_chrome_traces(paths, out_path: str | None = None) -> dict:
    """Merge per-host trace files into ONE Chrome trace-event object.

    Each host of a multi-host run writes its own trace
    (``Tracer(process_label=...).write``); pids are distinct processes,
    so concatenating the event lists yields one timeline in which every
    host appears as its own named track group (the ``process_name``
    metadata events survive the merge).  Timestamps are
    ``perf_counter_ns``-based and therefore NOT cross-host aligned -
    the merged view answers "what did each host do", not "who was
    first by a microsecond".
    """
    events: list = []
    for p in paths:
        with open(p) as f:
            events.extend(json.load(f).get("traceEvents", []))
    merged = {"traceEvents": events, "displayTimeUnit": "ms"}
    if out_path is not None:
        d = os.path.dirname(os.path.abspath(out_path))
        os.makedirs(d, exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(merged, f)
    return merged


NULL_TRACER = Tracer(enabled=False)
