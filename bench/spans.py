"""Host time of the program's own spans inside the timed window.

The program records its spans in memory (``repro.obs.Tracer.events``:
name, thread, start and duration in ``perf_counter_ns``, which is the
clock of ``bench.drive.CLOCK``); a traced run hands the pipeline the
bundle it records into.  A span's time counts only where it falls
inside ``[run.t0, run.t_end]``.
"""
from __future__ import annotations


def events(run) -> list:
    """The spans the run's pipeline bundle recorded; none where the run
    had no bundle."""
    pipe = getattr(run.stack, "pipe", None)
    tracer = getattr(getattr(pipe, "obs", None), "tracer", None)
    if tracer is None or not tracer.enabled:
        return []
    return list(tracer.events)


def clipped_s(spans, name: str, t0: float, t_end: float) -> float | None:
    """Seconds of the spans called ``name`` inside ``[t0, t_end]`` (host
    clock, s); None where there is no such span there.  A span is
    (name, thread id, thread name, start ns, duration ns, ...)."""
    lo, hi = int(t0 * 1e9), int(t_end * 1e9)
    total, seen = 0, False
    for ev in spans:
        if ev[0] != name:
            continue
        s, e = max(ev[3], lo), min(ev[3] + ev[4], hi)
        if e > s:
            total += e - s
            seen = True
    return total / 1e9 if seen else None


def ms_per_kreq(run, name: str) -> float | None:
    """Milliseconds of ``name`` spans in the timed window per 1,000
    requests served; None in an untraced run or where the program has
    no such span."""
    if run.trace is None or run.requests == 0:
        return None
    s = clipped_s(events(run), name, run.t0, run.t_end)
    if s is None:
        return None
    return s * 1e3 / (run.requests / 1e3)
