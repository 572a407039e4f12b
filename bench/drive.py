"""Drive a cell's stack through ``repro.serving.stream.run_stream`` and
record when each window's answers are on the host.

``run_stream`` is the program's own driver and prefetch thread; it is
used as it is.  Two thin wrappers give the benchmark its clock:

- ``Feed``, the request source ``run_stream`` pulls windows from, keeps
  the arrival ids, holds an open loop's windows until their last request
  has arrived (``bench.arrivals``) and ends the stream (``Closed``) once
  the timed window has closed;
- ``Served``, a proxy of the pipeline, hands every ``WindowResult`` to
  a ``Watcher`` thread, which copies its decisions and revenue to the
  host in serving order and stamps the time each became available.
"""
from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field

import numpy as np

CLOCK = time.perf_counter


class Closed(Exception):
    """Raised by ``Feed.window`` once the timed window has closed."""


@dataclass
class Window:
    """One served window, in serving order."""

    k: int  # window index within the run (warm-up windows first)
    n: int
    result: object  # the program's WindowResult
    decisions: np.ndarray  # (n,) served chain per request, on the host
    revenue: np.ndarray  # (n,) realized clicks per request, on the host
    regions: np.ndarray | None
    done: float  # host clock when both were on the host
    users: np.ndarray | None = None  # the requests' user ids
    arrived: np.ndarray | None = None  # open loop: host clock of arrival
    lag: float | None = None  # open loop: s the window was produced late


class Watcher(threading.Thread):
    """Copies each served window's answers to the host, in order."""

    def __init__(self):
        super().__init__(name="bench-watcher", daemon=True)
        self.q: queue.Queue = queue.Queue()
        self.done: list[tuple] = []
        self.error: BaseException | None = None

    def put(self, res) -> None:
        self.q.put(res)

    def close(self) -> None:
        self.q.put(None)
        self.join()
        if self.error is not None:
            raise self.error

    def run(self) -> None:
        try:
            while True:
                res = self.q.get()
                if res is None:
                    return
                dec = res.decisions_np
                rev = res.revenue_np
                reg = res.regions_np
                self.done.append((res, dec, rev, reg, CLOCK()))
        except BaseException as e:  # surfaced by close()
            self.error = e


class Served:
    """Pipeline proxy: ``serve_window`` also queues the result for the
    watcher; every other attribute is the pipeline's."""

    def __init__(self, pipe, watcher: Watcher):
        self._pipe = pipe
        self._watcher = watcher

    def serve_window(self, *args, **kwargs):
        res = self._pipe.serve_window(*args, **kwargs)
        self._watcher.put(res)
        return res

    def __getattr__(self, name):
        return getattr(self._pipe, name)


@dataclass
class Feed:
    """The request source ``run_stream`` pulls from: window t of this
    stream is window ``first + t`` of the run.  With an open-loop
    ``clock`` a window is produced once its last request has arrived,
    counted from ``t0``; none is produced whose last request would
    arrive after ``deadline``."""

    source: object
    first: int
    deadline: float | None = None
    clock: object = None  # bench.arrivals.Clock of an open loop
    t0: float = 0.0
    users: dict = field(default_factory=dict)
    arrived: dict = field(default_factory=dict)
    lag: dict = field(default_factory=dict)

    def window(self, t: int, n: int):
        k = self.first + t
        if self.clock is not None:
            at = self.t0 + self.clock.next(n)
            if self.deadline is not None and at[-1] >= self.deadline:
                raise Closed
            wait = at[-1] - CLOCK()
            if wait > 0:
                time.sleep(wait)
            self.arrived[k] = at
            self.lag[k] = CLOCK() - at[-1]
        elif self.deadline is not None and CLOCK() >= self.deadline:
            raise Closed
        chunk = self.source.window(k, n)
        self.users[k] = np.asarray(chunk.users)
        return chunk


def serve(stack, plan, *, first: int, count: int,
          deadline: float | None = None, t0: float | None = None,
          obs=None) -> list[Window]:
    """Serve up to ``count`` windows of ``plan`` (``bench.arrivals``)
    starting at run window ``first``; stop producing once ``deadline``
    passes.  With ``t0`` an open-loop plan's requests arrive from then
    on; without it every window is backlogged (warm-up).  Returns every
    window that was dispatched, with its host answers."""
    from repro.serving.stream import run_stream

    watcher = Watcher()
    watcher.start()
    clock = plan.clock() if plan.open_loop and t0 is not None else None
    feed = Feed(stack.source.program, first, deadline=deadline, clock=clock,
                t0=t0 or 0.0)
    sizes = [plan.size(first + i) for i in range(count)]
    budget, scale = stack.spec.traces(first, count)
    try:
        run_stream(Served(stack.pipe, watcher), sizes, feed,
                   budget_trace=budget, scale_trace=scale,
                   forecast=stack.spec.forecast, prefetch=2, obs=obs)
    except Closed:
        pass
    finally:
        watcher.close()
    out = []
    for i, (res, dec, rev, reg, done) in enumerate(watcher.done):
        k = first + i
        out.append(Window(
            k=k, n=int(res.n_valid), result=res, decisions=dec, revenue=rev,
            regions=reg, done=done, users=feed.users.get(k),
            arrived=feed.arrived.get(k), lag=feed.lag.get(k)))
    return out
