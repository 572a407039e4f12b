"""The per-layer readers of the program's named device programs and of
its replay-prep spans, on numbers known here: a reduced trace made by
hand, and ``repro.obs`` span events that straddle the timed window's
edges."""
import importlib.util
import os
from types import SimpleNamespace

import pytest

from bench import reduce, spans
from bench.build import BENCH


def _reader(name):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(trace=None, events=(), t0=10.0, t_end=11.0, requests=4096):
    """A run of one 4096-request window between t0 and t_end (s)."""
    from repro.obs.trace import Tracer

    tracer = Tracer()
    tracer.events.extend(events)
    stack = SimpleNamespace(pipe=SimpleNamespace(
        obs=SimpleNamespace(tracer=tracer)))
    return SimpleNamespace(trace=trace, stack=stack, t0=t0, t_end=t_end,
                           requests=requests)


DEVICE = {"gather_dev_ms_per_kreq": "jit_replay_gather",
          "fused_pass_dev_ms_per_kreq": "jit_fused_pass",
          "dual_dev_ms_per_kreq": "jit_dual_update"}


@pytest.mark.parametrize("name", sorted(DEVICE))
def test_device_program_readers(name):
    module_s = {"jit_replay_gather": 0.0128, "jit_fused_pass": 0.0011,
                "jit_dual_update": 0.0070, "jit_fn": 9.0, "jit__take": 9.0}
    red = reduce.Reduced(window_s=1.0, busy_s=0.0209, module_s=module_s)
    read = _reader(name)
    # seconds over 4.096 thousand requests, in ms
    assert read(_run(red)) == pytest.approx(
        module_s[DEVICE[name]] * 1e3 / 4.096)
    assert read(_run(None)) is None  # untraced run
    # a program without these names (the parent's jit_fn / jit__take)
    other = reduce.Reduced(window_s=1.0, busy_s=1.0,
                           module_s={"jit_fn": 1.0, "jit__take": 2.0})
    assert read(_run(other)) is None


def _ev(name, start_s, dur_s, tid=1, t=0):
    from repro.obs.trace import SpanEvent

    return SpanEvent(name, tid, "chunk-prefetch", int(start_s * 1e9),
                     int(dur_s * 1e9), None, "prep", t)


SPANS = ("arrivals", "context_rows", "gather_dispatch")


@pytest.mark.parametrize("span", SPANS)
def test_span_readers_clip_to_the_window(span):
    """Window [10, 11] s: a span from 9.9 to 10.1 counts 0.1 s, one
    inside 0.2 s, one from 10.9 to 11.2 counts 0.1 s, one wholly before
    and one after count nothing; other names count nothing."""
    events = [_ev(span, 9.9, 0.2), _ev(span, 10.4, 0.2),
              _ev(span, 10.9, 0.3), _ev(span, 9.0, 0.5),
              _ev(span, 11.5, 0.1), _ev("prep", 10.0, 1.0)]
    events += [_ev(other, 10.0, 0.9) for other in SPANS if other != span]
    red = reduce.Reduced(window_s=1.0, busy_s=1.0)
    got = _reader(f"{span}_ms_per_kreq")(_run(red, events))
    assert got == pytest.approx(0.4 * 1e3 / 4.096)
    assert spans.clipped_s(events, span, 10.0, 11.0) == pytest.approx(0.4)


@pytest.mark.parametrize("span", SPANS)
def test_span_readers_find_nothing(span):
    read = _reader(f"{span}_ms_per_kreq")
    red = reduce.Reduced(window_s=1.0, busy_s=1.0)
    # the parent program records no such span: its events are plain
    # 6-tuples (name, tid, thread, start ns, duration ns, args)
    parent_events = [("prep", 1, "chunk-prefetch", int(10.2e9), int(1e7),
                      {"t": 0})]
    assert read(_run(red, parent_events)) is None
    # such spans, all outside the window
    assert read(_run(red, [_ev(span, 12.0, 0.1)])) is None
    # an untraced run: no trace, and the pipeline's bundle is disabled
    off = _run(None, [_ev(span, 10.2, 0.1)])
    assert read(off) is None
    off.trace = red
    off.stack.pipe.obs.tracer.enabled = False
    assert read(off) is None


def test_span_readers_on_a_cpu_run():
    """A traced run of the cell at a CPU size: the three prep spans are
    found, and per window they sum to no more than ``prep``."""
    import jax

    from bench import arrivals, build, drive, measure
    from bench.tests import tiny
    from repro.obs import Obs

    w, cfg, tr = tiny.cell("geotenants-replay-sat")
    plan = arrivals.plan(tr, 3)
    obs = Obs()
    stack = build.build(cfg, tr, seed=3, chips=1, obs=obs)
    drive.serve(stack, plan, first=0, count=2, obs=obs)
    t0 = drive.CLOCK()
    windows = drive.serve(stack, plan, first=2, count=4, obs=obs)
    t_end = drive.CLOCK()
    run = measure.Run(cell=w, cfg=cfg, traffic=tr, stack=stack,
                      windows=windows, t0=t0, t_end=t_end,
                      seconds=t_end - t0, setup_s=0.0, chips=1,
                      kind=jax.devices()[0].device_kind)
    run.trace = reduce.Reduced(window_s=t_end - t0, busy_s=0.0)
    got = {s: _reader(f"{s}_ms_per_kreq")(run) for s in SPANS}
    assert all(v is not None and v > 0 for v in got.values()), got
    prep = spans.clipped_s(spans.events(run), "prep", t0, t_end)
    assert sum(got.values()) * run.requests / 1e6 <= prep
