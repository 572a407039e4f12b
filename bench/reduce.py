"""Reduce a JAX profiler trace (``.xplane.pb``) of the timed window to the
numbers the per-layer metrics read.

The timed window starts at the host annotation ``bench_window``, which
the harness opens on the serving thread at the window's first instant,
and lasts the run's ``seconds``.  Per device plane (``/device:TPU:<i>``)
the reduction reads the ``XLA Ops`` line - busy time is the union of the
op intervals - and the ``XLA Modules`` line, whose events name the
jitted program (``jit_<function>``) that ran.  Idle gaps between op
intervals on the first chip are named by the program's host spans
(``repro.obs`` spans, passed to the profiler as ``TraceAnnotation``)
open on the serving thread at the gap's midpoint; a ``stall`` there is
named by what the other host threads were doing.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW_MARK = "bench_window"
# host spans of repro.serving.stream / data.request_source /
# serving.pipeline that can explain a device gap
HOST_SPANS = ("prep", "chunk_tables", "stall", "serve", "h2d", "dispatch",
              "dual_update", "block_until_ready")
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute|psum|send|recv", re.I)
TOP = 10


@dataclass
class Reduced:
    window_s: float
    busy_s: float  # union of op intervals, mean over the chips used
    module_s: dict = field(default_factory=dict)  # program -> seconds
    collective_s: float = 0.0  # mean over chips
    op_s: dict = field(default_factory=dict)  # op name -> seconds, chip 0
    gap_s: dict = field(default_factory=dict)  # host activity -> seconds
    host_s: dict = field(default_factory=dict)  # host span -> seconds

    @property
    def breakdown(self) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.gap_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}

    def program_s(self, *prefixes: str) -> float:
        """Device seconds of the programs whose name starts with one of
        ``prefixes`` (``jit_din_block`` etc.)."""
        return float(sum(v for k, v in self.module_s.items()
                         if k.startswith(prefixes)))


def _program(name: str) -> str:
    """``jit_fn(123)`` -> ``jit_fn``."""
    return name.split("(", 1)[0].strip()


def _op(name: str) -> str:
    """An XLA op event, by instruction and result shape: ``copy =
    f32[16,131072,200]{2,0,1:T(8,128)} copy(...)`` -> ``copy
    f32[16,131072,200]``; ``fusion.12`` -> ``fusion``."""
    head, _, rest = name.partition(" = ")
    base = re.sub(r"[.\d]+$", "", head.lstrip("%")) or head
    shape = re.match(r"[a-z0-9]+\[[\d,]*\]", rest)
    return f"{base} {shape.group(0)}" if shape else base


def _union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _events(line):
    for ev in line.events:
        yield ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)


def newest(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def reduce_dir(trace_dir: str, seconds: float, chips: int) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(newest(trace_dir)),
                          seconds, chips)


def reduce_profile(pd, seconds: float, chips: int) -> Reduced:
    hosts = [p for p in pd.planes if p.name.startswith("/host:CPU")]
    devs = sorted((p for p in pd.planes
                   if re.match(r"/device:TPU:\d+$", p.name)),
                  key=lambda p: int(p.name.rsplit(":", 1)[1]))[:chips]
    if not devs:
        raise ValueError("the trace holds no TPU device plane")
    w0 = main = None
    spans = []  # (line id, name, start, end)
    for plane in hosts:
        for li, line in enumerate(plane.lines):
            for name, s, e in _events(line):
                if name == WINDOW_MARK and w0 is None:
                    w0, main = s, (plane.name, li)
                elif name in HOST_SPANS:
                    spans.append(((plane.name, li), name, s, e))
    if w0 is None:
        raise ValueError(f"no {WINDOW_MARK!r} annotation in the trace")
    w1 = w0 + int(seconds * 1e9)

    busy_total = coll_total = 0.0
    module_s: dict = defaultdict(float)
    op_s: dict = defaultdict(float)
    first_busy: list = []
    for d, plane in enumerate(devs):
        ops, mods = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = list(_events(line))
            elif line.name == "XLA Modules":
                mods = list(_events(line))
        intervals = []
        for name, s, e in ops:
            s, e = _clip(s, e, w0, w1)
            if e <= s:
                continue
            intervals.append((s, e))
            if COLLECTIVE.search(name.partition(" = ")[0]):
                coll_total += (e - s) / 1e9
            if d == 0:
                op_s[_op(name)] += (e - s) / 1e9
        busy = _union(intervals)
        busy_total += sum(e - s for s, e in busy) / 1e9
        if d == 0:
            first_busy = busy
        for name, s, e in mods:
            s, e = _clip(s, e, w0, w1)
            if e > s:
                module_s[_program(name)] += (e - s) / 1e9 / len(devs)

    host_s: dict = defaultdict(float)
    for _, name, s, e in spans:
        s, e = _clip(s, e, w0, w1)
        if e > s:
            host_s[name] += (e - s) / 1e9
    gap_s: dict = defaultdict(float)
    prev = w0
    for s, e in first_busy + [(w1, w1)]:
        if s > prev:
            gap_s[_gap_name(spans, main, (prev + s) // 2)] += (s - prev) / 1e9
        prev = max(prev, e)
    return Reduced(window_s=(w1 - w0) / 1e9, busy_s=busy_total / len(devs),
                   module_s=dict(module_s), collective_s=coll_total
                   / len(devs), op_s=dict(op_s), gap_s=dict(gap_s),
                   host_s=dict(host_s))


def _innermost(spans, t, keep):
    best = None
    for line, name, s, e in spans:
        if s <= t < e and keep(line) and (best is None or s >= best[0]):
            best = (s, name)
    return None if best is None else best[1]


def _gap_name(spans, main, t) -> str:
    here = _innermost(spans, t, lambda line: line == main)
    if here is None or here == "stall":
        other = _innermost(spans, t, lambda line: line != main)
        if other is not None:
            return f"{here or 'host'}/{other}"
    return here or "host"
