"""What one timed run recorded, and the metrics read from it.

Every metric, end-to-end or per-layer, is a reader of its own in
``bench/metrics/<name>.py``: ``read(run) -> float | None``.  A reader
that finds nothing to read returns None and the metric is left out of
the result line.  Which metrics a cell reports comes from
``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
from dataclasses import dataclass

from bench.build import BENCH, ROOT


class CompileCounter:
    """Counts XLA compilations while entered (JAX's monitoring events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, *_args, **_kw) -> None:
        if self.on and event == self.EVENT:
            self.count += 1

    def __enter__(self):
        self.on = True
        return self

    def __exit__(self, *exc):
        self.on = False
        return False


def empty_dir(path: str) -> None:
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)


@dataclass
class Run:
    """One timed run: the cell, its stack and the windows it served."""

    cell: dict
    cfg: dict
    traffic: dict
    stack: object
    windows: list  # drive.Window, in serving order
    t0: float
    t_end: float
    seconds: float
    setup_s: float
    chips: int
    kind: str
    compiles: int = 0
    trace: object = None  # reduce.Reduced, traced runs

    @property
    def counted(self) -> list:
        """Windows whose answers were on the host inside the window."""
        return [w for w in self.windows if w.done <= self.t_end]

    @property
    def requests(self) -> int:
        return int(sum(w.n for w in self.counted))

    @property
    def attempted(self) -> int:
        """Requests dispatched during the timed window."""
        return int(sum(w.n for w in self.windows))

    @property
    def failed(self) -> int:
        """Dispatched requests that never got an answer (none drained
        without one: the watcher reads every dispatched window)."""
        return int(sum(w.n - len(w.decisions) for w in self.windows))

    def required_flops(self, windows=None) -> float:
        """The model work the served requests need, as the cell's request
        source counts it (``bench/sources/<source>.py``)."""
        ws = self.counted if windows is None else windows
        return self.stack.source.required_flops(ws)

    def peak_flops(self) -> float:
        """The chips' bf16 peak, from ``bench/peaks.json``."""
        with open(os.path.join(BENCH, "peaks.json")) as f:
            peaks = json.load(f)["devices"]
        if self.kind not in peaks:
            raise KeyError(f"no peaks for device kind {self.kind!r} in "
                           f"bench/peaks.json")
        return self.chips * float(peaks[self.kind]["bf16_flops_per_s"])


def reduce_trace(trace_dir: str, run: Run):
    from bench import reduce

    return reduce.reduce_dir(trace_dir, run.seconds, run.chips)


def _reader(name: str):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metric_entries(cell_name: str, per_layer: bool) -> list[dict]:
    """The ``BENCHMARK.json`` metrics this cell reports."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    key = "per_layer" if per_layer else "end_to_end"
    return [m for m in spec[key]
            if cell_name in m.get("workloads", [cell_name])]


def metrics(run: Run, *, per_layer: bool) -> dict:
    out = {}
    for m in metric_entries(run.cell["name"], per_layer):
        value = _reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
