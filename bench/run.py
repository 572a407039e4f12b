"""One run of one benchmark cell on the chips JAX finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's system from its configuration and traffic files, warms
up every shape the traffic uses, serves the traffic for ``--seconds`` of
wall time through ``repro.serving.stream.run_stream``, checks what was
served against the plain reference, and prints one JSON line last on
standard output.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the timed
window.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

T_IMPORT = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))
# libtpu's own logs stay inside the checkout
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, ".bench", "tpu_logs"))

# cap on the windows one timed run can ask for; the window closes long
# before any cell gets there
MAX_WINDOWS = 200_000
TRACE_SECONDS = 6.0  # length of the traced window


def process_age_s() -> float:
    """Seconds since this process started (Linux /proc), so that set-up
    counts the interpreter's start and imports; the module's import time
    where /proc is not there."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_IMPORT


def devices(chips: int):
    """The TPU devices of this cell, or exit non-zero."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU; JAX's devices are "
                         f"{devs[0].platform} ({len(devs)})")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    age0 = process_age_s()
    t_main = time.perf_counter()

    from bench import build

    cell = build.workload(args.workload)
    devs = devices(int(cell["chips"]))
    cfg = build.load("configs", cell["config"])
    traffic = build.load("traffic", cell["traffic"])
    out, lines = execute(cell, cfg, traffic, devs, seed=args.seed,
                         seconds=args.seconds, trace=bool(args.trace),
                         started=t_main - age0)
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(out))
    return 0


def execute(cell: dict, cfg: dict, traffic: dict, devs, *, seed: int,
            seconds: float, trace: bool, started: float | None = None,
            check_fn=None, where: str | None = None):
    """One run of ``cell`` on ``devs``: returns (result line as a dict,
    the lines that print each compared number beside its limit).
    ``started`` is the host-clock instant the process began; set-up is
    counted from there.  ``check_fn`` replaces the comparison with the
    reference (tests).  The cell's source and spec modules are found
    under ``where`` (``bench/`` by default)."""
    from bench import arrivals, build, drive, measure

    if started is None:
        started = time.perf_counter()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    chips = int(cell["chips"])
    obs = None
    if trace:
        from repro.obs import Obs

        obs = Obs(annotate=True)
    plan = arrivals.plan(traffic, seed)
    stack = build.build(cfg, traffic, seed=seed, chips=chips, obs=obs,
                        where=where or build.BENCH)
    warm = drive.serve(stack, plan, first=0, count=plan.warmup, obs=obs)
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    trace_dir = mark = None
    compiles = measure.CompileCounter()
    if trace:
        import jax

        from bench import reduce

        trace_dir = os.path.join(ROOT, ".bench", "trace", cell["name"])
        measure.empty_dir(trace_dir)
        jax.profiler.start_trace(trace_dir)
        mark = jax.profiler.TraceAnnotation(reduce.WINDOW_MARK)
    gc.collect()
    gc.freeze()  # keep the collector out of the timed window
    if mark is not None:
        mark.__enter__()
    t0 = drive.CLOCK()
    setup_s = t0 - started
    with compiles:
        windows = drive.serve(stack, plan, first=len(warm),
                              count=MAX_WINDOWS, deadline=t0 + seconds,
                              t0=t0, obs=obs)
    if mark is not None:
        mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
    gc.unfreeze()
    memory = peak_bytes(devs)
    run = measure.Run(cell=cell, cfg=cfg, traffic=traffic, stack=stack,
                      windows=windows, t0=t0, t_end=t0 + seconds,
                      seconds=seconds, setup_s=setup_s, chips=chips,
                      kind=devs[0].device_kind,
                      compiles=compiles.count)
    if trace:
        run.trace = measure.reduce_trace(trace_dir, run)
    metrics = measure.metrics(run, per_layer=trace)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory}
    if trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    if check_fn is None:
        from bench import check

        check_fn = check.run
    verdict = check_fn(run, seed)
    out = {"correct": verdict.correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = run.trace.breakdown
    out["checks"] = verdict.numbers()
    return out, verdict.lines()


if __name__ == "__main__":
    raise SystemExit(main())
