"""Flight-recorder quickstart: trace a serving run, open it in Perfetto.

The one-liner is the CLI - any ``launch/serve.py`` scenario takes the
observability flags:

    PYTHONPATH=src python -m repro.launch.serve --scenario geotenants \
        --tenants 3 --tenant-mode priced --small --windows 20 \
        --metrics-out results/obs/metrics.prom \
        --trace-out results/obs/trace.json --obs-interval 5

which leaves three artifacts:

  results/obs/trace.json            Chrome trace-event JSON.  Open
      https://ui.perfetto.dev and drag the file in (or
      chrome://tracing).  The serving thread and the chunk-prefetch
      worker render as separate tracks; the per-window ``serve`` spans
      hold ``h2d`` -> ``dispatch`` -> ``dual_update``, the worker track
      shows ``prep`` holding ``arrivals`` and then ``chunk_tables`` (a
      generated source) or ``context_rows`` and ``gather_dispatch`` (a
      replay source), and any serving-thread gap shows up as a
      ``stall`` span - prefetch working means stalls ~ 0.  Every span
      carries ``args.parent`` (the span it sits in) and ``args.t`` (its
      window), so the worker's ``prep`` of window t and the serving
      thread's ``serve`` of window t share one id.
  results/obs/metrics.prom(.json)   Prometheus text + JSON snapshot of
      the ``greenflow_*`` registry (windows/requests served, prep /
      stall / submit histograms, h2d bytes, recompiles, per-axis
      lambda / spend / budget gauges).
  results/obs/metrics.prom.windows.jsonl   one JSON row per window:
      size, bucket, every dual price and per-axis spend-vs-budget by
      ConstraintSpec axis name, FLOPs, gCO2e, timing - the flight log.

Add ``--profile-dir /tmp/jaxprof`` to capture a jax.profiler trace of
the same run (device-side timeline, with the obs span names threaded
through as TraceAnnotations).  The device programs are named by the
program: ``jit_fused_pass`` (the online pass), ``jit_dual_update`` (the
nearline price update) and, for a replay source, ``jit_replay_gather``.

This script shows the same thing PROGRAMMATICALLY on a toy stream -
build an ``Obs``, hand it to the source / pipeline / driver, export:

    PYTHONPATH=src python examples/trace_serving.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main():
    from dataclasses import replace

    from repro.data.request_source import GeneratedSource
    from repro.data.synthetic import StreamingWorld
    from repro.experiments import build_serving_stack, serve_config
    from repro.obs import Obs, WindowEventLog
    from repro.serving.pipeline import ServingPipeline
    from repro.serving.stream import run_stream

    print("[example] building the small serving stack ...")
    exp, _, params, rcfg = build_serving_stack(
        serve_config(small=True), verbose=True)

    # ONE Obs is shared by the source (cache + chunk_tables spans), the
    # pipeline (h2d/dispatch/dual_update spans) and the stream driver
    # (prep/stall/serve spans, per-window metrics, the flight log)
    obs = Obs(events=WindowEventLog("results/obs/example.windows.jsonl"),
              interval=4)  # live line every 4 windows
    world = StreamingWorld.build(
        replace(exp.cfg.world, n_users=100_000))
    source = GeneratedSource(world, exp.models, exp.chains,
                             expose=exp.cfg.expose, seed=0, obs=obs)
    budget = 0.5 * float(exp.chains.costs.max()) * 64
    pipeline = ServingPipeline(source.universe, params, rcfg, budget,
                               obs=obs)
    sizes = [64, 128, 64, 128] * 4
    stats = run_stream(pipeline, sizes, source, prefetch=2, obs=obs)

    prom, snap = obs.export("results/obs/example.prom")
    trace = obs.tracer.write("results/obs/example_trace.json")
    print(f"served {len(stats.windows)} windows "
          f"({sum(stats.sizes)} requests) in {stats.wall_s:.2f}s")
    print(f"metrics:    {prom}  (+ {snap})")
    print(f"flight log: {obs.events.path} ({obs.events.rows_written} rows)")
    print(f"trace:      {trace}  -> open in https://ui.perfetto.dev")


if __name__ == "__main__":
    main()
