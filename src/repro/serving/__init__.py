"""Unified streaming serving runtime (paper §4.3-§5.3 online system).

A map of the unified allocator core and the layers over it:

  serving.spec            the DECLARATIVE front door: a
      ``ConstraintSpec`` is a list of constraint axes -
      ``TenantAxis(budgets, priced=...)``, ``RegionAxis(n, split=...)``,
      ``GlobalAxis(budget, pricing="flops"|"carbon")`` - that compiles
      onto the multi-price core's (M, K) cost map, (I, K) membership,
      (K,) budget/price vectors and per-K guard ``k_of``, with K the
      concatenation of the declared axes (priced tenant prices first,
      region prices after).  ``spec_from_legacy`` maps every historical
      flag combination to its spec, bit-identically.
  core.primal_dual        THE multi-price core: Eq. 10 ``allocate``,
      per-constraint ``consumption``, Algorithm 1 ``dual_descent``.
      One implementation spans every pricing shape - a scalar price
      (the paper, K=1, bit-identical), a (K,) price vector against an
      (M, K) option->constraint cost map (K over tenant x region), and
      per-request constraint membership.  ``window_step`` is the shared
      host-loop body the budget controllers wrap.  ``consumption``
      forms each constraint's spend from per-option request COUNTS
      (sums of 0/1 terms, exact in any order), so padding a window or
      splitting it over more request shards changes no price by
      itself.  Reward scoring runs its matmuls at each shard's batch
      shape, which XLA may round differently, so across shard COUNTS
      rewards and, through them, prices agree to float32 rounding
      while decisions are checked equal (``chip_smoke.py --chips 4``,
      tests/test_serving.py); ``reward_matrix_grouped`` likewise
      equals ``reward_matrix`` to rtol 1e-6, not bit for bit.
  serving.guard           the budget downgrade guard as a vectorized,
      jit-compatible pass: cumsum tail-reserve walk, mask-aware for
      padded windows, shardable over the request axis, and -
      via ``k_of`` - K per-constraint budgets at once (tenant blocks,
      serving regions), each constraint walking only its own requests.
      ``downgrade_guard_chain`` sequences several constraint FAMILIES
      (tenant budgets THEN region budgets) over one window.
  data.request_source     where REQUESTS come from.  A ``RequestSource``
      produces each window on demand as a ``WindowChunk`` - sampled
      arrivals, reward contexts, LOCAL rows and a per-window (G, n,
      cap) slice of compact execution tables - so host memory scales
      with the window, never the universe.  ``GeneratedSource`` streams
      an unbounded hash-generated user world
      (``data.synthetic.StreamingWorld``, U >= 100k); a
      ``TableReplaySource`` replays fixed precomputed tables (in
      memory or memmapped ``.npy``), bitwise identical to indexing the
      materialized ``CascadeServer`` it was built from.
      ``source.universe`` is the layout-only server handle a streaming
      pipeline is constructed over.  With ``device_tables`` (default
      for generated and in-memory replay sources) chunk tables live
      ON DEVICE end-to-end: stage scores never cross to host, the
      compaction runs as a jitted pass bitwise equal to the host
      builder, replay windows gather device-resident tables, and a
      slab-keyed LRU cache skips rescoring repeat-visitor chunks.
  serving.pipeline        ``ServingPipeline.from_spec``: reward scoring
      (model-prefix grouped), priced allocation, the fused guard,
      CompactPlan cascade execution and the nearline dual update in ONE
      jitted window pass, for ANY compiled spec: plain scalar; tenants
      shared/priced; geo regions; and the combined tenant x region
      system (a (T + R,) price vector where a tenant-t request pays
      (lam_tenant[t] + lam_region[r]) * c_{j,r}, per-(tenant, region)
      spends in ``WindowResult.tr_spend``).  Tables are a TRACED
      argument: ``serve_window(..., tables=chunk.tables)`` gathers
      within a RequestSource chunk instead of a materialized user
      axis.  Per-window budgets/scales take positional vectors or
      NAMED dicts keyed by ``spec.compile().budget_names`` /
      ``scale_names``.  Degenerate region ties are rounded by the
      exact flow split (``RegionAxis(split="flow")``).  All modes
      compose with the
      ("req",) shard_map mesh, bucketed window padding (``bucketing=
      "linear"|"pow2"``; pow2 keeps the compiled-shape count
      logarithmic under traffic swings) and the CI-forecast dual
      warm-start (``dual_budget``/``dual_cost_scale``).
      ``WindowResult.compiles``/``bucket`` surface per-window jit
      cache misses - zero in steady state, by construction -
      alongside ``h2d_bytes``/``prep_ms``/``stall_ms``.  The nearline
      dual chain runs through donated jits (``donate_dual``, default
      on): steady-state windows update the price allocation-free,
      with readable record copies in ``lam_before``/``lam_after``.
      The legacy keyword constructor survives as a thin shim over
      ``spec_from_legacy``.
  serving.stream          prefetching streaming driver: ``run_stream
      (..., prefetch=N)`` moves chunk production to one background
      worker feeding a bounded queue (windows in strict t order, so
      bitwise identical to ``prefetch=0`` - the sequential
      double-buffered reference), records per-window ``stall_ms``,
      and splits the old dispatch time into ``prep_ms`` +
      ``submit_ms`` (``dispatch_ms`` survives as their sum) + the
      ``SCENARIOS`` registry - ONE dict of per-window-size builders
      (constant, spike, diurnal, tenants, carbon, georegions,
      geotenants, swing) from which the valid-names error and the
      ``launch/serve.py --scenario`` choices both derive; per-window
      budget/scale traces and ``forecast=True`` thread time-varying
      carbon constraints through the pipeline without recompiles;
      ``StreamStats.steady_compiles`` audits the zero-recompile
      guarantee and ``StreamStats.h2d_bytes`` the transfer budget
      over a finished run.
  distributed.multihost   the MULTI-HOST request mesh over all of the
      above: ``initialize()`` brings up ``jax.distributed`` from
      ``GREENFLOW_COORDINATOR`` / ``_NUM_PROCESSES`` / ``_PROCESS_ID``
      (gloo CPU collectives configured first), after which
      ``launch.mesh.make_request_mesh()`` spans every process and the
      SAME fused pipeline runs unchanged - its guard prefix sums,
      per-axis spends and nearline dual updates stitch globally
      through order-fixed all_gather reductions
      (``distributed.sharding.ordered_psum``), so every host agrees
      BITWISE on lambda and every decision.  Windows are never
      shipped: arrivals are pure (seed, t) functions every host
      evaluates, ``pipeline.window_layout`` is the canonical padded
      layout all hosts derive from (n, bucket) alone, and
      ``MultihostSource`` wraps any RequestSource to materialize ONLY
      this host's ``launch.mesh.process_shard_rows`` slice of each
      window (``WindowChunk.shard`` carries the slice geometry into
      ``serve_window``).  Elastic re-sharding is reshard-on-restore:
      ``checkpoint_stream`` persists the tiny {cursor, dual chain,
      seed} state, a DIFFERENT-sized group restores it
      (``restore_stream`` + ``ShiftedSource``) and replays from the
      in-flight window bitwise - the fixed GLOBAL shard count (pad
      quantum lcm's ``mesh_num_shards``) makes the numerics
      process-count-invariant.  Per-host flight-recorder labels
      (``Obs(host=...)``) tag JSONL events and name Perfetto track
      groups; ``merge_chrome_traces`` folds every host's trace into
      one timeline.  ``launch/serve.py --processes/--process-id/
      --coordinator`` is the CLI bring-up (runbook in its module
      docstring); tests/test_multihost.py pins the parity, stitching
      and elastic gates with real subprocess meshes.
  carbon.*                the gCO2e side: intensity traces, the
      CarbonBudget / CarbonBudgetController wrappers (both
      spec-buildable via ``from_spec``), and the CarbonLedger
      (operational + embodied metering, per-region attribution for
      geo serving).
  obs (repro.obs)         the FLIGHT RECORDER over all of the above:
      a pure-Python metrics registry (counters / gauges / fixed-
      bucket log2 histograms under the ``greenflow_*`` namespace -
      see ``repro/obs/__init__.py`` for the full metric table), span
      tracing (``prep``/``stall``/``serve``/``h2d``/``dispatch``/
      ``dual_update``/``chunk_tables``/``ledger``/
      ``block_until_ready``) exported as Chrome trace-event JSON
      (ui.perfetto.dev; the chunk-prefetch worker and the serving
      thread render as separate tracks), and a per-window JSONL
      event log (size, bucket, per-axis lambda / spend vs budget by
      ``CompiledSpec.k_names``, FLOPs, gCO2e, h2d bytes, prep /
      stall / submit ms, recompiles).  Pass an ``Obs`` into
      ``run_stream`` / ``ServingPipeline`` / ``GeneratedSource`` /
      ``CarbonLedger`` (CLI: ``--metrics-out``, ``--trace-out``,
      ``--obs-interval``, ``--profile-dir``).  Two invariants, both
      pinned by tests/test_obs.py and bench_scale gates: telemetry
      on vs off is BITWISE identical (device arrays are only read in
      the post-drain flush), and disabled telemetry is free (shared
      no-op singletons, zero allocations on the window hot path).
      ``run_stream(..., clock=...)`` injects the timing clock so
      tests pin prep/stall/submit attribution deterministically.

Invariants (enforced at lint time by ``repro.analysis`` -- the
greenflow-check suite; ``python -m repro.analysis src`` and the CI
static-analysis job reject violations, ``--jaxpr-audit`` re-checks the
lowered fused pass):

  GF001  ordered collectives.  Serving/distributed code never calls raw
      ``lax.psum``: backend ring/tree reduction order varies with
      topology, and float addition is not associative.  Cross-host
      stitching goes through ``distributed.sharding.ordered_psum``
      (all_gather + local sum over the fixed shard axis) -- the bitwise
      decision/lambda parity the PR 9 mesh guarantees.
  GF002  no hidden host syncs.  The hot-path modules (pipeline, stream,
      guard, engine, request_source) keep ``.item()`` /
      ``jax.device_get`` / host numpy out of the window path: the
      prefetch overlap (PR 7) and the telemetry-off bitwise guarantee
      (PR 8) both assume device arrays are only read post-drain.
  GF003  no ``jnp.mean`` in dual-price arithmetic.  XLA strength-
      reduces mean to sum*(1/n) and reassociates the divisor chain;
      PR 4's scalar-vs-vectorized K=1 bit-parity broke exactly this
      way.  Dual norms structure their divisors explicitly (the two
      sanctioned reference expressions carry justified pragmas).
  GF004  jit hygiene.  ``static_argnames`` must name real parameters
      (a typo is silently ignored and retraces per value -- PR 2), and
      a buffer passed at a ``donate_argnums`` position is never read
      afterwards (the dual chain rebinds, with ``_lam_rec`` as the
      readable bitwise copy -- PR 7/9).
  GF005  pure windows.  Window-producing code is a function of
      (seed, t): no wall clocks (timing is injected via ``run_stream
      (clock=...)``, PR 8) and no global RNG (every host must derive
      identical arrivals, PR 9).
  GF006  signed-zero canonicalization uses ``jnp.where``, never
      ``+ 0.0`` -- XLA folds the add and -0.0 leaks into the monotone
      float-bit sort keys (PR 7's device compactor).

``launch/serve.py`` is the CLI front end (--scenario ... --source
table|generated|memmap --tenant-mode shared|priced --geo-split
flow|argmax --shards N); benchmarks: ``bench_serve.py`` (fused pass vs
legacy loop, BENCH_serve.json), ``bench_carbon.py`` (carbon-aware
allocator, BENCH_carbon.json), ``bench_geo.py`` (two-region router,
BENCH_geo.json), ``bench_geotenants.py`` (the combined tenant x region
spec vs the single-axis arms + the exact-dual pipeline gate,
BENCH_geotenants.json) and ``bench_scale.py`` (the streamed geotenants
pipeline at U >= 100k under 10x-1000x swings: requests/sec, p99 window
latency, flat peak RSS w.r.t. U and zero steady-state recompiles,
BENCH_scale.json); ``bench_multihost.py`` (1/2/4/8-process mesh sweep
at a fixed 8-shard global layout: per-process + aggregate req/s,
bitwise decision parity vs single-process, merged per-host Perfetto
trace, hardware-gated scaling assertion, BENCH_multihost.json) and
``bench_truncate.py`` (the Pallas cascade-truncation kernel vs the XLA
baseline at production batch sizes, BENCH_truncate.json).
"""
import importlib

from repro.serving.guard import (downgrade_guard, downgrade_guard_chain,
                                 downgrade_guard_np)
from repro.serving.spec import (ConstraintSpec, GlobalAxis, RegionAxis,
                                TenantAxis, spec_from_legacy)

_LAZY = {
    "ServingPipeline": "repro.serving.pipeline",
    "WindowResult": "repro.serving.pipeline",
    "StreamStats": "repro.serving.stream",
    "TrafficScenario": "repro.serving.stream",
    "SCENARIOS": "repro.serving.stream",
    "run_stream": "repro.serving.stream",
    "scenario_windows": "repro.serving.stream",
}

__all__ = ["downgrade_guard", "downgrade_guard_chain",
           "downgrade_guard_np", "ConstraintSpec", "TenantAxis",
           "RegionAxis", "GlobalAxis", "spec_from_legacy", *_LAZY]


def __getattr__(name):  # PEP 562: keep core.budget's import chain light
    if name in _LAZY:
        return getattr(importlib.import_module(_LAZY[name]), name)
    raise AttributeError(name)
