"""GreenFlow streaming serving driver: the paper's online system end to
end on the fused ServingPipeline (repro/serving/).

    PYTHONPATH=src python -m repro.launch.serve --small --windows 12
    PYTHONPATH=src python -m repro.launch.serve --scenario diurnal
    PYTHONPATH=src python -m repro.launch.serve --scenario tenants \
        --tenants 4 --tenant-mode shared
    PYTHONPATH=src python -m repro.launch.serve --scenario carbon \
        --ci-trace duck --ci-phase-h 6      # carbon-budgeted day
    PYTHONPATH=src python -m repro.launch.serve --shards 2   # request mesh
    PYTHONPATH=src python -m repro.launch.serve --legacy     # old loop

Builds (or loads from the results/cache) the trained cascade + reward
model, then streams request windows through the fused
score->decide->guard->execute pass with double-buffered host prep; the
nearline dual update chains on-device and never blocks a response.

Scenario flags
--------------
--scenario constant   steady traffic at --requests per window
--scenario spike      a --spike x burst in the middle third (Fig. 5)
--scenario diurnal    day-curve sinusoid between 0.4x and 1.6x
--scenario tenants    --tenants equal blocks per window; --tenant-mode
                      `shared` = per-tenant budgets under ONE dual price
                      (the fused per-tenant guard); `priced` = per-tenant
                      DUAL PRICES inside the one fused pass (a (T,)
                      price vector, each tenant descending on its own
                      budget; composes with --shards); `independent` =
                      one pipeline (own price + budget) per tenant
--scenario carbon     diurnal traffic priced against a grid-intensity
                      trace: per-window budgets in gCO2e, chain costs
                      c_j(t) = flops_j*kappa*CI(t), dual price in
                      reward-per-gram; the run is one 24 h day
                      (window_s = 86400/windows), metered by a
                      CarbonLedger into results/carbon_report.csv.
                      Knobs: --ci-trace diurnal|duck|constant (or
                      --ci-csv FILE), --ci-mean, --ci-phase-h (grid vs
                      traffic phase offset), --carbon-pricing
                      carbon|flops (native gram costs vs the
                      effective-FLOPs-budget reduction), --ci-forecast
                      (nearline dual warm-started on the NEXT window's
                      CI - closes the lambda-lag gap)
--scenario georegions the two-region geo-shifting router (spec:
                      RegionAxis(2) + GlobalAxis(pricing="carbon")):
                      each request picks (chain, serving region)
                      through one priced argmax with region costs
                      flops_j*kappa*CI_r(t) (region CI days
                      --geo-offset-h apart), (R,) dual prices +
                      per-region gram budgets + per-region guard;
                      per-region CarbonLedgers merge into
                      results/carbon_report_geo.csv.  --geo-split
                      flow|argmax picks the degenerate-tie rounding
                      (flow = the exact proportional flow split;
                      argmax = the historical knife edge)
--scenario geotenants the COMBINED tenant x region pipeline (spec:
                      TenantAxis(budgets, priced=True) + RegionAxis(2)
                      + GlobalAxis(pricing="carbon")): per-tenant gram
                      budgets AND per-region gram caps priced together
                      in ONE fused pass - a tenant-t request pays
                      (lam_tenant[t] + lam_region[r]) * c_{j,r} for
                      option (j, r), the guard chains a tenant walk
                      with a per-region walk, and WindowResult carries
                      the full (T, R) per-(tenant, region) spend.
                      Knobs: --tenants, --tenant-spread (budget
                      tightness ratio across tenants),
                      --region-cap-frac (each region's gram cap as a
                      fraction of the window's total tenant grams),
                      plus every georegions knob
--shards N            shard_map the pass over an N-way request mesh
                      (composes with tenants, georegions, geotenants)
--source table        index the materialized eval universe (default)
--source generated    stream windows from an unbounded hash-generated
                      user universe (--users sets its size; no (U, J)
                      table ever materializes - each window is scored
                      on the fly by a data.request_source
                      GeneratedSource; composes with every scenario)
--source memmap       replay fixed precomputed tables from memmapped
                      .npy files (saved to --replay-dir on first use):
                      only the rows a window touches page in
--legacy              run the seed's host loop (scoring + NumPy guard +
                      separate serve kernel) instead, for comparison
                      (with --scenario carbon: the CarbonBudgetController
                      host loop; table source only)

Reports per-window spend/lambda/downgrades/revenue, host dispatch time,
and the final PFEC summary.

Observability (repro/obs/): --metrics-out PATH writes a Prometheus-text
snapshot (+ PATH.json + PATH.windows.jsonl per-window flight log),
--trace-out PATH writes the host span trace as Chrome trace-event JSON
(open in ui.perfetto.dev), --obs-interval N prints a live line every N
windows, --profile-dir DIR wraps the run in jax.profiler.trace with
host spans as TraceAnnotations.  Telemetry never changes decisions or
prices - enabled runs are bitwise identical to disabled runs.

Multi-host runbook
------------------
One serve process per host, every process running the SAME command
plus its own identity flags (or the GREENFLOW_COORDINATOR /
GREENFLOW_NUM_PROCESSES / GREENFLOW_PROCESS_ID environment
variables)::

    # host 0 (also runs the coordinator service)
    PYTHONPATH=src python -m repro.launch.serve --source generated \
        --processes 2 --process-id 0 --coordinator host0:9987
    # host 1
    PYTHONPATH=src python -m repro.launch.serve --source generated \
        --processes 2 --process-id 1 --coordinator host0:9987

What happens (repro/distributed/multihost.py): the processes join one
``jax.distributed`` group, the request mesh spans every host's
devices, and each host GENERATES its deterministic slice of every
window - arrivals are pure (seed, t) functions, so no request ever
crosses the network; only the guard/dual collectives do.  All hosts
agree bitwise on every dual price and every decision (the parity gate
in tests/test_multihost.py).  Requirements: a streaming --source
(generated or memmap - every host needs the same universe; --source
table and --legacy are single-process), and --shards unset (the mesh
is the full process-spanning device set).  Per-host telemetry:
--metrics-out/--trace-out write per-host files suffixed with the host
label; merge the traces with ``repro.obs.merge_chrome_traces`` to see
every host's tracks in one Perfetto timeline.  Elastic resharding
(host join/leave) is checkpoint/replay - see
``repro.distributed.multihost.checkpoint_stream``.
"""
from __future__ import annotations

import argparse
from dataclasses import dataclass

import numpy as np

from repro.carbon.ledger import DEFAULT_EMBODIED_G_PER_DEVICE_H
from repro.core.pfec import pfec_report
from repro.experiments import build_serving_stack, serve_config
from repro.launch.compile_cache import enable_compile_cache
from repro.obs.events import _host_np
from repro.serving.pipeline import ServingPipeline
from repro.serving.stream import (SCENARIOS, StreamStats, TrafficScenario,
                                  run_stream)


def _f(x) -> float:
    """Scalar host value of a (possibly multi-process) device array."""
    return float(np.sum(_host_np(x)))


def make_legacy_scorer(exp, rcfg):
    """The seed's jitted reward scorer - the ONE definition every legacy
    host loop (FLOPs or carbon) shares: score(params, ctx) -> (n, J)."""
    import jax
    import jax.numpy as jnp

    from repro.core.reward_model import denormalize_rewards, reward_matrix

    mo = jnp.asarray(exp.chains.model_onehot)
    sh = jnp.asarray(exp.chains.scale_multihot)
    return jax.jit(lambda p, c: denormalize_rewards(
        p, reward_matrix(p, rcfg, c, mo, sh)))


def make_legacy_window(exp, server, params, rcfg, budget):
    """The seed's serving path, packaged for reuse (CLI --legacy and
    benchmarks/bench_serve.py share ONE definition of "legacy"): four
    host/device crossings per window - jitted scoring, NumPy controller
    (decide + guard + synchronous dual), jitted cascade execution.

    Returns (controller, window_fn) with window_fn(ctx, rows) ->
    (decisions, revenue).
    """
    import jax.numpy as jnp

    from repro.core.budget import BudgetController

    score = make_legacy_scorer(exp, rcfg)
    ctl = BudgetController(exp.chains, budget)

    def window(ctx, rows):
        rewards = np.asarray(score(params, jnp.asarray(ctx, jnp.float32)))
        dec = ctl.step_window(rewards)
        rev, _ = server.serve(rows, dec)
        return dec, rev

    return ctl, window


def _legacy_loop(exp, server, params, rcfg, sizes, budget):
    import time

    ctl, window = make_legacy_window(exp, server, params, rcfg, budget)
    rng = np.random.default_rng(0)
    n_eval = exp.ctx_eval.shape[0]
    total_rev = total_flops = 0.0
    print(f"{'win':>4} {'n':>5} {'spend/budget':>13} {'lam':>12} "
          f"{'downgraded':>10} {'revenue':>9} {'window_ms':>9}")
    for t, n in enumerate(sizes):
        t0 = time.perf_counter()
        rows = rng.integers(0, n_eval, n)
        dec, rev = window(exp.ctx_eval[rows], rows)
        dt = (time.perf_counter() - t0) * 1e3
        s = ctl.stats[-1]
        total_rev += rev.sum()
        total_flops += s.spend
        print(f"{t:>4} {n:>5} {s.spend / s.budget:>13.3f} {s.lam:>12.3e} "
              f"{s.downgraded:>10d} {rev.sum():>9.1f} {dt:>9.2f}")
    return total_rev, total_flops


def _build_ci_trace(args):
    from repro.carbon.intensity import (constant_trace, diurnal_trace,
                                        load_ci_csv, solar_duck_trace)

    if args.ci_csv:
        return load_ci_csv(args.ci_csv)
    if args.ci_trace == "diurnal":
        return diurnal_trace(mean=args.ci_mean)
    if args.ci_trace == "duck":
        return solar_duck_trace(mean=args.ci_mean)
    return constant_trace(args.ci_mean)


def _carbon_stream(server, params, rcfg, sizes, cb, ledger,
                   sample_window, pricing, mesh=None, forecast=False,
                   prefetch=2, obs=None, wrap_source=None):
    """Fused-pipeline carbon day: per-window gram budgets + CI-scaled
    costs threaded through run_stream (carbon pricing) or the
    effective-FLOPs-budget reduction (flops pricing); ``forecast`` aims
    each nearline dual update at the NEXT window's CI."""
    sched = cb.schedule(len(sizes))
    pipe = ServingPipeline(server, params, rcfg, cb.flops_ref,
                           ledger=ledger, mesh=mesh, obs=obs)
    if wrap_source is not None:  # multi-host: route windows over hosts
        sample_window = wrap_source(pipe, sample_window)
    if pricing == "carbon":
        st = run_stream(pipe, sizes, sample_window,
                        budget_trace=sched["grams"],
                        scale_trace=sched["scale"], forecast=forecast,
                        prefetch=prefetch, obs=obs)
    else:
        st = run_stream(pipe, sizes, sample_window,
                        budget_trace=sched["flops_budget"],
                        forecast=forecast, prefetch=prefetch, obs=obs)
    print(f"{'win':>4} {'n':>5} {'ci_g/kwh':>9} {'spend/budget':>13} "
          f"{'lam':>12} {'downgraded':>10} {'revenue':>9} "
          f"{'dispatch_ms':>11}")
    for t, r in enumerate(st.windows):
        print(f"{t:>4} {r.n_valid:>5} {sched['ci'][t]:>9.1f} "
              f"{_f(r.spend) / r.budget:>13.3f} "
              f"{_f(r.lam_after):>12.3e} {int(r.downgraded):>10d} "
              f"{r.revenue_np.sum():>9.1f} {st.dispatch_ms[t]:>11.2f}")
    print(f"[serve] {len(sizes)} windows in {st.wall_s:.2f}s "
          f"({len(sizes) / st.wall_s:.1f} win/s)")
    return st


def _geo_stream(chains, server, params, rcfg, sizes, flops_budget, args,
                sample_window, mesh=None, obs=None, wrap_source=None):
    """Two-region geo-shifted serving day: (R,) per-region gram budgets
    and kappa*CI_r(t) cost scales through the fused router, per-region
    CarbonLedgers merged into one region-attributed CSV."""
    import os

    from repro.carbon.controller import grams_per_flop
    from repro.carbon.intensity import two_region_traces
    from repro.carbon.ledger import DAY_S, CarbonLedger, geo_report_csv
    from repro.core.primal_dual import DualDescentConfig
    from repro.serving.spec import (ConstraintSpec, GlobalAxis,
                                    RegionAxis)

    traces = two_region_traces(mean=args.ci_mean,
                               offset_h=args.geo_offset_h)
    names = list(traces)
    n_w = len(sizes)
    window_s = DAY_S / n_w
    phase_s = args.ci_phase_h * 3600.0
    kpf = grams_per_flop(1.0)
    ci_w = {r: traces[r].resample(n_w, window_s, phase_s=phase_s)
            for r in names}
    scale_trace = np.stack([kpf * ci_w[r] for r in names], axis=1)
    g_total = flops_budget * kpf * args.ci_mean
    budget_trace = np.full((n_w, len(names)), g_total / len(names))
    split = args.geo_split
    print(f"[serve] geo day: {n_w} windows x {window_s / 3600.0:.2f} h, "
          f"regions {names} offset {args.geo_offset_h:.0f} h, "
          f"{g_total / len(names):.3e} g/window/region, split "
          f"{split}")
    spec = ConstraintSpec([
        RegionAxis(len(names), names=tuple(names), split=split),
        GlobalAxis(budget=float(flops_budget), pricing="carbon"),
    ])
    pipe = ServingPipeline.from_spec(
        server, params, rcfg, spec, mesh=mesh, obs=obs,
        dual_cfg=DualDescentConfig(max_iters=300, step_decay=0.98))
    if wrap_source is not None:  # multi-host: route windows over hosts
        sample_window = wrap_source(pipe, sample_window)
    st = run_stream(pipe, sizes, sample_window,
                    budget_trace=budget_trace, scale_trace=scale_trace,
                    forecast=args.ci_forecast, prefetch=args.prefetch,
                    obs=obs)
    header = " ".join(f"{'ci_' + r[-1]:>6} {'spd/bud_' + r[-1]:>9}"
                      for r in names)
    print(f"{'win':>4} {'n':>5} {'split':>12} {header} {'revenue':>9} "
          f"{'dispatch_ms':>11}")
    ledgers = {
        r: CarbonLedger(chains, traces[r], window_s=window_s,
                        phase_s=phase_s, name=r, obs=obs,
                        embodied_g_per_device_h=args.embodied_g_per_device_h,
                        n_devices=args.devices)
        for r in names}
    for t, r in enumerate(st.windows):
        regions = r.regions_np
        dec = r.decisions_np
        split = [int(x) for x in np.bincount(regions,
                                             minlength=len(names))]
        spends = _host_np(r.region_spend)
        cols = " ".join(
            f"{ci_w[n_][t]:>6.0f} "
            f"{spends[k] / r.k_budget[k]:>9.3f}"
            for k, n_ in enumerate(names))
        print(f"{t:>4} {r.n_valid:>5} {str(split):>12} {cols} "
              f"{r.revenue_np.sum():>9.1f} {st.dispatch_ms[t]:>11.2f}")
        for k, n_ in enumerate(names):
            ledgers[n_].record(dec[regions == k], t=t, ci=ci_w[n_][t])
    print(f"[serve] {n_w} windows in {st.wall_s:.2f}s "
          f"({n_w / st.wall_s:.1f} win/s)")
    report_path = args.carbon_report or os.path.join(
        os.path.dirname(__file__), "..", "..", "..", "results",
        "carbon_report_geo.csv")
    geo_report_csv(ledgers, report_path)
    print(f"\n[serve] per-region carbon ledger -> "
          f"{os.path.abspath(report_path)}")
    for n_, led in ledgers.items():
        rep = led.report()
        print(f"    {n_}: {rep['gco2e']:.4e} g operational + "
              f"{rep['embodied_gco2e']:.4e} g embodied = "
              f"{rep['total_gco2e']:.4e} gCO2e "
              f"({rep['n_requests']} requests)")
    return st


def _geotenants_stream(chains, server, params, rcfg, sizes,
                       flops_budget, args, sample_window, mesh=None,
                       obs=None, wrap_source=None):
    """The combined tenant x region day: per-tenant gram budgets AND
    per-region gram caps priced in one fused pass (the ConstraintSpec
    headline).  Budget trace entries are the (T + R,) concatenation -
    tenant grams first - and the per-(tenant, region) spends come back
    in WindowResult.tr_spend."""
    import os

    from repro.carbon.controller import grams_per_flop
    from repro.carbon.intensity import two_region_traces
    from repro.carbon.ledger import DAY_S, CarbonLedger, geo_report_csv
    from repro.core.primal_dual import DualDescentConfig
    from repro.serving.spec import (ConstraintSpec, GlobalAxis,
                                    RegionAxis, TenantAxis)

    if args.tenant_mode == "independent":
        raise SystemExit("--scenario geotenants composes tenants and "
                         "regions in ONE pipeline; --tenant-mode "
                         "independent contradicts that (use shared or "
                         "priced)")
    t_n = args.tenants
    traces = two_region_traces(mean=args.ci_mean,
                               offset_h=args.geo_offset_h)
    names = list(traces)
    r_n = len(names)
    n_w = len(sizes)
    window_s = DAY_S / n_w
    phase_s = args.ci_phase_h * 3600.0
    kpf = grams_per_flop(1.0)
    ci_w = {r: traces[r].resample(n_w, window_s, phase_s=phase_s)
            for r in names}
    scale_trace = np.stack([kpf * ci_w[r] for r in names], axis=1)
    g_total = flops_budget * kpf * args.ci_mean  # grams per window
    # distinct per-tenant tightness: budgets spread by --tenant-spread
    # (ratio of the loosest to the tightest tenant), summing to g_total
    w = np.linspace(1.0, args.tenant_spread, t_n)
    tenant_g = (g_total * w / w.sum()).astype(np.float64)
    region_g = np.full(r_n, args.region_cap_frac * g_total)
    budget_trace = np.tile(np.concatenate([tenant_g, region_g]),
                           (n_w, 1))
    split = args.geo_split
    print(f"[serve] geotenants day: {n_w} windows x "
          f"{window_s / 3600.0:.2f} h, {t_n} tenants x {r_n} regions "
          f"(offset {args.geo_offset_h:.0f} h), tenant grams "
          + "/".join(f"{g:.2e}" for g in tenant_g)
          + f", region cap {region_g[0]:.2e} g "
          f"({args.region_cap_frac:.0%} of total), split {split}, "
          f"tenant-mode {args.tenant_mode}")
    spec = ConstraintSpec([
        TenantAxis(tuple(tenant_g),
                   priced=args.tenant_mode == "priced"),
        RegionAxis(r_n, names=tuple(names), split=split),
        GlobalAxis(pricing="carbon"),
    ])
    pipe = ServingPipeline.from_spec(
        server, params, rcfg, spec, mesh=mesh, obs=obs,
        dual_cfg=DualDescentConfig(max_iters=300, step_decay=0.98))
    if wrap_source is not None:  # multi-host: route windows over hosts
        sample_window = wrap_source(pipe, sample_window)
    st = run_stream(pipe, sizes, sample_window,
                    budget_trace=budget_trace, scale_trace=scale_trace,
                    forecast=args.ci_forecast, prefetch=args.prefetch,
                    obs=obs)
    t_hdr = " ".join(f"{'t' + str(k) + ' s/b':>8}" for k in range(t_n))
    r_hdr = " ".join(f"{'r_' + r[-1] + ' s/b':>8}" for r in names)
    print(f"{'win':>4} {'n':>5} {'split':>12} {t_hdr} {r_hdr} "
          f"{'revenue':>9} {'dispatch_ms':>11}")
    ledgers = {
        r: CarbonLedger(chains, traces[r], window_s=window_s,
                        phase_s=phase_s, name=r, obs=obs,
                        embodied_g_per_device_h=args.embodied_g_per_device_h,
                        n_devices=args.devices)
        for r in names}
    tenant_spend = np.zeros(t_n)
    for t, r in enumerate(st.windows):
        regions = r.regions_np
        dec = r.decisions_np
        split_c = [int(x) for x in np.bincount(regions, minlength=r_n)]
        tr = _host_np(r.tr_spend)
        tenant_spend += tr.sum(axis=1)
        t_cols = " ".join(f"{tr[k].sum() / tenant_g[k]:>8.3f}"
                          for k in range(t_n))
        r_cols = " ".join(f"{tr[:, k].sum() / region_g[k]:>8.3f}"
                          for k in range(r_n))
        print(f"{t:>4} {r.n_valid:>5} {str(split_c):>12} {t_cols} "
              f"{r_cols} {r.revenue_np.sum():>9.1f} "
              f"{st.dispatch_ms[t]:>11.2f}")
        for k, n_ in enumerate(names):
            ledgers[n_].record(dec[regions == k], t=t, ci=ci_w[n_][t])
    print(f"[serve] {n_w} windows in {st.wall_s:.2f}s "
          f"({n_w / st.wall_s:.1f} win/s)")
    print("[serve] day totals, per tenant (spend_g / budget_g): "
          + " ".join(f"t{k}={tenant_spend[k] / (n_w * tenant_g[k]):.3f}"
                     for k in range(t_n)))
    report_path = args.carbon_report or os.path.join(
        os.path.dirname(__file__), "..", "..", "..", "results",
        "carbon_report_geotenants.csv")
    geo_report_csv(ledgers, report_path)
    print(f"[serve] per-region carbon ledger -> "
          f"{os.path.abspath(report_path)}")
    return st


def _legacy_carbon_loop(exp, server, params, rcfg, sizes, cb, ledger,
                        sample_window, pricing):
    """Host-loop carbon day on CarbonBudgetController (the --legacy twin
    of _carbon_stream)."""
    import jax.numpy as jnp

    from repro.carbon.controller import CarbonBudgetController

    score = make_legacy_scorer(exp, rcfg)
    ctl = CarbonBudgetController(exp.chains, cb, ledger=ledger,
                                 pricing=pricing)
    total_rev = total_flops = 0.0
    print(f"{'win':>4} {'n':>5} {'ci_g/kwh':>9} {'spend_g/budget_g':>17} "
          f"{'lam':>12} {'downgraded':>10} {'revenue':>9}")
    for t, n in enumerate(sizes):
        ctx, rows = sample_window(t, n)
        rewards = np.asarray(score(params, jnp.asarray(ctx, jnp.float32)))
        dec = ctl.step_window(rewards)
        rev, _ = server.serve(rows, dec)
        s = ctl.stats[-1]
        total_rev += rev.sum()
        total_flops += s.flops
        print(f"{t:>4} {n:>5} {s.ci_g_per_kwh:>9.1f} "
              f"{s.spend_g / s.budget_g:>17.3f} {s.lam:>12.3e} "
              f"{s.downgraded:>10d} {rev.sum():>9.1f}")
    return total_rev, total_flops


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="GreenFlow streaming serving (fused pipeline)")
    ap.add_argument("--windows", type=int, default=12)
    ap.add_argument("--requests", type=int, default=96,
                    help="requests per normal window")
    ap.add_argument("--scenario", default="spike",
                    choices=tuple(SCENARIOS))
    ap.add_argument("--spike", type=float, default=3.0,
                    help="traffic multiplier on the spike windows")
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--tenant-mode", default="shared",
                    choices=("shared", "priced", "independent"))
    ap.add_argument("--budget-frac", type=float, default=0.6)
    ap.add_argument("--shards", type=int, default=0,
                    help=">0: shard_map over an N-way request mesh")
    ap.add_argument("--processes", type=int, default=0,
                    help=">1: join a jax.distributed group of N serve "
                         "processes (one per host); the request mesh "
                         "then spans every host's devices and each "
                         "host generates its slice of every window "
                         "(see the multi-host runbook above)")
    ap.add_argument("--process-id", type=int, default=None,
                    help="this process's rank in the --processes group "
                         "(default: $GREENFLOW_PROCESS_ID)")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of the jax.distributed coordinator "
                         "(process 0's address; default: "
                         "$GREENFLOW_COORDINATOR)")
    ap.add_argument("--small", action="store_true", help="CI-sized world")
    ap.add_argument("--source", default="table",
                    choices=("table", "generated", "memmap"),
                    help="request source: index the materialized eval "
                         "universe, stream a hash-generated one, or "
                         "replay memmapped tables")
    ap.add_argument("--users", type=int, default=100_000,
                    help="--source generated: size of the streamed "
                         "user universe")
    ap.add_argument("--replay-dir", default=None,
                    help="--source memmap: directory for the saved "
                         ".npy universe (default: "
                         "results/replay_universe)")
    ap.add_argument("--legacy", action="store_true",
                    help="run the seed's host loop instead")
    ap.add_argument("--ci-trace", default="diurnal",
                    choices=("diurnal", "duck", "constant"),
                    help="grid-intensity shape for --scenario carbon")
    ap.add_argument("--ci-csv", default=None,
                    help="load the intensity trace from an exported CSV "
                         "(ichnos parse_ci_intervals layouts)")
    ap.add_argument("--ci-mean", type=float, default=450.0,
                    help="mean grid intensity, gCO2e/kWh")
    ap.add_argument("--ci-phase-h", type=float, default=0.0,
                    help="hours the intensity day leads the traffic day")
    ap.add_argument("--carbon-pricing", default="carbon",
                    choices=("carbon", "flops"))
    ap.add_argument("--carbon-report", default=None,
                    help="CSV path for the carbon ledger (default: "
                         "results/carbon_report.csv, georegions: "
                         "results/carbon_report_geo.csv)")
    ap.add_argument("--ci-forecast", action="store_true",
                    help="warm-start the nearline dual on the NEXT "
                         "window's known CI (carbon/georegions)")
    ap.add_argument("--geo-offset-h", type=float, default=8.0,
                    help="hours region b's CI peak trails region a's")
    ap.add_argument("--geo-split", default="flow",
                    choices=("flow", "argmax"),
                    help="region-tie rounding: 'flow' = exact "
                         "proportional flow split of the degenerate "
                         "window, 'argmax' = the historical knife edge")
    ap.add_argument("--tenant-spread", type=float, default=4.0,
                    help="geotenants: gram-budget ratio of the loosest "
                         "to the tightest tenant")
    ap.add_argument("--region-cap-frac", type=float, default=0.6,
                    help="geotenants: each region's per-window gram cap "
                         "as a fraction of the total tenant grams")
    ap.add_argument("--embodied-g-per-device-h", type=float,
                    default=DEFAULT_EMBODIED_G_PER_DEVICE_H,
                    help="embodied-carbon amortization per device-hour "
                         "(default: the ichnos-style server constant; "
                         "0 disables the ledger line)")
    ap.add_argument("--devices", type=int, default=1,
                    help="devices metered for embodied carbon (per "
                         "region in georegions)")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="window-prep prefetch queue depth (0 = the "
                         "sequential double-buffered reference path)")
    ap.add_argument("--metrics-out", default=None,
                    help="write a Prometheus-text metrics snapshot here "
                         "at exit (plus a JSON snapshot at PATH.json and "
                         "the per-window JSONL flight log at "
                         "PATH.windows.jsonl)")
    ap.add_argument("--trace-out", default=None,
                    help="write the host span trace as Chrome "
                         "trace-event JSON (open in ui.perfetto.dev or "
                         "chrome://tracing; prefetch and serving "
                         "threads land on separate tracks)")
    ap.add_argument("--obs-interval", type=int, default=0,
                    help=">0: print a compact live telemetry line every "
                         "N windows")
    ap.add_argument("--profile-dir", default=None,
                    help="run under jax.profiler.trace writing here; "
                         "host spans become TraceAnnotations lined up "
                         "against XLA device events")
    return ap


@dataclass
class ServeRun:
    """What one ``serve`` call streamed: the StreamStats of every
    pipeline it ran (empty for the --legacy host loops) and the run's
    total clicks and FLOPs."""

    streams: list[StreamStats]
    total_revenue: float
    total_flops: float


def serve(args, stack, *, obs=None, multihost: bool = False) -> ServeRun:
    """Stream the scenario that ``args`` (from ``build_parser``) names
    over ``stack`` = (exp, server, params, rcfg), the tuple
    ``experiments.build_serving_stack`` returns."""
    exp, server, params, rcfg = stack
    streams = []
    chains = exp.chains
    budget = args.budget_frac * chains.costs.max() * args.requests
    n_tenants = (args.tenants
                 if args.scenario in ("tenants", "geotenants") else 1)
    sc = TrafficScenario(args.scenario, args.windows, args.requests,
                         spike_mult=args.spike, n_tenants=n_tenants)
    sizes = sc.window_sizes()

    if args.source != "table":
        if args.legacy:
            raise SystemExit("--legacy indexes the materialized server; "
                             "the streaming --source forms have no "
                             "legacy loop")
        from repro.data.request_source import (GeneratedSource,
                                               TableReplaySource)
        if args.source == "generated":
            from dataclasses import replace

            from repro.data.synthetic import StreamingWorld
            wcfg = replace(exp.cfg.world, n_users=args.users)
            source = GeneratedSource(StreamingWorld.build(wcfg),
                                     exp.models, chains,
                                     expose=exp.cfg.expose, obs=obs)
            print(f"[serve] source: generated stream over "
                  f"U={args.users:,} hash-materialized users (no per-"
                  f"user tables held)")
        else:
            import os
            path = args.replay_dir or os.path.join(
                os.path.dirname(__file__), "..", "..", "..", "results",
                "replay_universe")
            if not os.path.exists(os.path.join(path, "meta.json")):
                print(f"[serve] saving replay universe -> {path}")
                TableReplaySource.from_server(
                    server, exp.ctx_eval).save(path)
            source = TableReplaySource.load(path, chains)
            print(f"[serve] source: memmapped replay of "
                  f"U={source.n_users:,} users from {path}")
        # streaming pipelines build over the layout-only universe; the
        # source plugs straight into run_stream (duck-typed .window)
        server = source.universe
        sample_window = source
    else:
        rng = np.random.default_rng(0)
        n_eval = exp.ctx_eval.shape[0]

        def sample_window(t, n):
            rows = rng.integers(0, n_eval, n)
            return exp.ctx_eval[rows], rows

    mesh = None
    wrap_source = None
    if multihost:
        from repro.launch.mesh import make_request_mesh
        mesh = make_request_mesh()  # spans every process's devices

        def wrap_source(pipe, src_):
            from repro.distributed.multihost import MultihostSource
            return MultihostSource(src_, pipe)
    elif args.shards > 0 and not args.legacy:
        from repro.launch.mesh import make_request_mesh
        mesh = make_request_mesh(args.shards)

    if args.scenario == "carbon":
        # the run is one 24 h day: the diurnal traffic curve spans the
        # n_windows horizon, so the intensity day must span it too
        import os

        from repro.carbon.controller import CarbonBudget
        from repro.carbon.ledger import DAY_S, CarbonLedger

        trace = _build_ci_trace(args)
        window_s = DAY_S / len(sizes)
        cb = CarbonBudget.from_flops(
            float(budget), trace, window_s=window_s,
            phase_s=args.ci_phase_h * 3600.0)
        ledger = CarbonLedger(
            chains, trace, window_s=window_s, phase_s=cb.phase_s,
            embodied_g_per_device_h=args.embodied_g_per_device_h,
            n_devices=args.devices, obs=obs)
        print(f"[serve] carbon day: {len(sizes)} windows x "
              f"{window_s / 3600.0:.2f} h, CI '{trace.name}' mean "
              f"{trace.mean():.0f} g/kWh, budget "
              f"{cb.grams_per_window:.3e} g/window "
              f"({args.carbon_pricing} pricing)")
        if args.legacy:
            total_rev, total_flops = _legacy_carbon_loop(
                exp, server, params, rcfg, sizes, cb, ledger,
                sample_window, args.carbon_pricing)
        else:
            streams = [_carbon_stream(
                server, params, rcfg, sizes, cb, ledger,
                sample_window, args.carbon_pricing, mesh=mesh,
                forecast=args.ci_forecast, prefetch=args.prefetch,
                obs=obs, wrap_source=wrap_source)]
        report_path = args.carbon_report or os.path.join(
            os.path.dirname(__file__), "..", "..", "..", "results",
            "carbon_report.csv")
        ledger.to_csv(report_path)
        rep = ledger.report()
        print(f"\n[serve] carbon ledger -> {os.path.abspath(report_path)}")
        print(f"    realized      {rep['kwh']:.4e} kWh  "
              f"{rep['gco2e']:.4e} gCO2e")
        print(f"    all-max base  {rep['baseline_kwh']:.4e} kWh  "
              f"{rep['baseline_gco2e']:.4e} gCO2e")
        print(f"    embodied      {rep['embodied_gco2e']:.4e} gCO2e "
              f"({args.devices} device(s) amortized)  total "
              f"{rep['total_gco2e']:.4e} gCO2e")
        print(f"    daily savings {rep['daily_saved_kwh']:.4e} kWh/day  "
              f"{rep['daily_saved_tco2e']:.4e} tCO2e/day "
              f"(vs all-max-chain)")
        for s, v in rep["stage_flops"].items():
            print(f"    stage {s:10s} {v:.4e} FLOPs")
        for m, v in rep["model_flops"].items():
            print(f"    model {m:10s} {v:.4e} FLOPs")
    elif args.scenario == "georegions":
        if args.legacy:
            raise SystemExit("--scenario georegions has no legacy loop "
                             "(the router exists only in the fused pass)")
        streams = [_geo_stream(
            chains, server, params, rcfg, sizes, float(budget), args,
            sample_window, mesh=mesh, obs=obs, wrap_source=wrap_source)]
    elif args.scenario == "geotenants":
        if args.legacy:
            raise SystemExit("--scenario geotenants has no legacy loop "
                             "(the combined tenant x region pass exists "
                             "only in the fused pipeline)")
        streams = [_geotenants_stream(
            chains, server, params, rcfg, sizes, float(budget), args,
            sample_window, mesh=mesh, obs=obs, wrap_source=wrap_source)]
    elif args.legacy:
        total_rev, total_flops = _legacy_loop(exp, server, params, rcfg,
                                              sizes, budget)
    else:
        if args.scenario == "tenants" and args.tenant_mode == "independent":
            if multihost:
                raise SystemExit("--tenant-mode independent runs one "
                                 "pipeline per tenant; compose with "
                                 "--processes via shared or priced")
            pipes = [ServingPipeline(server, params, rcfg,
                                     budget / n_tenants, obs=obs)
                     for _ in range(n_tenants)]
            streams = [run_stream(
                p, [n // n_tenants for n in sizes], sample_window,
                prefetch=args.prefetch, obs=obs) for p in pipes]
            for t in range(len(sizes)):
                spends = [float(s.windows[t].spend) for s in streams]
                print(f"win {t:>3}: per-tenant spend/budget "
                      + " ".join(f"{sp / (budget / n_tenants):.3f}"
                                 for sp in spends))
        else:
            tb = None
            if args.scenario == "tenants":  # shared or per-tenant prices
                tb = np.full(n_tenants, budget / n_tenants, np.float32)
            pipe = ServingPipeline(server, params, rcfg, budget,
                                   mesh=mesh, tenant_budgets=tb,
                                   tenant_mode=(args.tenant_mode
                                                if tb is not None
                                                else "shared"), obs=obs)
            if wrap_source is not None:  # multi-host window routing
                sample_window = wrap_source(pipe, sample_window)
            st = run_stream(pipe, sizes, sample_window,
                            prefetch=args.prefetch, obs=obs)
            streams = [st]
            priced = tb is not None and args.tenant_mode == "priced"
            lam_hdr = "lam(per-tenant)" if priced else "lam"
            print(f"{'win':>4} {'n':>5} {'spend/budget':>13} "
                  f"{lam_hdr:>12} {'downgraded':>10} {'revenue':>9} "
                  f"{'dispatch_ms':>11}")
            for t, r in enumerate(st.windows):
                if priced:
                    lam_disp = "/".join(
                        f"{v:.2e}" for v in _host_np(r.lam_after))
                else:
                    lam_disp = f"{_f(r.lam_after):.3e}"
                print(f"{t:>4} {r.n_valid:>5} "
                      f"{_f(r.spend) / r.budget:>13.3f} "
                      f"{lam_disp:>12} "
                      f"{int(r.downgraded):>10d} "
                      f"{r.revenue_np.sum():>9.1f} "
                      f"{st.dispatch_ms[t]:>11.2f}")
            c_min = float(chains.costs.min())
            print(f"[serve] {len(sizes)} windows in {st.wall_s:.2f}s "
                  f"({len(sizes) / st.wall_s:.1f} win/s), worst overshoot "
                  f"vs cap: {st.overshoot(c_min) * 100:.3f}%")
    if streams:
        total_rev = sum(st.total_revenue for st in streams)
        total_flops = sum(_f(r.flops) for st in streams
                          for r in st.windows)
    return ServeRun(streams, float(total_rev), float(total_flops))


def main(argv=None):
    args = build_parser().parse_args(argv)
    multihost = False
    host = None
    if args.processes > 1 or args.coordinator:
        from repro.distributed import multihost as mh
        if args.legacy:
            raise SystemExit("--processes runs the fused SPMD pipeline; "
                             "--legacy is single-process")
        if args.source == "table":
            raise SystemExit("--processes needs a streaming --source "
                             "(generated or memmap): every host "
                             "generates its own slice of each window")
        if args.shards > 0:
            raise SystemExit("--shards picks a device subset; with "
                             "--processes the mesh is always the full "
                             "process-spanning device set (drop "
                             "--shards)")
        multihost = mh.initialize(
            coordinator=args.coordinator,
            num_processes=args.processes or None,
            process_id=args.process_id)
        if not multihost:
            raise SystemExit("--processes > 1 needs a --coordinator "
                             "(or $GREENFLOW_COORDINATOR)")
        host = mh.host_label()
        # per-host artifact files: suffix every output with the label
        for attr in ("metrics_out", "trace_out"):
            if getattr(args, attr):
                setattr(args, attr, getattr(args, attr) + "." + host)
        print(f"[serve] multihost: {mh.host_report()}")
    print(f"[serve] jax compilation cache -> {enable_compile_cache()}")
    obs = None
    if (args.metrics_out or args.trace_out or args.obs_interval
            or args.profile_dir):
        from repro.obs import Obs, WindowEventLog
        obs = Obs(events=(WindowEventLog(args.metrics_out
                                         + ".windows.jsonl")
                          if args.metrics_out else None),
                  interval=args.obs_interval,
                  annotate=bool(args.profile_dir), host=host)
    if args.profile_dir:
        import jax
        jax.profiler.start_trace(args.profile_dir)
        print(f"[obs] jax profiler trace -> {args.profile_dir}")

    print("[serve] building world + training cascade & reward models ...")
    stack = build_serving_stack(serve_config(small=args.small), verbose=True)
    run = serve(args, stack, obs=obs, multihost=multihost)

    print("\n[serve] PFEC (GreenFlow serving run):")
    rep = pfec_report(clicks=run.total_revenue, flops=run.total_flops)
    for k, v in rep.as_row().items():
        print(f"    {k:14s} {v}")

    if args.profile_dir:
        import jax
        jax.profiler.stop_trace()
    if obs is not None:
        import os
        if args.metrics_out:
            prom, js = obs.export(args.metrics_out)
            print(f"[obs] metrics -> {prom} (+ {os.path.basename(js)})")
            if obs.events is not None:
                print(f"[obs] window log -> {obs.events.path} "
                      f"({obs.events.rows_written} rows)")
        if args.trace_out:
            path = obs.tracer.write(args.trace_out)
            print(f"[obs] trace -> {path} "
                  f"({len(obs.tracer.events)} spans; open in "
                  f"ui.perfetto.dev)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
