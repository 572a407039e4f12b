"""Scale benchmark: the streaming request path at U >= 100k.

    PYTHONPATH=src python benchmarks/bench_scale.py [--json PATH]
    PYTHONPATH=src python benchmarks/bench_scale.py --small   # CI smoke

The materialized serving path precomputes (U, I) stage scores, (U, I)
clicks and (G, U, cap) compact tables before the first request; host
memory scales with the universe.  This benchmark drives the SAME fused
geotenants pipeline (per-tenant dual prices x per-region caps, one
jitted pass) from a ``GeneratedSource`` - every window generated,
scored and compacted on the fly - and measures what the streaming
refactor claims:

  * requests/sec end-to-end (prefetched ``run_stream``: a background
    worker builds windows ahead of the serving thread; tables compact
    ON DEVICE and the dual chain runs donated) and the serve-only
    window latency (p50/p99, host-blocked), with a per-run
    prep/stall/h2d breakdown;
  * the same big universe through the exact PR 6 path (host table
    compaction, sequential prep, undonated dual) - bitwise-identical
    decisions, a host->device transfer comparison, and a >= 2x
    throughput gate on full-size runs with >= 4 cores (the overlap
    claim needs parallel hardware; below that the speedup is
    report-only, like ci.yml skipping wall-clock speedup asserts);
  * peak host RSS at a small universe vs U >= 100k under an IDENTICAL
    window schedule - the gate asserts the delta stays under
    --rss-gate-mb, i.e. nothing anywhere allocates O(U) (for scale,
    the JSON also reports what materializing U would cost);
  * jit recompiles per window under decade-ladder traffic swings
    (1x..--spike x): with pow2 bucketed padding every shape compiles
    once, and the gate asserts ZERO steady-state recompiles;
  * the small-U parity gate: replaying the materialized server's own
    universe through the chunked path (``TableReplaySource``) is
    BITWISE identical - decisions, revenues, prices, spends - in both
    the plain and the geotenants pipeline;
  * the big universe again with the FULL repro.obs telemetry stack
    live (metrics registry + span tracer + JSONL window exporter):
    bitwise-identical to the telemetry-off run, and on full-size
    multi-core runs a <2% throughput-overhead gate.

Everything model-sized stays at the cached --small serving stack; only
the user universe scales, which is exactly the point.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _vm_mb(key: str = "VmRSS:") -> float:
    """Current (VmRSS:) or peak (VmHWM:) resident set, MB."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith(key):
                    return int(line.split()[1]) / 1024.0
    except OSError:
        pass
    return float("nan")


class _MeteredSource:
    """Wraps a RequestSource; samples VmRSS after each window build."""

    def __init__(self, src):
        self._src = src
        self.rss_mb: list[float] = []

    def window(self, t, n):
        chunk = self._src.window(t, n)
        self.rss_mb.append(_vm_mb())
        return chunk


def _geotenants_spec(chains, n_base, budget_frac, t_n=2, r_n=2):
    from repro.serving.spec import (ConstraintSpec, GlobalAxis,
                                    RegionAxis, TenantAxis)

    per_req = budget_frac * float(chains.costs.max())
    total = per_req * n_base
    spec = ConstraintSpec([
        TenantAxis(tuple(np.full(t_n, total / t_n)), priced=True),
        RegionAxis(r_n, names=("region_a", "region_b")),
        GlobalAxis(pricing="carbon"),
    ])
    scale = np.array([1.0, 1.3], np.float32)  # region cost ratios

    def traces(sizes):
        """Budgets scale with the window (tenant grams first, then the
        per-region caps at 60% of the total); cost scales are fixed."""
        bt, st_ = [], []
        for n in sizes:
            tot = per_req * n
            bt.append(np.concatenate([np.full(t_n, tot / t_n),
                                      np.full(r_n, 0.6 * tot)])
                      .astype(np.float32))
            st_.append(scale)
        return bt, st_

    return spec, traces


def _parity_gate(exp, server, params, rcfg, *, windows=6, base=48,
                 budget_frac=0.5) -> dict:
    """Small-U bitwise gate: the chunked TableReplaySource path against
    indexing the materialized server - same arrivals, free-running
    prices - in the plain AND the geotenants pipeline."""
    from repro.data.request_source import TableReplaySource
    from repro.serving.pipeline import ServingPipeline
    from repro.serving.stream import (TrafficScenario, run_stream,
                                      scenario_windows)

    chains = exp.chains
    budget = budget_frac * float(chains.costs.max()) * base
    src = TableReplaySource.from_server(server, exp.ctx_eval, seed=7)

    def sample(t, n):
        rows = src.arrivals(t, n)
        return exp.ctx_eval[rows], rows

    checked = 0
    for mode in ("plain", "geotenants"):
        sc = TrafficScenario("spike", windows, base, spike_mult=3.0,
                             n_tenants=2 if mode == "geotenants" else 1)
        sizes = scenario_windows(sc)
        if mode == "plain":
            pipe_m = ServingPipeline(server, params, rcfg, budget)
            pipe_s = ServingPipeline(src.universe, params, rcfg, budget)
            kw = {}
        else:
            spec, traces = _geotenants_spec(chains, base, budget_frac)
            bt, st_ = traces(sizes)
            pipe_m = ServingPipeline.from_spec(server, params, rcfg,
                                               spec)
            pipe_s = ServingPipeline.from_spec(src.universe, params,
                                               rcfg, spec)
            kw = {"budget_trace": bt, "scale_trace": st_}
        res_m = run_stream(pipe_m, sizes, sample, **kw)
        res_s = run_stream(pipe_s, sizes, src, **kw)
        for t, (a, b) in enumerate(zip(res_m.windows, res_s.windows)):
            tag = f"{mode} w{t}"
            assert np.array_equal(a.decisions_np, b.decisions_np), tag
            assert np.array_equal(a.revenue_np, b.revenue_np), tag
            assert np.array_equal(np.asarray(a.spend),
                                  np.asarray(b.spend)), tag
            assert np.array_equal(np.asarray(a.lam_after),
                                  np.asarray(b.lam_after)), tag
            checked += 1
    return {"bitwise": True, "windows_checked": checked,
            "modes": ["plain", "geotenants"]}


def _swing_run(exp, params, rcfg, *, n_users, sizes, lat_sizes,
               budget_frac=0.5, chunk=512, device_tables=True,
               prefetch=2, donate=True, telemetry=False):
    """One streamed geotenants run at ``n_users``: a prefetched
    throughput pass over ``sizes``, then a host-blocked latency pass
    over ``lat_sizes`` on the same warm pipeline.

    ``device_tables=False, prefetch=0, donate=False`` reproduces the
    PR 6 serving path exactly (host table compaction, sequential
    double-buffered prep, undonated dual chain) - the baseline the
    zero-stall claim is measured against.  ``telemetry=True`` runs with
    the FULL repro.obs stack live (enabled registry, span tracer,
    JSONL window exporter) - the arm the <2% overhead gate compares
    against the telemetry-off twin.  Returns ``(metrics,
    stream_stats)`` so callers can bitwise-compare the modes."""
    import jax

    from dataclasses import replace

    from repro.data.request_source import GeneratedSource
    from repro.data.synthetic import StreamingWorld
    from repro.serving.pipeline import ServingPipeline
    from repro.serving.stream import run_stream

    obs = None
    if telemetry:
        import tempfile

        from repro.obs import Obs, WindowEventLog
        obs = Obs(events=WindowEventLog(os.path.join(
            tempfile.mkdtemp(prefix="bench_scale_obs_"),
            "windows.jsonl")))
    chains = exp.chains
    wcfg = replace(exp.cfg.world, n_users=n_users)
    gen = GeneratedSource(StreamingWorld.build(wcfg), exp.models,
                          chains, expose=exp.cfg.expose, seed=5,
                          chunk=chunk, device_tables=device_tables,
                          obs=obs)
    spec, traces = _geotenants_spec(chains, sizes[0], budget_frac)
    pipe = ServingPipeline.from_spec(gen.universe, params, rcfg, spec,
                                     bucketing="pow2",
                                     donate_dual=donate, obs=obs)
    src = _MeteredSource(gen)
    bt, st_ = traces(sizes)
    rss0 = _vm_mb()
    st = run_stream(pipe, sizes, src, budget_trace=bt, scale_trace=st_,
                    prefetch=prefetch, obs=obs)
    total_req = int(sum(sizes))

    # serve-only latency: chunk built first, then submit -> results
    # host-ready (the nearline price chains on-device, off this path).
    # Device-built tables are ASYNC futures - force them before the
    # timer so table production stays attributed to prep, not serve.
    lat_s = []
    bt2, st2 = traces(lat_sizes)
    for i, n in enumerate(lat_sizes):
        c = gen.window(1000 + i, n)
        jax.block_until_ready(c.tables)
        t0 = time.perf_counter()
        r = pipe.serve_window(c.ctx, c.rows, tables=c.tables,
                              budget=bt2[i], cost_scale=st2[i])
        jax.block_until_ready((r.decisions, r.revenue, r.spend))
        lat_s.append(time.perf_counter() - t0)

    metrics = {
        "n_users": int(n_users),
        "mode": {"device_tables": bool(device_tables),
                 "prefetch": int(prefetch), "donate_dual": bool(donate),
                 "telemetry": bool(telemetry)},
        "sizes": [int(n) for n in sizes],
        "requests": total_req,
        "wall_s": round(st.wall_s, 3),
        "requests_per_sec": round(total_req / st.wall_s, 1),
        "compiles_per_window": st.compiles,
        "steady_state_recompiles": int(st.steady_compiles),
        "compiled_buckets": len({r.bucket for r in st.windows}),
        "prep_ms_total": round(float(sum(st.prep_ms)), 1),
        "stall_ms_total": round(float(sum(st.stall_ms)), 1),
        "submit_ms_total": round(float(sum(st.submit_ms)), 1),
        "h2d_mb": round(st.h2d_bytes / 1e6, 2),
        "table_cache": {"hits": int(gen.cache_hits),
                        "misses": int(gen.cache_misses)},
        "p50_window_ms": round(float(np.percentile(lat_s, 50)) * 1e3, 2),
        "p99_window_ms": round(float(np.percentile(lat_s, 99)) * 1e3, 2),
        "latency_sizes": [int(n) for n in lat_sizes],
        "rss_before_mb": round(rss0, 1),
        "peak_rss_mb": round(max(src.rss_mb), 1),
        "vm_hwm_mb": round(_vm_mb("VmHWM:"), 1),
        "total_revenue": round(st.total_revenue, 2),
    }
    return metrics, st


def run(*, users_small: int = 20_000, users_big: int = 150_000,
        base: int = 16, spike: float = 1000.0, cycles: int = 2,
        budget_frac: float = 0.5, rss_gate_mb: float = 200.0,
        small: bool = False, json_path: str | None = None) -> dict:
    from repro.experiments import build_serving_stack, serve_config
    from repro.serving.stream import TrafficScenario, scenario_windows

    if small:  # CI smoke: 3 decades, one ladder cycle, shorter latency
        spike, cycles = min(spike, 100.0), 1
    exp, server, params, rcfg = build_serving_stack(
        serve_config(small=True), verbose=True)

    print("[bench_scale] parity gate (small U, bitwise) ...")
    parity = _parity_gate(exp, server, params, rcfg)
    print(f"[bench_scale] parity OK over {parity['windows_checked']} "
          f"windows ({'+'.join(parity['modes'])})")

    decades = max(1, int(np.log10(max(10.0, spike))) + 1)
    sc = TrafficScenario("swing", decades * cycles, base,
                         spike_mult=spike, n_tenants=2)
    sizes = scenario_windows(sc)
    lat_sizes = scenario_windows(
        TrafficScenario("swing", decades, base, spike_mult=spike,
                        n_tenants=2))
    runs = {}
    streams = {}
    plans = (
        ("small_universe", users_small, {}),
        ("big_universe", users_big, {}),
        ("big_universe_pr6", users_big,
         {"device_tables": False, "prefetch": 0, "donate": False}),
        ("big_universe_obs", users_big, {"telemetry": True}),
    )
    for label, n_users, mode_kw in plans:
        print(f"[bench_scale] {label}: U={n_users:,}, "
              f"windows {sizes} ...")
        runs[label], streams[label] = _swing_run(
            exp, params, rcfg, n_users=n_users, sizes=sizes,
            lat_sizes=lat_sizes, budget_frac=budget_frac, **mode_kw)
        r = runs[label]
        print(f"[bench_scale]   {r['requests_per_sec']} req/s, "
              f"p99 {r['p99_window_ms']} ms, prep "
              f"{r['prep_ms_total']} ms, stall {r['stall_ms_total']} "
              f"ms, h2d {r['h2d_mb']} MB, peak RSS "
              f"{r['peak_rss_mb']} MB, steady recompiles "
              f"{r['steady_state_recompiles']}")

    # cross-mode parity: the device-table + prefetched + donated path
    # must reproduce the PR 6 host path bitwise at the big universe
    for t, (a, b) in enumerate(zip(streams["big_universe"].windows,
                                   streams["big_universe_pr6"].windows)):
        tag = f"mode parity w{t}"
        assert np.array_equal(a.decisions_np, b.decisions_np), tag
        assert np.array_equal(a.revenue_np, b.revenue_np), tag
        assert np.array_equal(np.asarray(a.spend),
                              np.asarray(b.spend)), tag
        assert np.array_equal(np.asarray(a.lam_after),
                              np.asarray(b.lam_after)), tag
    print(f"[bench_scale] mode parity OK over "
          f"{len(streams['big_universe'].windows)} windows "
          f"(device+prefetch+donate vs PR 6 path, bitwise)")
    # telemetry parity: the full obs stack (registry + tracer + JSONL
    # exporter) must not perturb a single decision, spend or price
    for t, (a, b) in enumerate(zip(streams["big_universe"].windows,
                                   streams["big_universe_obs"].windows)):
        tag = f"obs parity w{t}"
        assert np.array_equal(a.decisions_np, b.decisions_np), tag
        assert np.array_equal(a.revenue_np, b.revenue_np), tag
        assert np.array_equal(np.asarray(a.spend),
                              np.asarray(b.spend)), tag
        assert np.array_equal(np.asarray(a.lam_after),
                              np.asarray(b.lam_after)), tag
    print(f"[bench_scale] telemetry parity OK over "
          f"{len(streams['big_universe'].windows)} windows "
          f"(obs on vs off, bitwise)")
    speedup = (runs["big_universe"]["requests_per_sec"]
               / runs["big_universe_pr6"]["requests_per_sec"])
    print(f"[bench_scale] big-universe speedup vs PR 6 path: "
          f"{speedup:.2f}x")
    obs_overhead_pct = (runs["big_universe"]["requests_per_sec"]
                        / runs["big_universe_obs"]["requests_per_sec"]
                        - 1.0) * 100.0
    print(f"[bench_scale] telemetry overhead: "
          f"{obs_overhead_pct:+.2f}% throughput")

    # what the retired path would have allocated at U_big: four (U, I)
    # float32 stage-score matrices, a (U, I) click matrix and the
    # (G, U, cap) int+float compact tables
    i_n = exp.cfg.world.n_items
    g_n = int(server.compact.p_sorted.shape[0])
    cap = int(server.compact.cap)
    mat_mb = (users_big * i_n * 4 * 5 +
              users_big * g_n * cap * 8) / 1e6
    delta = (runs["big_universe"]["peak_rss_mb"]
             - runs["small_universe"]["peak_rss_mb"])
    result = {
        "config": {"base": base, "spike": spike, "cycles": cycles,
                   "budget_frac": budget_frac, "small": small,
                   "users_small": users_small, "users_big": users_big,
                   "n_items": int(i_n), "chains": exp.chains.n_chains,
                   "pipeline": "geotenants (2 tenants x 2 regions, "
                               "pow2 buckets)"},
        "parity_gate": parity,
        "runs": runs,
        "speedup_vs_pr6": round(speedup, 2),
        "obs_overhead_pct": round(obs_overhead_pct, 2),
        "peak_rss_delta_mb": round(delta, 1),
        "rss_gate_mb": rss_gate_mb,
        "materialized_tables_mb_at_big": round(mat_mb, 1),
        "steady_state_recompiles": int(
            sum(r["steady_state_recompiles"] for r in runs.values())),
    }
    assert result["steady_state_recompiles"] == 0, \
        "bucketed padding must keep the jit cache warm in steady state"
    assert delta < rss_gate_mb, (
        f"peak RSS grew {delta:.1f} MB from U={users_small:,} to "
        f"U={users_big:,} (gate {rss_gate_mb} MB): something allocates "
        f"O(U)")
    # the 2x claim is an OVERLAP claim: prefetch + device tables only
    # buy wall-clock when host prep and device execution can run on
    # different hardware.  Arm it on full-size multi-core runs; on a
    # single/dual-core host the two modes do the same serial work and
    # a wall-clock gate would only measure scheduler noise (same
    # policy as ci.yml skipping bench_chain_sim's --check-speedup).
    cores = os.cpu_count() or 1
    gated_speedup = (not small) and cores >= 4
    result["speedup_gate"] = (
        "armed" if gated_speedup else
        f"report-only ({'--small run' if small else f'{cores} cores'}: "
        f"prefetch overlap needs parallel hardware)")
    if gated_speedup:
        assert speedup >= 2.0, (
            f"big-universe throughput {speedup:.2f}x the PR 6 path "
            f"(gate: >= 2x): the zero-stall claim regressed")
    # the <2% telemetry budget is likewise a wall-clock measurement:
    # arm it on full-size multi-core runs, report-only elsewhere
    result["obs_overhead_gate"] = (
        "armed" if gated_speedup else
        f"report-only ({'--small run' if small else f'{cores} cores'}: "
        f"sub-2% deltas need a full-size run to rise above noise)")
    if gated_speedup:
        assert obs_overhead_pct < 2.0, (
            f"telemetry costs {obs_overhead_pct:.2f}% throughput "
            f"(gate: < 2%): observability must stay free-ish")
    result["gates"] = {"zero_steady_recompiles": True,
                       "rss_flat_wrt_users": True,
                       "bitwise_parity": True,
                       "mode_parity_bitwise": True,
                       "obs_parity_bitwise": True,
                       "speedup_2x": bool(gated_speedup),
                       "obs_overhead_lt_2pct": bool(gated_speedup)}
    if json_path is not None:
        from repro.obs.env import env_info
        result["env"] = env_info()
        path = os.path.abspath(json_path)
        with open(path, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        print(json.dumps(result, indent=2))
        print(f"[bench_scale] wrote {path}")
    return result


def main() -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json",
                    default=os.path.join(REPO, "BENCH_scale.json"))
    ap.add_argument("--small", action="store_true",
                    help="CI smoke: 100x swings, one ladder cycle")
    ap.add_argument("--users-small", type=int, default=20_000)
    ap.add_argument("--users", type=int, default=150_000,
                    help="the big universe (the U >= 100k claim)")
    ap.add_argument("--base", type=int, default=16,
                    help="requests in a 1x window (decades multiply it)")
    ap.add_argument("--spike", type=float, default=1000.0,
                    help="top of the decade ladder (1000 = 4 decades)")
    ap.add_argument("--cycles", type=int, default=2,
                    help="ladder repetitions in the throughput pass")
    ap.add_argument("--budget-frac", type=float, default=0.5)
    ap.add_argument("--rss-gate-mb", type=float, default=200.0)
    args = ap.parse_args()
    return run(users_small=args.users_small, users_big=args.users,
               base=args.base, spike=args.spike, cycles=args.cycles,
               budget_frac=args.budget_frac,
               rss_gate_mb=args.rss_gate_mb, small=args.small,
               json_path=args.json)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
