"""Carbon benchmark: carbon-aware vs constant-CI allocation over a day.

    PYTHONPATH=src python benchmarks/bench_carbon.py [--json PATH]

Protocol (deterministic, decision-level - no wall-clock): one diurnal
traffic day (the ``carbon`` scenario curve) is sampled once; both
allocators see the SAME requests, the same reward-model predictions,
and the same diurnal grid-intensity trace, at several traffic-vs-grid
phase offsets.  Allocation uses the exact dual oracle (bisection on the
scalar price, the same machinery as ``evaluate_methods``/``dual_bisect``)
so the comparison measures the *allocation policy*, not nearline lag:

  * constant-CI  - today's allocator: one FLOPs price for the whole day
    (CI treated as the constant mean, exactly the seed's Eq. 2 view),
    daily budget halfway between the serve floor (everyone on the
    cheapest chain) and the unconstrained spend - the band where
    allocation actually happens.  Realized FLOPs are then metered
    against the TRUE time-varying CI(t).
  * carbon-aware - the repro.carbon policy: effective per-request costs
    c_j(t) = flops_j * kappa * CI(t) and one reward-per-GRAM price,
    i.e. water-filling computation into green-grid hours.

Two frontier points are reported per phase:

  * ``equal_grams``    - carbon-aware given exactly the constant
    allocator's realized daily gCO2e: clicks retained/gained;
  * ``matched_clicks`` - the smallest gram budget whose clicks still
    match the constant allocator: gCO2e saved at equal-or-better
    clicks (the ISSUE acceptance gate, asserted for every phase).

The constant allocator's day is FEASIBLE for the carbon-aware policy at
the same gram budget, so at the exact dual the equal-grams point can
only gain clicks; the gain is strict because the optimum shifts spend
toward low-CI windows.  ``results/carbon_report.csv`` is the phase-0
carbon-aware day metered window-by-window by the CarbonLedger
(per-stage/per-model attribution + all-max-chain daily savings).
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _exact_alloc(R: np.ndarray, costs: np.ndarray, s_req: np.ndarray,
                 budget: float, *, iters: int = 80) -> np.ndarray:
    """Eq. 10 decisions at the smallest price fitting ``budget``.

    R (N, J) predicted rewards; costs (J,) FLOPs; s_req (N,) per-request
    cost scale (1 = FLOPs pricing, kappa*CI(t_i) = carbon pricing), so
    request i's effective cost vector is s_req[i] * costs.  Delegates to
    the ONE bisection oracle (``bench_geo._exact_alloc``, the general
    per-request-per-option form) so the two benchmarks' "exact dual"
    arms can never drift apart.
    """
    try:
        from benchmarks.bench_geo import _exact_alloc as general
    except ModuleNotFoundError:  # script mode: repo root not on sys.path
        import sys

        sys.path.insert(0, REPO)
        from benchmarks.bench_geo import _exact_alloc as general

    return general(R, s_req[:, None] * costs[None, :], budget,
                   iters=iters)


def run(*, windows: int = 24, requests: int = 64, band_frac: float = 0.5,
        ci_mean: float = 450.0, ci_amplitude: float = 0.45,
        phases=(0.0, 6.0, 12.0, 18.0), small: bool = True,
        json_path: str | None = None, report_path: str | None = None,
        check_dominance: bool = True) -> dict:
    from repro.carbon.controller import grams_per_flop
    from repro.carbon.intensity import diurnal_trace
    from repro.carbon.ledger import DAY_S, CarbonLedger
    from repro.experiments import (build_serving_stack, predicted_rewards,
                                   serve_config)
    from repro.serving.stream import TrafficScenario, scenario_windows

    exp, server, params, rcfg = build_serving_stack(
        serve_config(small=small), verbose=True)
    chains = exp.chains
    costs = chains.costs
    sizes = scenario_windows(TrafficScenario("carbon", windows, requests))
    window_s = DAY_S / windows
    trace = diurnal_trace(mean=ci_mean, rel_amplitude=ci_amplitude)
    kpf = grams_per_flop(1.0)  # g per FLOP per unit CI

    # one shared day of traffic: same arrivals for every allocator/phase
    pred = predicted_rewards(exp, params, rcfg, exp.ctx_eval)  # (U, J)
    rng = np.random.default_rng(0)
    rows = np.concatenate([rng.integers(0, pred.shape[0], n)
                           for n in sizes])
    w_of = np.repeat(np.arange(windows), sizes)
    R = pred[rows]
    true_rev = exp.revenue_eval[rows]
    ridx = np.arange(len(rows))

    def clicks_of(dec):
        return float(true_rev[ridx, dec].sum())

    # the allocation band: below `floor` Eq. 3b is infeasible, above
    # `natural` the constraint is slack and all policies coincide
    floor = float(costs.min()) * len(rows)
    natural = float(np.sum(costs[np.argmax(R, axis=1)]))
    f_budget = floor + band_frac * (natural - floor)

    rows_out = []
    ledger0 = None
    ones = np.ones(len(rows))
    for phase_h in phases:
        ci_w = trace.resample(windows, window_s, phase_s=phase_h * 3600.0)
        s_req = (kpf * ci_w)[w_of]  # g/FLOP seen by each request

        dec_c = _exact_alloc(R, costs, ones, f_budget)
        clicks_c = clicks_of(dec_c)
        grams_c = float(np.sum(s_req * costs[dec_c]))

        # frontier point 1: equal realized grams
        dec_eq = _exact_alloc(R, costs, s_req, grams_c)
        clicks_eq = clicks_of(dec_eq)
        grams_eq = float(np.sum(s_req * costs[dec_eq]))

        # frontier point 2: cheapest gram budget matching const's clicks.
        # Bracket: walk lo down until its clicks drop below const's (or
        # the serve floor is reached), so the bisection never silently
        # caps the reported saving at an arbitrary fraction.
        g_floor = float(costs.min() * np.sum(s_req))
        lo = 0.8 * grams_c
        while lo > g_floor and clicks_of(
                _exact_alloc(R, costs, s_req, lo, iters=60)) >= clicks_c:
            lo = max(g_floor, lo * 0.8)
        hi = grams_c
        for _ in range(20):
            mid = 0.5 * (lo + hi)
            if clicks_of(_exact_alloc(R, costs, s_req, mid,
                                      iters=60)) >= clicks_c:
                hi = mid
            else:
                lo = mid
        dec_m = _exact_alloc(R, costs, s_req, hi, iters=60)
        clicks_m = clicks_of(dec_m)
        grams_m = float(np.sum(s_req * costs[dec_m]))

        row = {
            "ci_phase_h": phase_h,
            "constant_ci": {"clicks": clicks_c, "gco2e": grams_c,
                            "flops": float(np.sum(costs[dec_c]))},
            "equal_grams": {"clicks": clicks_eq, "gco2e": grams_eq,
                            "flops": float(np.sum(costs[dec_eq])),
                            "clicks_delta_pct": round(
                                100 * (clicks_eq / clicks_c - 1), 2)},
            "matched_clicks": {"clicks": clicks_m, "gco2e": grams_m,
                               "flops": float(np.sum(costs[dec_m])),
                               "gco2e_saved_pct": round(
                                   100 * (1 - grams_m / grams_c), 2)},
            "dominates": bool(clicks_eq >= clicks_c
                              and clicks_m >= clicks_c
                              and grams_m < grams_c),
        }
        rows_out.append(row)
        print(f"[bench_carbon] phase {phase_h:>4.1f}h: const "
              f"{clicks_c:.0f} clicks @ {grams_c:.3e} g | equal-grams "
              f"{row['equal_grams']['clicks_delta_pct']:+.2f}% clicks | "
              f"matched-clicks "
              f"{row['matched_clicks']['gco2e_saved_pct']:+.2f}% g saved")

        if phase_h == phases[0]:
            ledger0 = CarbonLedger(chains, trace, window_s=window_s,
                                   phase_s=phase_h * 3600.0)
            for t, dec_w in enumerate(
                    np.split(dec_eq, np.cumsum(sizes)[:-1])):
                ledger0.record(dec_w, t=t)

    result = {
        "config": {"windows": windows, "requests": requests,
                   "band_frac": band_frac, "ci_mean": ci_mean,
                   "ci_amplitude": ci_amplitude, "small": small,
                   "chains": chains.n_chains, "window_s": window_s,
                   "n_requests_day": int(len(rows)),
                   "floor_flops": floor, "natural_flops": natural,
                   "daily_flops_budget": f_budget,
                   "traffic": "diurnal day curve (carbon scenario)",
                   "intensity": "diurnal, evening peak",
                   "allocator": "exact dual oracle (bisection), "
                                "decisions on reward-model predictions"},
        "phases": rows_out,
        "dominates_all_phases": bool(all(r["dominates"]
                                         for r in rows_out)),
    }
    if report_path is not None and ledger0 is not None:
        ledger0.to_csv(report_path)
        rep = ledger0.report()
        result["carbon_report"] = {
            "path": os.path.relpath(report_path, REPO),
            "daily_kwh": rep["daily_kwh"],
            "daily_gco2e": rep["daily_gco2e"],
            "daily_saved_kwh_vs_allmax": rep["daily_saved_kwh"],
            "daily_saved_tco2e_vs_allmax": rep["daily_saved_tco2e"],
        }
        print(f"[bench_carbon] wrote {os.path.abspath(report_path)}")
    if json_path is not None:
        from repro.obs.env import env_info
        result["env"] = env_info()
        path = os.path.abspath(json_path)
        with open(path, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        print(json.dumps(result, indent=2))
        print(f"[bench_carbon] wrote {path}")
    if check_dominance:
        assert result["dominates_all_phases"], result
    return result


def main() -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=os.path.join(REPO,
                                                   "BENCH_carbon.json"))
    ap.add_argument("--report", default=os.path.join(
        REPO, "results", "carbon_report.csv"))
    ap.add_argument("--windows", type=int, default=24)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--band-frac", type=float, default=0.5,
                    help="daily budget position in [floor, natural]")
    ap.add_argument("--full", action="store_true",
                    help="the non---small serve world")
    ap.add_argument("--no-check", action="store_true",
                    help="skip the dominance assertion")
    args = ap.parse_args()
    return run(windows=args.windows, requests=args.requests,
               band_frac=args.band_frac, small=not args.full,
               json_path=args.json, report_path=args.report,
               check_dominance=not args.no_check)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
