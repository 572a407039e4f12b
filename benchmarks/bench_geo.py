"""Geo-shifting benchmark: two-region serving vs pinned-region arms.

    PYTHONPATH=src python benchmarks/bench_geo.py [--json PATH]

Protocol mirrors ``bench_carbon.py`` (deterministic, decision-level):
one diurnal traffic day is sampled once; every arm sees the SAME
requests, the same reward-model predictions, and the same pair of
grid-intensity traces, at several traffic-vs-grid phase offsets.
Regions a/b share the diurnal CI shape ``--region-offset-h`` hours
apart (``two_region_traces`` - e.g. EU vs US-west).  Allocation uses
the exact dual oracle (bisection on one gram price), so the comparison
measures the *routing policy*, not nearline lag:

  * ``pinned_a`` / ``pinned_b`` - single-region serving: request i's
    effective chain costs are kappa * CI_r(t_i) * flops_j for its
    (fixed) region r.  Both arms face the same daily gCO2e budget.
  * ``geo``      - the geo-shifted router: each request chooses
    (chain, serving region) JOINTLY through the same priced argmax over
    the J*R option space with region-dependent effective costs
    c_{j,r}(t) = flops_j * kappa * CI_r(t) - computation flows to
    whichever region is greener at that hour.

Two frontier points are reported per phase:

  * ``equal_grams``    - geo given exactly the BEST pinned arm's
    realized daily gCO2e: clicks retained/gained.  Any pinned
    allocation is feasible for the geo option space at the same gram
    budget, so the exact dual can only gain clicks - the ISSUE
    acceptance gate asserts >= for every tested phase offset.
  * ``matched_clicks`` - the smallest gram budget whose clicks still
    match the best pinned arm: gCO2e saved at equal-or-better clicks.

The per-region-budget NEARLINE router (per-region dual prices + guard +
ledgers inside the fused pipeline) is the serving-system counterpart -
exercised by ``launch/serve.py --scenario georegions`` and the CI
smoke; this benchmark isolates the policy value of the region choice
itself.
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _exact_alloc(r_opt: np.ndarray, eff: np.ndarray, budget: float,
                 *, iters: int = 80) -> np.ndarray:
    """Option decisions at the smallest gram price fitting ``budget``.

    r_opt (N, M) per-option rewards; eff (N, M) per-request per-option
    effective gCO2e cost.  Spend is non-increasing in the price =>
    bisection is exact up to float resolution (cf. dual_bisect).
    """
    ridx = np.arange(r_opt.shape[0])

    def alloc(lam):
        return np.argmax(r_opt - lam * eff, axis=1)

    def spend(dec):
        return float(eff[ridx, dec].sum())

    if spend(alloc(0.0)) <= budget:
        return alloc(0.0)
    lo, hi = 0.0, 1.0
    while spend(alloc(hi)) > budget and hi < 1e30:
        hi *= 2.0
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        if spend(alloc(mid)) <= budget:
            hi = mid
        else:
            lo = mid
    return alloc(hi)


def run(*, windows: int = 24, requests: int = 48, band_frac: float = 0.5,
        ci_mean: float = 450.0, ci_amplitude: float = 0.45,
        region_offset_h: float = 8.0, phases=(0.0, 6.0, 12.0, 18.0),
        small: bool = True, json_path: str | None = None,
        check_dominance: bool = True) -> dict:
    from repro.carbon.controller import grams_per_flop
    from repro.carbon.intensity import two_region_traces
    from repro.carbon.ledger import DAY_S
    from repro.experiments import (build_serving_stack, predicted_rewards,
                                   serve_config)
    from repro.serving.stream import TrafficScenario, scenario_windows

    exp, server, params, rcfg = build_serving_stack(
        serve_config(small=small), verbose=True)
    chains = exp.chains
    costs = chains.costs
    j_n = len(costs)
    sizes = scenario_windows(
        TrafficScenario("georegions", windows, requests))
    window_s = DAY_S / windows
    traces = two_region_traces(mean=ci_mean, offset_h=region_offset_h,
                               rel_amplitude=ci_amplitude)
    kpf = grams_per_flop(1.0)  # g per FLOP per unit CI

    # one shared day of traffic: same arrivals for every arm/phase
    pred = predicted_rewards(exp, params, rcfg, exp.ctx_eval)  # (U, J)
    rng = np.random.default_rng(0)
    rows = np.concatenate([rng.integers(0, pred.shape[0], n)
                           for n in sizes])
    w_of = np.repeat(np.arange(windows), sizes)
    n_req = len(rows)
    ridx = np.arange(n_req)
    R = pred[rows]
    r_geo = np.tile(R, (1, 2))  # option m = r*J + j, region-major
    true_rev = exp.revenue_eval[rows]

    def clicks_of(dec_m):
        return float(true_rev[ridx, dec_m % j_n].sum())

    region_names = list(traces)
    rows_out = []
    for phase_h in phases:
        ci_w = {r: traces[r].resample(windows, window_s,
                                      phase_s=phase_h * 3600.0)
                for r in region_names}
        s_req = {r: (kpf * ci_w[r])[w_of] for r in region_names}
        eff = {r: s_req[r][:, None] * costs[None, :]
               for r in region_names}  # (N, J) per pinned arm
        eff_geo = np.concatenate([eff[r] for r in region_names], axis=1)

        # the allocation band, in grams of region a: below the gram
        # floor Eq. 3b is infeasible, above the natural spend the
        # constraint is slack and all arms coincide
        ra = region_names[0]
        floor_g = float(costs.min() * s_req[ra].sum())
        natural_g = float(
            eff[ra][ridx, np.argmax(R, axis=1)].sum())
        g_budget = floor_g + band_frac * (natural_g - floor_g)

        pinned = {}
        for r in region_names:
            dec = _exact_alloc(R, eff[r], g_budget)
            pinned[r] = {
                "clicks": clicks_of(dec),
                "gco2e": float(eff[r][ridx, dec].sum()),
                "flops": float(costs[dec].sum()),
            }
        best = max(region_names, key=lambda r: pinned[r]["clicks"])
        clicks_b, grams_b = pinned[best]["clicks"], pinned[best]["gco2e"]

        # frontier point 1: geo at exactly the best pinned arm's grams
        dec_eq = _exact_alloc(r_geo, eff_geo, grams_b)
        clicks_eq = clicks_of(dec_eq)
        split = [float(np.mean(dec_eq // j_n == k))
                 for k in range(len(region_names))]

        # frontier point 2: cheapest gram budget matching best pinned's
        # clicks.  Bracket: walk lo down until clicks drop below (or the
        # serve floor is reached) so the saving is never silently capped.
        g_floor_geo = float(
            (costs.min() * np.minimum.reduce(
                [s_req[r] for r in region_names])).sum())
        lo = 0.8 * grams_b
        while lo > g_floor_geo and clicks_of(
                _exact_alloc(r_geo, eff_geo, lo, iters=60)) >= clicks_b:
            lo = max(g_floor_geo, lo * 0.8)
        hi = grams_b
        for _ in range(20):
            mid = 0.5 * (lo + hi)
            if clicks_of(_exact_alloc(r_geo, eff_geo, mid,
                                      iters=60)) >= clicks_b:
                hi = mid
            else:
                lo = mid
        dec_m = _exact_alloc(r_geo, eff_geo, hi, iters=60)
        clicks_m = clicks_of(dec_m)
        grams_m = float(eff_geo[ridx, dec_m].sum())

        row = {
            "ci_phase_h": phase_h,
            "pinned": pinned,
            "best_pinned": best,
            "equal_grams": {
                "clicks": clicks_eq,
                "gco2e": float(eff_geo[ridx, dec_eq].sum()),
                "flops": float(costs[dec_eq % j_n].sum()),
                "region_split": dict(zip(region_names, split)),
                "clicks_delta_pct": round(
                    100 * (clicks_eq / clicks_b - 1), 2)},
            "matched_clicks": {
                "clicks": clicks_m, "gco2e": grams_m,
                "gco2e_saved_pct": round(100 * (1 - grams_m / grams_b),
                                         2)},
            "dominates": bool(clicks_eq >= clicks_b
                              and clicks_m >= clicks_b
                              and grams_m <= grams_b),
        }
        rows_out.append(row)
        print(f"[bench_geo] phase {phase_h:>4.1f}h: best pinned "
              f"({best}) {clicks_b:.0f} clicks @ {grams_b:.3e} g | geo "
              f"equal-grams {row['equal_grams']['clicks_delta_pct']:+.2f}%"
              f" clicks (split {split}) | matched-clicks "
              f"{row['matched_clicks']['gco2e_saved_pct']:+.2f}% g saved")

    result = {
        "config": {"windows": windows, "requests": requests,
                   "band_frac": band_frac, "ci_mean": ci_mean,
                   "ci_amplitude": ci_amplitude,
                   "region_offset_h": region_offset_h, "small": small,
                   "chains": chains.n_chains, "window_s": window_s,
                   "n_requests_day": int(n_req),
                   "regions": region_names,
                   "traffic": "diurnal day curve (georegions scenario)",
                   "intensity": "two-region diurnal, offset peaks",
                   "allocator": "exact dual oracle (bisection) over the "
                                "J*R (chain, region) option space, "
                                "decisions on reward-model predictions"},
        "phases": rows_out,
        "dominates_all_phases": bool(all(r["dominates"]
                                         for r in rows_out)),
    }
    if json_path is not None:
        from repro.obs.env import env_info
        result["env"] = env_info()
        path = os.path.abspath(json_path)
        with open(path, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        print(json.dumps(result, indent=2))
        print(f"[bench_geo] wrote {path}")
    if check_dominance:
        assert result["dominates_all_phases"], result
    return result


def main() -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=os.path.join(REPO, "BENCH_geo.json"))
    ap.add_argument("--windows", type=int, default=24)
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--band-frac", type=float, default=0.5,
                    help="daily gram budget position in [floor, natural]")
    ap.add_argument("--region-offset-h", type=float, default=8.0,
                    help="hours region b's CI peak trails region a's")
    ap.add_argument("--phases", default="0,6,12,18",
                    help="traffic-vs-grid phase offsets (hours, csv)")
    ap.add_argument("--full", action="store_true",
                    help="the non---small serve world")
    ap.add_argument("--no-check", action="store_true",
                    help="skip the dominance assertion")
    args = ap.parse_args()
    return run(windows=args.windows, requests=args.requests,
               band_frac=args.band_frac,
               region_offset_h=args.region_offset_h,
               phases=tuple(float(x) for x in args.phases.split(",")),
               small=not args.full, json_path=args.json,
               check_dominance=not args.no_check)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
