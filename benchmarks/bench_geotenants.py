"""Combined tenant x region benchmark: the ConstraintSpec headline.

    PYTHONPATH=src python benchmarks/bench_geotenants.py [--json PATH]

Protocol mirrors ``bench_geo.py`` (deterministic, decision-level): one
diurnal traffic day is sampled once - requests arrive in T equal tenant
blocks per window - and every arm sees the SAME requests, the same
reward-model predictions and the same pair of grid-intensity traces
(regions a/b share the diurnal CI shape ``--region-offset-h`` hours
apart), at several traffic-vs-grid phase offsets.  Each tenant t has
its own daily gCO2e budget g_t (distinct tightness).  Allocation uses
exact dual oracles (bisection), so the comparison measures the value
of COMPOSING the two constraint axes, not nearline lag.

Because every tenant's constraint only involves its own requests, the
day-level problem decouples per tenant; each arm solves T independent
problems and sums clicks:

  * ``tenants_only``  - per-tenant budgets, NO region choice: tenant
    t's requests are pinned to a single region (the better of the two
    for that tenant), exact scalar dual on its gram budget g_t.  Its
    REALIZED daily grams then anchor the equal-grams comparison.
  * ``regions_only``  - region choice WITHOUT cross-region gram
    flexibility: tenant t's equal-grams allowance is rigidly split in
    half per region (each region owns a fixed share) and a
    2-constraint exact dual (nested bisection) routes (chain, region)
    under both caps.
  * ``combined``      - the ConstraintSpec pipeline's problem: the
    same grams spend FREELY across both regions under one per-tenant
    budget, exact scalar dual over the J*R (chain, region) option
    space, primal rounded with the pipeline's green tie-break.

At the equal-grams anchor both baseline arms are restrictions of the
combined feasible set, so the exact dual can only gain clicks - the CI
gate asserts combined >= best(tenants_only, regions_only) for every
tested phase offset.

The benchmark also gates the PIPELINE against the oracle: a
``ServingPipeline.from_spec([TenantAxis(priced=True), RegionAxis(2,
split="argmax"), GlobalAxis(pricing="carbon")])`` day served with the
entry prices pinned to the oracle's per-tenant duals (region prices 0,
guard off - the oracle has no region caps) must reproduce the oracle's
decisions on every f32-DECIDED request (>= 99.5%; requests whose top-2
option gap only a float64 oracle can resolve - duplicate sampled users
with exactly tied rewards - legitimately tie-break by index in the f32
pipeline) and clicks (rel. error <= 1e-3) - the acceptance gate that
the fused combined pass prices exactly what the oracle prices.
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _exact_alloc(r_opt: np.ndarray, eff: np.ndarray, budget: float,
                 *, iters: int = 80):
    """Smallest-price exact-dual decisions fitting ``budget`` (cf.
    bench_geo._exact_alloc) - returns (decisions, lam)."""
    ridx = np.arange(r_opt.shape[0])

    def alloc(lam):
        return np.argmax(r_opt - lam * eff, axis=1)

    def spend(dec):
        return float(eff[ridx, dec].sum())

    if spend(alloc(0.0)) <= budget:
        return alloc(0.0), 0.0
    lo, hi = 0.0, 1.0
    while spend(alloc(hi)) > budget and hi < 1e30:
        hi *= 2.0
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        if spend(alloc(mid)) <= budget:
            hi = mid
        else:
            lo = mid
    return alloc(hi), hi


def _exact_alloc_2(r_opt: np.ndarray, eff_a: np.ndarray,
                   eff_b: np.ndarray, bud_a: float, bud_b: float,
                   *, iters: int = 40):
    """Exact dual for TWO per-region budgets over the (chain, region)
    option space, by nested bisection: the inner loop finds the
    smallest region-b price fitting bud_b at a given region-a price
    (region-b spend is non-increasing in its own price), the outer loop
    the smallest region-a price whose inner solution fits bud_a.
    Returns (option decisions, (lam_a, lam_b)); the result is always
    FEASIBLE (both caps respected), which is all the dominance gate
    needs from a baseline arm.
    """
    ridx = np.arange(r_opt.shape[0])
    j_n = eff_a.shape[1]

    def alloc(la, lb):
        return np.argmax(
            r_opt - np.concatenate([la * eff_a, lb * eff_b], axis=1),
            axis=1)

    def spends(dec):
        in_b = dec >= j_n
        ca = eff_a[ridx, np.minimum(dec, j_n - 1)]
        cb = eff_b[ridx, np.maximum(dec - j_n, 0)]
        return (float(np.sum(np.where(in_b, 0.0, ca))),
                float(np.sum(np.where(in_b, cb, 0.0))))

    def inner(la):
        if spends(alloc(la, 0.0))[1] <= bud_b:
            return 0.0
        lo, hi = 0.0, 1.0
        while spends(alloc(la, hi))[1] > bud_b and hi < 1e30:
            hi *= 2.0
        for _ in range(iters):
            mid = 0.5 * (lo + hi)
            if spends(alloc(la, mid))[1] <= bud_b:
                hi = mid
            else:
                lo = mid
        return hi

    def fits_a(la):
        return spends(alloc(la, inner(la)))[0] <= bud_a

    if fits_a(0.0):
        la = 0.0
    else:
        lo, hi = 0.0, 1.0
        while not fits_a(hi) and hi < 1e30:
            hi *= 2.0
        for _ in range(iters):
            mid = 0.5 * (lo + hi)
            if fits_a(mid):
                hi = mid
            else:
                lo = mid
        la = hi
    lb = inner(la)
    return alloc(la, lb), (la, lb)


def _green_alloc(r_sel: np.ndarray, s_sel: np.ndarray,
                 costs: np.ndarray, lam: float,
                 eps_rel: float = 1e-6) -> np.ndarray:
    """Factored exact-dual decisions with the pipeline's green
    tie-break: region = argmin_r (lam + eps) * s_r (ties - and the
    whole lam = 0 slack case - resolve to the GREENER region, exactly
    the fused pass's eps_green floor), then chain = the Eq. 10 argmax
    at the chosen region's price.  Mathematically the same allocation
    as the joint argmax over the J*R option space (the per-flop price
    factors out of the chain choice); only the degenerate tie is
    pinned down.  s_sel: (N, R) per-request per-region gram scales.
    """
    j_n = len(costs)
    n = len(r_sel)
    eps = eps_rel * float(np.abs(r_sel).max()) \
        / max(float(np.mean(s_sel) * np.mean(costs)), 1e-30)
    r0 = np.argmin((lam + eps) * s_sel, axis=1)
    price = (lam * s_sel[np.arange(n), r0])[:, None] * costs[None, :]
    dec = np.argmax(r_sel - price, axis=1)
    return r0 * j_n + dec


def run(*, windows: int = 24, requests: int = 48, n_tenants: int = 3,
        band_fracs=(0.35, 0.55, 0.75), ci_mean: float = 450.0,
        ci_amplitude: float = 0.45, region_offset_h: float = 8.0,
        phases=(0.0, 6.0, 12.0, 18.0), small: bool = True,
        json_path: str | None = None, check_dominance: bool = True,
        check_pipeline: bool = True) -> dict:
    from repro.carbon.controller import grams_per_flop
    from repro.carbon.intensity import two_region_traces
    from repro.carbon.ledger import DAY_S
    from repro.experiments import (build_serving_stack, predicted_rewards,
                                   serve_config)
    from repro.serving.stream import TrafficScenario, scenario_windows

    assert len(band_fracs) == n_tenants
    exp, server, params, rcfg = build_serving_stack(
        serve_config(small=small), verbose=True)
    chains = exp.chains
    costs = chains.costs
    j_n = len(costs)
    sizes = scenario_windows(TrafficScenario(
        "geotenants", windows, requests, n_tenants=n_tenants))
    window_s = DAY_S / windows
    traces = two_region_traces(mean=ci_mean, offset_h=region_offset_h,
                               rel_amplitude=ci_amplitude)
    region_names = list(traces)
    kpf = grams_per_flop(1.0)  # g per FLOP per unit CI

    # one shared day of traffic: T contiguous equal tenant blocks per
    # window (the pipeline's block layout), same arrivals for every arm
    pred = predicted_rewards(exp, params, rcfg, exp.ctx_eval)  # (U, J)
    rng = np.random.default_rng(0)
    rows = np.concatenate([rng.integers(0, pred.shape[0], n)
                           for n in sizes])
    w_of = np.repeat(np.arange(windows), sizes)
    t_of = np.concatenate([np.repeat(np.arange(n_tenants), n // n_tenants)
                           for n in sizes])
    n_req = len(rows)
    R = pred[rows]
    r_geo = np.tile(R, (1, 2))  # option m = r*J + j, region-major
    true_rev = exp.revenue_eval[rows]

    def clicks_of(sel, dec_m):
        return float(true_rev[sel][np.arange(sel.sum()),
                                   dec_m % j_n].sum())

    rows_out = []
    pipe_check = None
    for phase_h in phases:
        ci_w = {r: traces[r].resample(windows, window_s,
                                      phase_s=phase_h * 3600.0)
                for r in region_names}
        s_req = {r: (kpf * ci_w[r])[w_of] for r in region_names}
        eff = {r: s_req[r][:, None] * costs[None, :]
               for r in region_names}  # (N, J) per region
        eff_geo = np.concatenate([eff[r] for r in region_names], axis=1)
        ra = region_names[0]

        arms = {"tenants_only": 0.0, "regions_only": 0.0,
                "combined": 0.0}
        tenant_rows = []
        lam_star = np.zeros(n_tenants)
        for t in range(n_tenants):
            sel = t_of == t
            # daily gram band for tenant t, anchored at region a
            # exactly like bench_geo ([floor_a, natural_a]); the pinned
            # tenants-only arm binds against this budget, and its
            # REALIZED grams then anchor the equal-grams comparison -
            # every arm below spends (at most) the grams the best
            # pinned arm actually spent, so both baselines are
            # restrictions of the combined feasible set at EQUAL grams
            # and the exact dual can only gain clicks.
            floor_g = float(costs.min() * s_req[ra][sel].sum())
            natural_g = float(
                eff[ra][sel][np.arange(sel.sum()),
                             np.argmax(R[sel], axis=1)].sum())
            g_t = floor_g + band_fracs[t] * (natural_g - floor_g)

            # tenants-only: pinned single region, best of the two (a
            # pinned arm whose floor exceeds g_t serves all-cheapest
            # and overspends; the JSON records feasibility)
            pinned = {}
            for r in region_names:
                dec, _ = _exact_alloc(R[sel], eff[r][sel], g_t)
                spend_r = float(eff[r][sel][np.arange(sel.sum()),
                                            dec].sum())
                pinned[r] = (clicks_of(sel, dec), spend_r,
                             spend_r <= g_t * (1 + 1e-6))
            best_r = max(region_names, key=lambda r: pinned[r][0])
            c_ten, grams_eq, _ = pinned[best_r]

            # regions-only: the same grams under rigid halves - each
            # region owns grams_eq/2 of tenant t's spend, a
            # 2-constraint nested-bisection dual over the (chain,
            # region) options (region choice without cross-region gram
            # flexibility)
            dec2, _ = _exact_alloc_2(
                r_geo[sel], eff[ra][sel], eff[region_names[1]][sel],
                grams_eq / 2, grams_eq / 2)
            c_reg = clicks_of(sel, dec2)
            grams_reg = float(eff_geo[sel][np.arange(sel.sum()),
                                           dec2].sum())

            # combined: grams_eq spends freely across both regions.
            # The bisection finds the dual; the primal is rounded with
            # the green tie-break (same chains and price, greener
            # region on ties, so the spend can only drop)
            _, lam_c = _exact_alloc(r_geo[sel], eff_geo[sel], grams_eq)
            lam_star[t] = lam_c
            s_sel = np.stack([s_req[r][sel] for r in region_names],
                             axis=1)
            dec_c = _green_alloc(R[sel], s_sel, costs, lam_c)
            c_com = clicks_of(sel, dec_c)
            grams_c = float(eff_geo[sel][np.arange(sel.sum()),
                                         dec_c].sum())
            assert grams_c <= grams_eq * (1 + 1e-9) or lam_c == 0.0

            arms["tenants_only"] += c_ten
            arms["regions_only"] += c_reg
            arms["combined"] += c_com
            tenant_rows.append({
                "tenant": t, "grams_budget": g_t,
                "grams_equal": grams_eq,
                "lam_star": lam_c,
                "tenants_only_clicks": c_ten,
                "tenants_only_region": best_r,
                "tenants_only_feasible": bool(pinned[best_r][2]),
                "regions_only_clicks": c_reg,
                "regions_only_gco2e": grams_reg,
                "combined_clicks": c_com,
                "combined_gco2e": grams_c,
                "combined_gco2e_saved_pct": round(
                    100 * (1 - grams_c / max(grams_eq, 1e-30)), 2),
                "combined_split": [
                    float(np.mean(dec_c // j_n == k))
                    for k in range(len(region_names))],
            })

        best_base = max(arms["tenants_only"], arms["regions_only"])
        grams_eq_total = sum(tr["grams_equal"] for tr in tenant_rows)
        grams_c_total = sum(tr["combined_gco2e"] for tr in tenant_rows)
        row = {
            "ci_phase_h": phase_h,
            "clicks": arms,
            "tenants": tenant_rows,
            "combined_vs_best_pct": round(
                100 * (arms["combined"] / best_base - 1), 2),
            "combined_vs_tenants_pct": round(
                100 * (arms["combined"] / arms["tenants_only"] - 1), 2),
            "gco2e_saved_pct": round(
                100 * (1 - grams_c_total / grams_eq_total), 2),
            "dominates": bool(arms["combined"] >= arms["tenants_only"]
                              and arms["combined"]
                              >= arms["regions_only"]
                              and grams_c_total
                              <= grams_eq_total * (1 + 1e-9)),
        }
        rows_out.append(row)
        print(f"[bench_geotenants] phase {phase_h:>4.1f}h: tenants-only "
              f"{arms['tenants_only']:.0f} | regions-only "
              f"{arms['regions_only']:.0f} | combined "
              f"{arms['combined']:.0f} clicks "
              f"({row['combined_vs_tenants_pct']:+.2f}% vs "
              f"tenants-only, {row['combined_vs_best_pct']:+.2f}% vs "
              f"best baseline, {row['gco2e_saved_pct']:+.2f}% g saved "
              f"at equal-or-better clicks)")

        # pipeline-vs-oracle gate, once (phase 0 geometry): the fused
        # combined pass at the oracle's per-tenant entry prices must
        # reproduce the oracle's decisions
        if check_pipeline and pipe_check is None:
            s_all = np.stack([s_req[r] for r in region_names], axis=1)
            pipe_check = _pipeline_matches_oracle(
                server, params, rcfg, exp, sizes, rows, n_tenants,
                lam_star, ci_w, kpf, region_names, R, s_all, costs,
                clicks_of, j_n, t_of)
            print(f"[bench_geotenants] pipeline vs oracle: "
                  f"{pipe_check['decision_match_rate']:.4f} decisions, "
                  f"clicks rel err "
                  f"{pipe_check['clicks_rel_err']:.2e}")

    result = {
        "config": {"windows": windows, "requests": requests,
                   "n_tenants": n_tenants,
                   "band_fracs": list(band_fracs), "ci_mean": ci_mean,
                   "ci_amplitude": ci_amplitude,
                   "region_offset_h": region_offset_h, "small": small,
                   "chains": chains.n_chains, "window_s": window_s,
                   "n_requests_day": int(n_req),
                   "regions": region_names,
                   "traffic": "diurnal day curve, T equal tenant "
                              "blocks per window",
                   "arms": {
                       "tenants_only": "per-tenant budgets, pinned "
                                       "best single region (realized "
                                       "grams anchor the equal-grams "
                                       "comparison)",
                       "regions_only": "geo routing under rigid "
                                       "half-per-region splits of the "
                                       "equal grams (2-constraint "
                                       "nested-bisection dual)",
                       "combined": "the same grams freely across both "
                                   "regions under one per-tenant "
                                   "budget over the J*R option space "
                                   "(the ConstraintSpec pipeline's "
                                   "problem)"},
                   "allocator": "exact dual oracles (bisection), "
                                "decisions on reward-model "
                                "predictions"},
        "phases": rows_out,
        "pipeline_check": pipe_check,
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
        print(f"[bench_geotenants] wrote {path}")
    if check_dominance:
        assert result["dominates_all_phases"], result
        if pipe_check is not None:
            assert pipe_check["decision_match_rate"] >= 0.995, pipe_check
            assert abs(pipe_check["clicks_rel_err"]) <= 1e-3, pipe_check
    return result


def _pipeline_matches_oracle(server, params, rcfg, exp, sizes, rows,
                             n_tenants, lam_star, ci_w, kpf,
                             region_names, R, s_all, costs, clicks_of,
                             j_n, t_of):
    """Serve the oracle's day through the ConstraintSpec pipeline with
    entry prices pinned to the oracle's per-tenant duals (region prices
    0 - the oracle has no region caps - and guard off): decisions must
    match the oracle's."""
    from repro.serving.pipeline import ServingPipeline
    from repro.serving.spec import (ConstraintSpec, GlobalAxis,
                                    RegionAxis, TenantAxis)

    r_n = len(region_names)
    # budgets in the spec are per-window references; the check pins
    # prices, so only the shapes matter
    spec = ConstraintSpec([
        TenantAxis(tuple(1.0 for _ in range(n_tenants)), priced=True),
        RegionAxis(r_n, split="argmax"),
        GlobalAxis(pricing="carbon"),
    ])
    pipe = ServingPipeline.from_spec(server, params, rcfg, spec,
                                     guard=False)
    lam_pin = np.concatenate([lam_star,
                              np.zeros(r_n)]).astype(np.float32)
    big = np.full(n_tenants + r_n, 1e30, np.float32)
    scale_w = np.stack([kpf * ci_w[r] for r in region_names], axis=1)

    # oracle decisions for the same pinned prices (per-tenant scalar
    # price, f64, green tie-break - the pipeline's semantics).  The
    # match is gated on DECIDED requests: those whose top-2 option gap
    # is resolvable in float32 (duplicate sampled users carry exactly
    # tied rewards that only the f64 oracle can split by their ~1e-14
    # price differences - the f32 pipeline legitimately tie-breaks by
    # index there).
    dec_oracle = np.empty(len(rows), np.int64)
    decided = np.empty(len(rows), bool)
    for t in range(n_tenants):
        sel = t_of == t
        lam_t = float(lam_star[t])
        dec_oracle[sel] = _green_alloc(R[sel], s_all[sel], costs,
                                       lam_t)
        # decidedness follows the factored structure: the REGION
        # preference gap and the CHAIN top-2 gap at the chosen
        # region's price must each clear f32 resolution (the same
        # chain in the other region is always a near-tie option, and
        # chains sharing a model prefix can carry exactly equal
        # rewards - both tie-break by construction, not by pricing)
        s_sel = s_all[sel]
        n_t = int(sel.sum())
        eps = 1e-6 * float(np.abs(R[sel]).max()) \
            / max(float(np.mean(s_sel) * np.mean(costs)), 1e-30)
        u = (lam_t + eps) * s_sel  # (N_t, R)
        gap_r = np.abs(u[:, 0] - u[:, 1]) \
            / np.maximum(u.max(axis=1), 1e-30)
        r0 = np.argmin(u, axis=1)
        score = R[sel].astype(np.float64) \
            - (lam_t * s_sel[np.arange(n_t), r0])[:, None] \
            * costs[None, :]
        srt = np.sort(score, axis=1)
        gap_c = srt[:, -1] - srt[:, -2]
        decided[sel] = (gap_r > 1e-6) \
            & (gap_c > 1e-6 * float(np.abs(R[sel]).max()))

    match = np.zeros(len(rows), bool)
    clicks_pipe = 0.0
    off = 0
    for t, n in enumerate(sizes):
        r_w = rows[off:off + n]
        res = pipe.serve_window(exp.ctx_eval[r_w], r_w, lam=lam_pin,
                                update_lam=False, budget=big,
                                cost_scale=scale_w[t])
        dec_m = (res.regions_np * j_n + res.decisions_np)
        match[off:off + n] = dec_m == dec_oracle[off:off + n]
        clicks_pipe += float(res.revenue_np.sum())
        off += n
    clicks_oracle = clicks_of(np.ones(len(rows), bool), dec_oracle)
    return {
        "decision_match_rate": float(match[decided].mean()),
        "decision_match_rate_all": float(match.mean()),
        "decided_fraction": float(decided.mean()),
        "clicks_pipeline": clicks_pipe,
        "clicks_oracle": clicks_oracle,
        "clicks_rel_err": (clicks_pipe - clicks_oracle)
        / max(abs(clicks_oracle), 1e-30),
    }


def main() -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json",
                    default=os.path.join(REPO, "BENCH_geotenants.json"))
    ap.add_argument("--windows", type=int, default=24)
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--tenants", type=int, default=3)
    ap.add_argument("--band-fracs", default="0.35,0.55,0.75",
                    help="per-tenant daily gram budget positions in "
                         "[floor, natural]")
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
               n_tenants=args.tenants,
               band_fracs=tuple(float(x)
                                for x in args.band_fracs.split(",")),
               region_offset_h=args.region_offset_h,
               phases=tuple(float(x) for x in args.phases.split(",")),
               small=not args.full, json_path=args.json,
               check_dominance=not args.no_check)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
