"""Serving benchmark: the legacy host loop vs the fused ServingPipeline.

    PYTHONPATH=src python benchmarks/bench_serve.py [--json PATH]

Measures, at the ``launch/serve.py --small`` config on this host:

  * legacy window latency - the seed's serving path, four host/device
    crossings per window: jitted reward scoring -> NumPy controller
    (Eq. 10 decide + multi-pass guard + synchronous dual descent) ->
    jitted cascade execution, host-blocking after each;
  * fused response latency - the ServingPipeline's online pass (grouped
    scoring -> Eq. 10 -> vectorized guard -> CompactPlan execution) in
    one dispatch, measured submit -> decisions/revenue/spend ready; the
    nearline dual update is dispatched separately and chains on-device,
    exactly as the paper's online/nearline split prescribes - it never
    sits on the response path;
  * sustained throughput for both - windows/sec over a streamed run
    INCLUDING each path's dual update, so the nearline work is fully
    accounted for where it belongs.

Legacy/fused windows are interleaved so load swings on a shared machine
hit both paths instead of skewing the ratio.  Decision parity (pinned
lambda) is asserted always; the >= 2x latency gate is wall-clock and
therefore opt-in (--check-speedup), mirroring bench_chain_sim.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def run(*, windows: int = 40, requests: int = 96, budget_frac: float = 0.6,
        small: bool = True, json_path: str | None = None,
        check_speedup: bool = False) -> dict:
    import jax

    from repro.experiments import build_serving_stack, serve_config
    from repro.launch.serve import make_legacy_window
    from repro.serving.pipeline import ServingPipeline

    exp, server, params, rcfg = build_serving_stack(
        serve_config(small=small), verbose=True)
    chains = exp.chains
    budget = budget_frac * float(chains.costs.max()) * requests
    rng = np.random.default_rng(0)
    n_eval = exp.ctx_eval.shape[0]

    def sample():
        rows = rng.integers(0, n_eval, requests)
        return exp.ctx_eval[rows].astype(np.float32), rows

    ctl, legacy_window = make_legacy_window(exp, server, params, rcfg,
                                            budget)
    pipe = ServingPipeline(server, params, rcfg, budget)

    def fused_window(ctx, rows):
        res = pipe.serve_window(ctx, rows)
        jax.block_until_ready((res.decisions, res.revenue, res.spend))
        return res

    # parity: pinned lambda, decisions + revenue must match exactly
    for _ in range(3):
        ctx, rows = sample()
        lam = float(ctl.pd.lam)
        dec_l, rev_l = legacy_window(ctx, rows)
        res = pipe.serve_window(ctx, rows, lam=lam)
        assert np.array_equal(dec_l, res.decisions_np), "decision parity"
        assert np.array_equal(rev_l, res.revenue_np), "revenue parity"

    # latency: interleaved, device queue drained before each measurement
    lat_legacy, lat_fused = [], []
    for _ in range(windows):
        ctx, rows = sample()
        t0 = time.perf_counter()
        legacy_window(ctx, rows)
        lat_legacy.append(time.perf_counter() - t0)
        jax.block_until_ready(pipe.lam)  # drain the nearline chain
        t0 = time.perf_counter()
        fused_window(ctx, rows)
        lat_fused.append(time.perf_counter() - t0)

    # sustained throughput incl. each path's dual update
    ctx, rows = sample()
    t0 = time.perf_counter()
    for _ in range(windows):
        legacy_window(ctx, rows)
    thr_legacy = windows / (time.perf_counter() - t0)
    t0 = time.perf_counter()
    for _ in range(windows):
        pipe.serve_window(ctx, rows)
    jax.block_until_ready(pipe.lam)
    thr_fused = windows / (time.perf_counter() - t0)

    med_l = float(np.median(lat_legacy) * 1e3)
    med_f = float(np.median(lat_fused) * 1e3)
    result = {
        "config": {"windows": windows, "requests": requests,
                   "budget_frac": budget_frac, "small": small,
                   "chains": chains.n_chains,
                   "eval_users": int(n_eval),
                   "dual_iters": pipe.dual_cfg.max_iters},
        "legacy": {
            "median_window_ms": round(med_l, 3),
            "p95_window_ms": round(
                float(np.percentile(lat_legacy, 95) * 1e3), 3),
            "windows_per_sec": round(thr_legacy, 2),
        },
        "fused": {
            "median_window_ms": round(med_f, 3),
            "p95_window_ms": round(
                float(np.percentile(lat_fused, 95) * 1e3), 3),
            "windows_per_sec": round(thr_fused, 2),
        },
        "speedup_median_latency": round(med_l / med_f, 2),
        "speedup_throughput": round(thr_fused / thr_legacy, 2),
        "decision_parity": True,  # asserted above
    }
    if json_path is not None:
        from repro.obs.env import env_info
        result["env"] = env_info()
        path = os.path.abspath(json_path)
        with open(path, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        print(json.dumps(result, indent=2))
        print(f"[bench_serve] wrote {path}")
    if check_speedup:
        assert result["speedup_median_latency"] >= 2.0, result
    return result


def main() -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_serve.json"))
    ap.add_argument("--windows", type=int, default=40)
    ap.add_argument("--requests", type=int, default=96)
    ap.add_argument("--budget-frac", type=float, default=0.6)
    ap.add_argument("--full", action="store_true",
                    help="the non---small serve world")
    ap.add_argument("--check-speedup", action="store_true",
                    help="assert the >=2x median latency gate "
                         "(wall-clock: meaningful on an idle machine)")
    args = ap.parse_args()
    return run(windows=args.windows, requests=args.requests,
               budget_frac=args.budget_frac, small=not args.full,
               json_path=args.json, check_speedup=args.check_speedup)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
