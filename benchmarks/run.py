"""Benchmark harness: one function per paper table/figure + serving
micro-latency + roofline summary.  Prints ``name,us_per_call,derived``
CSV rows (plus per-table columns), per the repo skeleton contract.

    PYTHONPATH=src python -m benchmarks.run [--fast]
    PYTHONPATH=src python -m benchmarks.run --all   # every BENCH_*.json

``--all`` is the one-stop regeneration entrypoint: it reruns every
standalone benchmark (chain simulation, fused serving, carbon
allocation, geo-shifting) and rewrites the corresponding
``BENCH_*.json`` at the repo root, then exits.
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def _emit(rows: list[dict], wall_s: float):
    per = 1e6 * wall_s / max(1, len(rows))
    for r in rows:
        name = r.pop("name")
        derived = ";".join(f"{k}={v}" for k, v in r.items())
        print(f"{name},{per:.1f},{derived}")
    sys.stdout.flush()


def bench_serving_latency(exp, reward_params, reward_cfg) -> list[dict]:
    """us_per_call of the online/nearline hot paths on THIS host (CPU;
    TPU latency derives from the roofline table instead)."""
    import jax
    import jax.numpy as jnp
    from repro.core.primal_dual import allocate, dual_descent
    from repro.core.reward_model import reward_matrix

    chains = exp.chains
    ctx = jnp.asarray(exp.ctx_eval[:256])
    mo = jnp.asarray(chains.model_onehot)
    sh = jnp.asarray(chains.scale_multihot)
    costs = jnp.asarray(chains.costs, jnp.float32)

    score = jax.jit(lambda p, c: reward_matrix(p, reward_cfg, c, mo, sh))
    r = score(reward_params, ctx).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(20):
        r = score(reward_params, ctx).block_until_ready()
    score_us = (time.perf_counter() - t0) / 20 * 1e6

    dd = jax.jit(lambda rw: dual_descent(rw, costs, float(np.median(
        chains.costs)) * rw.shape[0], 0.0, max_iters=100))
    lam, _ = dd(r)
    lam.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(20):
        lam, _ = dd(r)
        lam.block_until_ready()
    dual_us = (time.perf_counter() - t0) / 20 * 1e6

    al = jax.jit(lambda rw, l: allocate(rw, costs, l))
    al(r, lam).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(50):
        d = al(r, lam).block_until_ready()
    alloc_us = (time.perf_counter() - t0) / 50 * 1e6

    return [
        {"name": "serve_reward_score_256req", "us": round(score_us, 1),
         "us_per_req": round(score_us / 256, 2)},
        {"name": "nearline_dual_100iter", "us": round(dual_us, 1)},
        {"name": "serve_allocate_256req", "us": round(alloc_us, 1),
         "us_per_req": round(alloc_us / 256, 3)},
    ]


def bench_chain_sim_row() -> list[dict]:
    """Rank-based chain simulator vs the seed per-chain loop (the same
    measurement as benchmarks/bench_chain_sim.py, summarized as one row;
    the standalone script also writes BENCH_chain_sim.json)."""
    from benchmarks import bench_chain_sim

    r = bench_chain_sim.run(repeats=3)
    return [{"name": "chain_sim_U160_I200_J128",
             "us": round(r["vectorized_s"] * 1e6, 1),
             "speedup_vs_seed": r["speedup_vs_seed"],
             "exact": r["exact_match_vs_reference"]}]


def bench_kernels() -> list[dict]:
    """Interpret-mode wall time is NOT TPU perf; reported for harness
    completeness with the jnp-reference ratio as `derived`."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    rows = []
    key = jax.random.PRNGKey(0)
    feats = jax.random.normal(key, (256, 27, 64))
    for name, fn, rfn in (
        ("dot_interact_256x27x64",
         lambda: ops.dot_interact(feats, block_b=64),
         lambda: ref.dot_interact_ref(feats)),
    ):
        fn().block_until_ready()  # compile
        t0 = time.perf_counter()
        for _ in range(5):
            fn().block_until_ready()
        k_us = (time.perf_counter() - t0) / 5 * 1e6
        rref = jax.jit(rfn)
        rref().block_until_ready()  # compile
        t0 = time.perf_counter()
        for _ in range(5):
            rref().block_until_ready()
        r_us = (time.perf_counter() - t0) / 5 * 1e6
        rows.append({"name": f"kernel_{name}", "us": round(k_us, 1),
                     "interpret_vs_jnp": round(k_us / max(r_us, 1e-9), 2)})
    return rows


def run_all_json(fast: bool = False) -> dict:
    """Regenerate every BENCH_*.json from one entrypoint; returns
    {bench name: json path}.  ``fast`` shrinks each bench to a
    CI-smoke size (minutes -> tens of seconds; numbers are NOT
    comparable to the full-size records)."""
    import os

    from benchmarks import (bench_carbon, bench_chain_sim, bench_geo,
                            bench_geotenants, bench_multihost,
                            bench_scale, bench_serve, bench_truncate)

    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    out = {}
    print("[run --all] chain simulation ...")
    bench_chain_sim.run(json_path=os.path.join(repo,
                                               "BENCH_chain_sim.json"),
                        **({"repeats": 3} if fast else {}))
    out["chain_sim"] = "BENCH_chain_sim.json"
    print("[run --all] fused serving vs legacy loop ...")
    bench_serve.run(json_path=os.path.join(repo, "BENCH_serve.json"),
                    **({"windows": 10, "requests": 48} if fast else {}))
    out["serve"] = "BENCH_serve.json"
    print("[run --all] carbon-aware vs constant-CI allocation ...")
    bench_carbon.run(json_path=os.path.join(repo, "BENCH_carbon.json"),
                     report_path=os.path.join(repo, "results",
                                              "carbon_report.csv"),
                     **({"windows": 12, "requests": 24,
                         "phases": (0.0, 12.0)} if fast else {}))
    out["carbon"] = "BENCH_carbon.json"
    print("[run --all] geo-shifted vs pinned-region serving ...")
    bench_geo.run(json_path=os.path.join(repo, "BENCH_geo.json"),
                  **({"windows": 12, "requests": 24,
                      "phases": (0.0, 12.0)} if fast else {}))
    out["geo"] = "BENCH_geo.json"
    print("[run --all] combined tenant x region vs single-axis arms ...")
    bench_geotenants.run(
        json_path=os.path.join(repo, "BENCH_geotenants.json"),
        **({"windows": 12, "requests": 24, "n_tenants": 2,
            "band_fracs": (0.35, 0.65),
            "phases": (0.0, 12.0)} if fast else {}))
    out["geotenants"] = "BENCH_geotenants.json"
    print("[run --all] streamed request world at scale ...")
    bench_scale.run(json_path=os.path.join(repo, "BENCH_scale.json"),
                    small=fast)
    out["scale"] = "BENCH_scale.json"
    print("[run --all] multi-host request mesh sweep ...")
    bench_multihost.run(
        json_path=os.path.join(repo, "BENCH_multihost.json"),
        **({"procs": (1, 2), "sizes": [64, 96, 64]} if fast else {}))
    out["multihost"] = "BENCH_multihost.json"
    print("[run --all] cascade-truncation kernel vs XLA baseline ...")
    bench_truncate.run(
        json_path=os.path.join(repo, "BENCH_truncate.json"),
        **({"batches": (256, 1024), "u_count": 128, "parity_batch": 64,
            "smoke_batch": 32, "reps": 3} if fast else {}))
    out["truncate"] = "BENCH_truncate.json"
    for name, path in out.items():
        print(f"[run --all] {name:10s} -> {path}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller world (CI-sized)")
    ap.add_argument("--skip-tables", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="regenerate every BENCH_*.json and exit "
                         "(--fast shrinks each bench to smoke size; "
                         "--skip-tables is implied)")
    args = ap.parse_args()

    if args.all:
        run_all_json(fast=args.fast)
        return

    from benchmarks import roofline, tables
    from repro.data.synthetic import WorldConfig
    from repro.experiments import ExperimentConfig, train_reward_model

    print("name,us_per_call,derived")

    cfg = tables.BENCH_CFG
    if args.fast:
        cfg = ExperimentConfig(
            world=WorldConfig(n_users=800, n_items=200, hist_len=10, seed=7),
            expose=8, n_scales=4, cascade_steps=100, reward_steps=200,
            batch=48)

    t0 = time.time()
    exp = tables.get_experiment(cfg)
    print(f"setup_experiment,{(time.time()-t0)*1e6:.0f},"
          f"users={cfg.world.n_users};items={cfg.world.n_items};"
          f"chains={exp.chains.n_chains}")

    t0 = time.time()
    rp, rc = train_reward_model(exp)
    print(f"train_reward_model,{(time.time()-t0)*1e6:.0f},"
          f"steps={cfg.reward_steps}")

    if not args.skip_tables:
        for fn, needs_reward in (
            (tables.fig4_budget_curves, True),
            (tables.table2_stage_ablation, True),
            (tables.table3_model_ablation, True),
            (tables.table4_reward_ablation, False),
            (tables.fig5_traffic_spikes, True),
            (tables.pfec_summary, True),
        ):
            t0 = time.time()
            rows = fn(exp, rp, rc) if needs_reward else fn(exp)
            _emit(rows, time.time() - t0)

    _emit(bench_serving_latency(exp, rp, rc), 0.0)
    _emit(bench_chain_sim_row(), 0.0)
    _emit(bench_kernels(), 0.0)

    # roofline summary (requires a completed dry-run; silent if absent)
    try:
        rows = roofline.full_table()
        ok = [r for r in rows if "error" not in r and "skipped" not in r]
        if ok:
            for r in ok:
                print(f"roofline_{r['arch']}_{r['shape']}_{r['mesh']},0,"
                      f"dominant={r['dominant']};"
                      f"t_comp_ms={r['t_compute_s']*1e3:.3f};"
                      f"t_mem_ms={r['t_memory_s']*1e3:.3f};"
                      f"t_coll_ms={r['t_collective_s']*1e3:.3f};"
                      f"frac={r['roofline_frac']:.4f}")
    except Exception as e:  # noqa: BLE001
        print(f"roofline_summary,0,unavailable={type(e).__name__}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
