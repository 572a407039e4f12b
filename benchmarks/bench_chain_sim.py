"""Chain-simulation benchmark: seed per-chain loop vs rank-based engine.

Measures ``simulate_revenue_matrix`` at the system-test scale (the ISSUE
acceptance config: U=160 users, I=200 items, J=128 chains) and records
the speedup over the SEED implementation (per-chain ``np.argpartition``
over the full score matrices, reproduced verbatim below for timing).

    PYTHONPATH=src python benchmarks/bench_chain_sim.py [--json PATH]

Writes BENCH_chain_sim.json at the repo root by default.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.cascade.engine import (simulate_revenue_matrix,
                                  simulate_revenue_matrix_reference)
from repro.core.action_chain import (ActionChainSet, ModelInstance,
                                     StageSpec, generate_action_chains)


# ---------------------------------------------------------------------------
# Seed implementation (pre rank-based rewrite), kept verbatim for timing
# ---------------------------------------------------------------------------


def _seed_run_chain(stage_scores, chain_desc, clicks, *, expose=20):
    n1, n2, n3, rank_name = chain_desc
    s1 = stage_scores["DSSM"]
    keep2 = np.argpartition(-s1, kth=min(n2, s1.shape[1] - 1),
                            axis=1)[:, :n2]
    s2 = np.take_along_axis(stage_scores["YDNN"], keep2, axis=1)
    k3 = min(n3, n2)
    idx3 = np.argpartition(-s2, kth=min(k3, s2.shape[1] - 1) - 1,
                           axis=1)[:, :k3]
    keep3 = np.take_along_axis(keep2, idx3, axis=1)
    s3 = np.take_along_axis(stage_scores[rank_name], keep3, axis=1)
    e = min(expose, k3)
    idx_e = np.argsort(-s3, axis=1)[:, :e]
    exposed = np.take_along_axis(keep3, idx_e, axis=1)
    return np.take_along_axis(clicks, exposed, axis=1).sum(axis=1)


def _seed_simulate(stage_scores, chains: ActionChainSet, clicks, *,
                   expose=20):
    u = clicks.shape[0]
    out = np.zeros((u, chains.n_chains), np.float32)
    k_rank = chains.n_stages - 1
    for j in range(chains.n_chains):
        n1 = int(chains.scale_value[j, 0])
        n2 = int(chains.scale_value[j, 1])
        n3 = int(chains.scale_value[j, 2])
        mi = int(chains.chain_idx[j, k_rank, 0])
        rank_name = chains.stages[k_rank].models[mi].name
        out[:, j] = _seed_run_chain(stage_scores, (n1, n2, n3, rank_name),
                                    clicks, expose=expose)
    return out


def _time(fn, *, repeats: int) -> float:
    fn()  # warmup (jit compile for the vectorized path)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(min(times))


def _time_interleaved(fns: list, *, repeats: int) -> list[float]:
    """min-of-N with the candidates ALTERNATED, so a load swing on a
    shared machine hits all of them instead of skewing the ratio."""
    for fn in fns:
        fn()  # warmup
    mins = [float("inf")] * len(fns)
    for _ in range(repeats):
        for k, fn in enumerate(fns):
            t0 = time.perf_counter()
            fn()
            mins[k] = min(mins[k], time.perf_counter() - t0)
    return mins


def run(*, users: int = 160, items: int = 200, expose: int = 8,
        repeats: int = 25, json_path: str | None = None,
        check_speedup: bool = False) -> dict:
    """Measure seed loop vs rank-based engine; optionally write JSON."""
    u, i, e = users, items, expose
    rng = np.random.default_rng(0)
    # float32: the dtype the real pipeline produces (jax model scores)
    scores = {k: rng.normal(size=(u, i)).astype(np.float32)
              for k in ("DSSM", "YDNN", "DIN", "DIEN")}
    clicks = (rng.random((u, i)) < 0.1).astype(np.float32)
    # 8 x 8 x 2 = 128 chains (J in the acceptance config)
    n2 = tuple(int(x) for x in np.linspace(0.2 * i, 0.5 * i, 8))
    n3 = tuple(int(x) for x in np.linspace(e, 0.2 * i, 8))
    chains = generate_action_chains((
        StageSpec("recall", (ModelInstance("DSSM", 13e3),), (i,), 4),
        StageSpec("prerank", (ModelInstance("YDNN", 123e3),), n2, 4),
        StageSpec("rank", (ModelInstance("DIN", 7020e3),
                           ModelInstance("DIEN", 7098e3)), n3, 4),
    ))

    t_seed, t_vec = _time_interleaved(
        [lambda: _seed_simulate(scores, chains, clicks, expose=e),
         lambda: simulate_revenue_matrix(scores, chains, clicks, expose=e)],
        repeats=repeats)
    t_ref = _time(lambda: simulate_revenue_matrix_reference(
        scores, chains, clicks, expose=e), repeats=max(2, repeats // 8))

    vec = simulate_revenue_matrix(scores, chains, clicks, expose=e)
    ref = simulate_revenue_matrix_reference(scores, chains, clicks, expose=e)
    seed = _seed_simulate(scores, chains, clicks, expose=e)
    exact_vs_ref = bool(np.array_equal(vec, ref))
    # seed used different (argpartition) tie handling; on the tie-free
    # random scores here the exposed sets coincide, so values match too
    exact_vs_seed = bool(np.array_equal(vec, seed.astype(np.float32)))

    result = {
        "config": {"users": u, "items": i, "chains": chains.n_chains,
                   "expose": e, "repeats": repeats},
        "seed_loop_s": round(t_seed, 5),
        "numpy_reference_s": round(t_ref, 5),
        "vectorized_s": round(t_vec, 5),
        "speedup_vs_seed": round(t_seed / t_vec, 2),
        "speedup_vs_reference": round(t_ref / t_vec, 2),
        "exact_match_vs_reference": exact_vs_ref,
        "exact_match_vs_seed": exact_vs_seed,
    }
    if json_path is not None:
        from repro.obs.env import env_info
        result["env"] = env_info()
        path = os.path.abspath(json_path)
        with open(path, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        print(json.dumps(result, indent=2))
        print(f"[bench_chain_sim] wrote {path}")
    # exactness is deterministic: always enforced.  The speedup gate is
    # wall-clock and flaky on shared runners, so it is opt-in.
    assert exact_vs_ref, "vectorized != reference"
    if check_speedup:
        assert result["speedup_vs_seed"] >= 5.0, result
    return result


def main() -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_chain_sim.json"))
    ap.add_argument("--users", type=int, default=160)
    ap.add_argument("--items", type=int, default=200)
    ap.add_argument("--expose", type=int, default=8)
    # min-of-N timing: N high enough to catch a quiet slice of a noisy
    # shared machine (each vectorized repeat is tens of ms)
    ap.add_argument("--repeats", type=int, default=25)
    ap.add_argument("--check-speedup", action="store_true",
                    help="assert the >=5x speedup (wall-clock: only "
                         "meaningful on an otherwise idle machine)")
    args = ap.parse_args()
    return run(users=args.users, items=args.items, expose=args.expose,
               repeats=args.repeats, json_path=args.json,
               check_speedup=args.check_speedup)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
