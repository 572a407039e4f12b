"""Cascade-truncation kernel benchmark at production batch sizes.

    PYTHONPATH=src python benchmarks/bench_truncate.py [--fast]

Times the survivor-compaction truncation round - the per-request
``mask -> cumsum -> expose-cut -> revenue`` of CompactPlan execution -
on two implementations over the same (G, U, cap) tables:

  * the XLA baseline ``cascade.engine._revenue_compact`` (vectorized
    gather + ``jnp.cumsum``; what the fused pipeline runs today and
    the fallback wherever Pallas is unavailable);
  * the Pallas kernel ``kernels.cascade_truncate.compact_truncate_revenue``
    (scalar-prefetched row gather + triangular-matmul cumsum, one grid
    step per request).

Parity between the two is asserted before any timing - to float32
reduction tolerance, since the kernel sums revenue over the padded
lane width in a different association order than the baseline's
masked row sum (the survivor COUNTS are exact; only the final click
sum reassociates).  The kernel timing is HARDWARE-GATED exactly like the kernel
itself: on TPU/GPU the compiled kernel runs at every production batch
size; on CPU only the interpreter exists, which executes grid steps in
Python and would take minutes at B=16384 - so CPU runs time the
interpreter at a small smoke batch (recorded as ``interpret_smoke``)
and the XLA baseline at the full production sweep.

Writes BENCH_truncate.json at the repo root.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _time(fn, *args, reps: int = 10, **kw) -> float:
    import jax

    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def run(*, batches: tuple[int, ...] = (1024, 4096, 16384),
        g_count: int = 16, u_count: int = 512, cap: int = 150,
        expose: int = 8, parity_batch: int = 256,
        smoke_batch: int = 64, reps: int = 10,
        json_path: str | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.cascade.engine import _revenue_compact
    from repro.kernels.cascade_truncate import compact_truncate_revenue
    from repro.obs.env import env_info

    backend = jax.default_backend()
    kernel_armed = backend in ("tpu", "gpu")

    rng = np.random.default_rng(0)
    p = np.stack([np.stack([rng.permutation(cap) for _ in range(u_count)])
                  for _ in range(g_count)]).astype(np.int32)
    ck = rng.random((g_count, u_count, cap)).astype(np.float32)
    p_d, ck_d = jnp.asarray(p), jnp.asarray(ck)

    def sample(b):
        return (jnp.asarray(rng.integers(0, g_count, b), jnp.int32),
                jnp.asarray(rng.integers(0, u_count, b), jnp.int32),
                jnp.asarray(rng.integers(1, cap + 1, b), jnp.int32))

    # parity first: the kernel must match the XLA baseline (to f32
    # reduction tolerance - the padded-lane sum reassociates) before
    # any timing
    g_b, r_b, n3_b = sample(parity_batch)
    base = np.asarray(_revenue_compact(p_d, ck_d, g_b, r_b, n3_b,
                                       expose=expose))
    kern = np.asarray(compact_truncate_revenue(
        p_d, ck_d, g_b, r_b, n3_b, expose=expose,
        interpret=not kernel_armed))
    np.testing.assert_allclose(kern, base, rtol=1e-6, atol=1e-6)
    parity_max_rel = float(np.max(np.abs(kern - base)
                                  / np.maximum(np.abs(base), 1e-9)))

    sweep = []
    for b in batches:
        g_b, r_b, n3_b = sample(b)
        t_base = _time(_revenue_compact, p_d, ck_d, g_b, r_b, n3_b,
                       expose=expose, reps=reps)
        row = {
            "batch": b,
            "baseline_us": 1e6 * t_base,
            "baseline_req_per_s": b / t_base,
        }
        if kernel_armed:
            t_k = _time(compact_truncate_revenue, p_d, ck_d, g_b, r_b,
                        n3_b, expose=expose, interpret=False, reps=reps)
            row["kernel_us"] = 1e6 * t_k
            row["kernel_req_per_s"] = b / t_k
            row["speedup"] = t_base / t_k
        sweep.append(row)
        extra = (f", kernel {row['kernel_us']:.0f}us "
                 f"({row['speedup']:.2f}x)" if kernel_armed else "")
        print(f"[bench_truncate] B={b}: baseline "
              f"{row['baseline_us']:.0f}us{extra}", flush=True)

    interp = None
    if not kernel_armed:
        g_b, r_b, n3_b = sample(smoke_batch)
        t_i = _time(compact_truncate_revenue, p_d, ck_d, g_b, r_b, n3_b,
                    expose=expose, interpret=True, reps=max(1, reps // 5))
        interp = {"batch": smoke_batch, "interpret_us": 1e6 * t_i}
        print(f"[bench_truncate] interpret smoke B={smoke_batch}: "
              f"{interp['interpret_us']:.0f}us", flush=True)

    out = {
        "benchmark": "cascade_truncate",
        "tables": {"groups": g_count, "users": u_count, "cap": cap,
                   "expose": expose},
        "backend": backend,
        "kernel_armed": kernel_armed,
        "parity": {"batch": parity_batch, "rtol": 1e-6,
                   "max_rel_err": parity_max_rel,
                   "mode": "compiled" if kernel_armed else "interpret"},
        "sweep": sweep,
        "interpret_smoke": interp,
        "env": env_info(),
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(out, f, indent=2)
        print(f"[bench_truncate] wrote {json_path}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json",
                    default=os.path.join(REPO, "BENCH_truncate.json"))
    ap.add_argument("--fast", action="store_true",
                    help="small batches / few reps (smoke)")
    args = ap.parse_args(argv)
    if args.fast:
        run(batches=(256, 1024), u_count=128, parity_batch=64,
            smoke_batch=32, reps=3, json_path=args.json)
    else:
        run(json_path=args.json)


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
