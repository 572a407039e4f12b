"""Multi-host request-mesh benchmark: throughput scaling + parity.

    PYTHONPATH=src python benchmarks/bench_multihost.py [--fast]

Sweeps the SAME stream over jax.distributed process groups of 1 / 2 /
4 / 8 local processes (each with ``8 / P`` fake host devices, so the
global shard count - and therefore every padded shape and every
stitched collective - is identical at every P).  Per process group it
reports:

  * per-process and aggregate request throughput (req/s) with the
    stall / prep / submit / h2d breakdown from ``StreamStats``;
  * a BITWISE decision-parity gate: every P's stitched decisions, lam
    trace and per-window spends must equal the single-process
    reference exactly (the fixed-shard-count invariant that makes
    elastic re-sharding safe);
  * zero steady-state recompiles on every host;
  * one Perfetto trace per host, merged into a single
    ``multihost_trace.json`` whose track groups are the hosts
    (``Tracer(process_label=...)`` -> ``merge_chrome_traces``), plus
    per-host JSONL flight logs carrying the ``host`` label.

The near-linear scaling assertion is HARDWARE-GATED: P processes on
fewer than P cores time-slice one CPU, so speedup is meaningless
there.  On < 4 cores the sweep is report-only; at >= 4 cores the gate
arms and requires aggregate throughput at P=4 to reach at least half
of linear (efficiency >= 0.5) over P=1.

Writes BENCH_multihost.json at the repo root.
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

CHILD = textwrap.dedent("""
    import json, os, sys
    import numpy as np

    from repro.distributed import multihost as mh

    dist = mh.initialize()
    import jax
    import jax.numpy as jnp

    from repro.cascade.engine import CascadeServer
    from repro.core.action_chain import (ModelInstance, StageSpec,
                                         generate_action_chains)
    from repro.core.reward_model import RewardModelConfig, reward_model_init
    from repro.data.request_source import TableReplaySource
    from repro.launch.mesh import make_request_mesh, process_shard_rows
    from repro.obs import Obs, WindowEventLog
    from repro.serving.pipeline import ServingPipeline, window_layout
    from repro.serving.stream import run_stream

    sizes = json.loads(os.environ["MH_SIZES"])
    art = os.environ["MH_ART_DIR"]
    host = mh.host_label()

    rng = np.random.default_rng(0)
    u, i = 40, 150
    scores = {k: rng.normal(size=(u, i)).astype(np.float32)
              for k in ("DSSM", "YDNN", "DIN", "DIEN")}
    clicks = (rng.random((u, i)) < 0.15).astype(np.float32)
    n2 = tuple(int(x) for x in np.linspace(0.2 * i, 0.5 * i, 4))
    n3 = tuple(int(x) for x in np.linspace(8, 0.2 * i, 4))
    chains = generate_action_chains((
        StageSpec("recall", (ModelInstance("DSSM", 13e3),), (i,), 4),
        StageSpec("prerank", (ModelInstance("YDNN", 123e3),), n2, 4),
        StageSpec("rank", (ModelInstance("DIN", 7020e3),
                           ModelInstance("DIEN", 7098e3)), n3, 4),
    ))
    server = CascadeServer(stage_scores=scores, chains=chains,
                           clicks=clicks, expose=8)
    ctx = np.random.default_rng(5).normal(size=(u, 12)).astype(np.float32)
    src = TableReplaySource.from_server(server, ctx, seed=7,
                                        device_tables=False)
    rcfg = RewardModelConfig(n_stages=3, max_models=2, n_scale_groups=4,
                             d_context=12, d_feature=16, d_hidden=16,
                             d_state=8)
    params = dict(reward_model_init(jax.random.PRNGKey(0), rcfg))
    params["label_norm"] = jnp.asarray(
        np.linspace(1.0, 3.0, chains.n_chains).astype(np.float32))
    budget = 0.5 * float(chains.costs.max()) * 64
    mesh = make_request_mesh()
    pipe = ServingPipeline(src.universe, params, rcfg, budget, mesh=mesh)

    obs = Obs(host=host, events=WindowEventLog(
        os.path.join(art, f"windows.{host}.jsonl")))
    source = mh.MultihostSource(src, pipe) if dist else src
    stats = run_stream(pipe, sizes, source, prefetch=1, obs=obs)
    trace = obs.tracer.write(os.path.join(art, f"trace.{host}.json"))

    windows = []
    for t, (r, n) in enumerate(zip(stats.windows, sizes)):
        if dist:
            b = pipe.window_bucket(n)
            perm, valid, _ = window_layout(n, b, None)
            rows_g = np.concatenate(
                [np.arange(lo, hi) for lo, hi in
                 process_shard_rows(pipe.mesh, b)])
            req = perm[rows_g[valid[rows_g] > 0]]
        else:
            req = np.arange(n)
        windows.append({
            "req": req.tolist(),
            "dec": np.asarray(r.decisions_np).tolist(),
            "lam": np.asarray(mh._host_value(r.lam_after),
                              np.float64).reshape(-1).tolist(),
            "spend": np.asarray(mh._host_value(r.spend),
                                np.float64).reshape(-1).tolist(),
        })
    local_req = sum(len(w["req"]) for w in windows)
    out = {
        "host": mh.host_report(), "label": host, "trace": trace,
        "wall_s": float(stats.wall_s),
        "local_requests": local_req,
        "local_req_per_s": local_req / stats.wall_s,
        "submit_ms": float(sum(stats.submit_ms)),
        "prep_ms": float(sum(stats.prep_ms)),
        "stall_ms": float(sum(stats.stall_ms)),
        "h2d_bytes": int(stats.h2d_bytes),
        "steady_compiles": int(stats.steady_compiles),
        "windows": windows,
    }
    with open(os.environ["MH_OUT"], "w") as f:
        json.dump(out, f)
    print("BENCH CHILD OK", host, flush=True)
""")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launch_group(n_procs: int, sizes: list[int], art_dir: str,
                  timeout: int) -> list[dict]:
    from repro.launch.compile_cache import compile_cache_dir

    assert 8 % n_procs == 0
    os.makedirs(art_dir, exist_ok=True)
    port = _free_port()
    procs = []
    for pid in range(n_procs):
        out = os.path.join(art_dir, f"digest_{pid}.json")
        env = dict(os.environ)
        env.update({
            "PYTHONPATH": os.path.join(REPO, "src"),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": ("--xla_force_host_platform_device_count="
                          f"{8 // n_procs}"),
            "MH_SIZES": json.dumps(sizes),
            "MH_ART_DIR": art_dir, "MH_OUT": out,
            "JAX_COMPILATION_CACHE_DIR": compile_cache_dir(),
        })
        if n_procs > 1:
            env.update({
                "GREENFLOW_COORDINATOR": f"localhost:{port}",
                "GREENFLOW_NUM_PROCESSES": str(n_procs),
                "GREENFLOW_PROCESS_ID": str(pid),
            })
        procs.append((out, subprocess.Popen(
            [sys.executable, "-c", CHILD], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)))
    digests = []
    for out, p in procs:
        o, _ = p.communicate(timeout=timeout)
        if p.returncode != 0:
            raise RuntimeError(f"bench child failed ({out}):\n{o[-4000:]}")
        with open(out) as f:
            digests.append(json.load(f))
    return digests


def _stitch(children: list[dict], t: int, key: str) -> np.ndarray:
    req = np.concatenate([np.asarray(c["windows"][t]["req"], np.int64)
                          for c in children])
    val = np.concatenate([np.asarray(c["windows"][t][key])
                          for c in children])
    order = np.argsort(req)
    if not (req[order] == np.arange(len(req))).all():
        raise AssertionError("stitched request ids are not a permutation")
    return val[order]


def _check_parity(ref: dict, children: list[dict]) -> None:
    """Bitwise: stitched decisions + replicated lam/spend vs P=1."""
    for t in range(len(ref["windows"])):
        rw = ref["windows"][t]
        for c in children:
            if c["windows"][t]["lam"] != rw["lam"]:
                raise AssertionError(f"lam diverged at window {t} on "
                                     f"{c['label']}")
            if c["windows"][t]["spend"] != rw["spend"]:
                raise AssertionError(f"spend diverged at window {t} on "
                                     f"{c['label']}")
        dec = (_stitch(children, t, "dec") if len(children) > 1
               else np.asarray(rw["dec"]))
        if not np.array_equal(dec, np.asarray(rw["dec"])):
            raise AssertionError(f"decisions diverged at window {t}")


def run(*, procs: tuple[int, ...] = (1, 2, 4, 8),
        sizes: list[int] | None = None, json_path: str | None = None,
        trace_out: str | None = None, timeout: int = 900) -> dict:
    from repro.obs.env import env_info
    from repro.obs.trace import merge_chrome_traces

    if sizes is None:
        sizes = [256, 512, 256, 384, 256, 256]
    total_req = sum(sizes)
    base = os.path.join(REPO, "results", "obs", "multihost")
    sweep: list[dict] = []
    ref_children: list[dict] | None = None
    for p in procs:
        art = os.path.join(base, f"p{p}")
        children = _launch_group(p, sizes, art, timeout)
        if p == 1:
            ref_children = children
        if ref_children is not None:
            _check_parity(ref_children[0], children)
        for c in children:
            # P=1 may pay a one-time donated-lam relayout retrace per
            # bucket; the multihost path replicates lam globally before
            # window 0, so its steady state must be exactly zero.
            if p > 1 and c["steady_compiles"]:
                raise AssertionError(
                    f"{c['label']} (P={p}): {c['steady_compiles']} "
                    "steady-state recompiles")
        wall = max(c["wall_s"] for c in children)
        row = {
            "processes": p,
            "devices_per_process": 8 // p,
            "global_shards": 8,
            "wall_s": wall,
            "aggregate_req_per_s": total_req / wall,
            "per_process": [{
                "label": c["label"],
                "wall_s": c["wall_s"],
                "req_per_s": c["local_req_per_s"],
                "local_requests": c["local_requests"],
                "submit_ms": c["submit_ms"],
                "prep_ms": c["prep_ms"],
                "stall_ms": c["stall_ms"],
                "h2d_bytes": c["h2d_bytes"],
            } for c in children],
            "bitwise_parity_vs_p1": True,
            "steady_compiles": max(c["steady_compiles"]
                                   for c in children),
        }
        sweep.append(row)
        print(f"[bench_multihost] P={p}: {row['aggregate_req_per_s']:.1f}"
              f" req/s aggregate over {wall:.1f}s, parity OK",
              flush=True)

    # merge every host's Perfetto trace into one multi-track file
    paths = [c["trace"] for p_row, p in zip(sweep, procs)
             for c in _read_group(base, p)]
    if trace_out is None:
        trace_out = os.path.join(base, "multihost_trace.json")
    merged = merge_chrome_traces(paths, out_path=trace_out)

    cores = os.cpu_count() or 1
    gate_p = max((p for p in procs if p <= cores), default=1)
    scaling = {
        "cpu_cores": cores,
        "gate_armed": cores >= 4 and len(procs) > 1,
        "gate_processes": gate_p,
        "min_efficiency": 0.5,
    }
    by_p = {r["processes"]: r["aggregate_req_per_s"] for r in sweep}
    if scaling["gate_armed"] and 1 in by_p and gate_p in by_p:
        eff = by_p[gate_p] / (gate_p * by_p[1])
        scaling["efficiency"] = eff
        if eff < scaling["min_efficiency"]:
            raise AssertionError(
                f"scaling gate: P={gate_p} efficiency {eff:.2f} < 0.5")
    elif 1 in by_p and len(by_p) > 1:
        hi = max(p for p in by_p if p > 1)
        scaling["efficiency_report_only"] = by_p[hi] / (hi * by_p[1])

    out = {
        "benchmark": "multihost",
        "sizes": sizes,
        "total_requests": total_req,
        "sweep": sweep,
        "scaling": scaling,
        "merged_trace": trace_out,
        "merged_trace_events": len(merged["traceEvents"]),
        "env": env_info(),
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(out, f, indent=2)
        print(f"[bench_multihost] wrote {json_path}")
    return out


def _read_group(base: str, p: int) -> list[dict]:
    art = os.path.join(base, f"p{p}")
    out = []
    for pid in range(p):
        with open(os.path.join(art, f"digest_{pid}.json")) as f:
            out.append(json.load(f))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json",
                    default=os.path.join(REPO, "BENCH_multihost.json"))
    ap.add_argument("--fast", action="store_true",
                    help="P in {1, 2} with short windows (smoke)")
    ap.add_argument("--procs", type=int, nargs="+", default=None,
                    help="process counts to sweep (must divide 8)")
    ap.add_argument("--trace-out", default=None,
                    help="merged Perfetto trace path")
    args = ap.parse_args(argv)
    procs = tuple(args.procs) if args.procs else (
        (1, 2) if args.fast else (1, 2, 4, 8))
    sizes = [64, 96, 64] if args.fast else None
    run(procs=procs, sizes=sizes, json_path=args.json,
        trace_out=args.trace_out)


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(REPO, "src"))
    main()
