"""Smoke run of the streaming serving path on a TPU.

    python chip_smoke.py             # one chip: phases (a)-(e)
    python chip_smoke.py --chips 4   # the 4-chip request mesh vs one chip

Drives what ``python -m repro.launch.serve --source generated`` runs,
through the same functions, at the full width of the shipped cascade
(``experiments.serve_config()``: DSSM, YouTubeDNN, DIN and DIEN stage
models, 400 items, history 10, 4 scales) over a 100,000-user generated
universe.  Everything is built from the seed in this one process; the
script reads no cached experiment and starts no other process.

Phases, one chip:

  (a) device   - JAX's first device must be a TPU, or the script exits
                 non-zero before anything else runs;
  (b) build    - train the four cascade models and the reward model;
  (c) plain    - 8 windows of the spike scenario under one FLOPs budget
                 (two padding buckets compile);
  (d) geotenants - 8 windows, 2 tenants x 2 regions, per-tenant prices,
                 CI-forecast dual warm-start;
  (e) kernel   - ``CascadeServer.serve`` through the compiled Pallas
                 truncation kernel against the XLA ``_revenue_compact``.

After each stream: no compile in a window whose bucket was already
served, spend within budget on windows at or below the base traffic and
within max(budget, n * cheapest) on every window (both to a float32
rounding tolerance), revenue > 0, finite prices, and the pass's outputs
on the TPU.

``--chips 4`` runs only (b) and the two streams over
``make_request_mesh(4)`` next to the same streams on one chip, and
fails if any decision differs.

The times printed are those of one smoke run, compilation included;
they are not benchmark figures.  The last line of output is one JSON
object naming the device; it is printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
USERS = 100_000
WINDOWS = 8
# spend is summed in float32 on the device, budgets are float32 too: a
# few-hundred-term sum may land a few ulps off the guard's own prefix
SPEND_RTOL = 1e-5
PLAIN = ["--scenario", "spike"]
GEOTENANTS = ["--scenario", "geotenants", "--tenants", "2",
              "--tenant-mode", "priced", "--ci-forecast"]


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def check_device(chips: int):
    """Phase (a): the TPU devices, or exit non-zero."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU - JAX's devices are "
                         f"{devs[0].platform} ({len(devs)})")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"TPU devices, JAX sees {len(devs)}")
    return devs


def serve_args(spec: list[str], *, shards: int = 0):
    from repro.launch import serve

    argv = ["--source", "generated", "--users", str(USERS),
            "--windows", str(WINDOWS), *spec,
            "--carbon-report",
            os.path.join(REPO, "results", "obs", "smoke_carbon_report.csv")]
    if shards:
        argv += ["--shards", str(shards)]
    return serve.build_parser().parse_args(argv)


def _platforms(x) -> set:
    return {d.platform for d in x.devices()}


def check_stream(name: str, run, stack, base: int, platform: str) -> None:
    """The asserts every stream must pass (see the module docstring)."""
    from repro.obs.events import _host_np

    (st,) = run.streams
    c_min = float(stack[0].chains.costs.min())
    _check(st.steady_compiles == 0,
           f"{name}: {st.steady_compiles} compiles in warm buckets "
           f"(per window: {st.compiles})")
    for t, r in enumerate(st.windows):
        spend = float(np.sum(_host_np(r.spend)))
        if r.n_valid <= base:
            _check(spend <= r.budget * (1.0 + SPEND_RTOL),
                   f"{name} window {t}: spend/budget "
                   f"{spend / r.budget:.6f} > 1")
        _check(bool(np.all(np.isfinite(_host_np(r.lam_after)))),
               f"{name} window {t}: non-finite price {r.lam_after}")
        for what in ("decisions", "revenue", "spend", "lam_after"):
            got = _platforms(getattr(r, what))
            _check(got == {platform},
                   f"{name} window {t}: {what} lives on {got}")
    over = st.overshoot(c_min)
    _check(over <= SPEND_RTOL, f"{name}: worst overshoot vs cap {over}")
    _check(run.total_revenue > 0, f"{name}: revenue {run.total_revenue}")


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def stream_phase(name: str, spec: list[str], stack, platform: str, dev,
                 *, shards: int = 0):
    """One serve() stream and its checks; returns the ServeRun."""
    from repro.launch import serve

    args = serve_args(spec, shards=shards)
    t0 = time.perf_counter()
    run = serve.serve(args, stack)
    wall = time.perf_counter() - t0
    check_stream(name, run, stack, args.requests, platform)
    (st,) = run.streams
    first = st.windows[0].stall_ms + st.submit_ms[0]
    print(f"[smoke] {name}: {len(st.windows)} windows passed; phase "
          f"{wall:.1f} s, first window {first:.0f} ms incl. compile, "
          f"{len(st.windows) / st.wall_s:.2f} windows/s, buckets "
          f"{sorted({r.bucket[0] for r in st.windows})}, peak device "
          f"bytes {_peak_bytes(dev)} (smoke run, not a benchmark)")
    return run


def kernel_phase(stack, platform: str) -> None:
    """Phase (e): the served Pallas kernel against the XLA path."""
    import jax.numpy as jnp

    from repro.cascade.engine import _revenue_compact
    from repro.kernels.cascade_truncate import compact_truncate_revenue

    server = stack[1]
    plan = server.compact
    _check(plan is not None, "the serving stack lost its CompactPlan")
    _check(server.use_pallas, "CascadeServer did not pick the kernel")
    rng = np.random.default_rng(0)
    b = 4096
    rows = rng.integers(0, plan.p_sorted.shape[1], b).astype(np.int32)
    dec = rng.integers(0, server.chains.n_chains, b).astype(np.int32)
    t0 = time.perf_counter()
    got, _ = server.serve(rows, dec)
    wall = time.perf_counter() - t0
    args = (jnp.asarray(plan.p_sorted), jnp.asarray(plan.clicks_sorted),
            jnp.asarray(plan.group_of_chain[dec]), jnp.asarray(rows),
            jnp.asarray(plan.n3_of_chain[dec]))
    want = np.asarray(_revenue_compact(*args, expose=plan.expose))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)
    hlo = compact_truncate_revenue.lower(
        *args, expose=plan.expose,
        interpret=platform == "cpu").compile().as_text()
    if platform == "tpu":
        _check("tpu_custom_call" in hlo, "no Pallas kernel in the HLO")
    print(f"[smoke] kernel: CascadeServer.serve == _revenue_compact on "
          f"{b} requests over ({plan.p_sorted.shape}) tables; first "
          f"call {wall * 1e3:.0f} ms incl. compile (smoke run)")


def mesh_phase(stack, platform: str, devs, chips: int) -> None:
    """Both specs over a ``chips``-way request mesh and on one chip:
    every decision must agree; spend and price differences print."""
    from repro.obs.events import _host_np

    for name, spec in (("plain", PLAIN), ("geotenants", GEOTENANTS)):
        one = stream_phase(name, spec, stack, platform, devs[0])
        mesh = stream_phase(f"{name} x{chips}", spec, stack, platform,
                            devs[0], shards=chips)
        d_spend = d_lam = r_spend = r_lam = 0.0
        for t, (a, b) in enumerate(zip(one.streams[0].windows,
                                       mesh.streams[0].windows)):
            _check(len(b.decisions.devices()) == chips,
                   f"{name} window {t}: the mesh pass ran on "
                   f"{b.decisions.devices()}")
            _check(np.array_equal(a.decisions_np, b.decisions_np),
                   f"{name} window {t}: decisions differ on {chips} "
                   f"chips")
            if a.regions is not None:
                _check(np.array_equal(a.regions_np, b.regions_np),
                       f"{name} window {t}: regions differ on {chips} "
                       f"chips")
            for x, y, kind in ((a.spend, b.spend, "spend"),
                               (a.lam_after, b.lam_after, "lam")):
                x, y = _host_np(x), _host_np(y)
                diff = float(np.max(np.abs(x - y)))
                rel = diff / max(float(np.max(np.abs(x))), 1e-30)
                if kind == "spend":
                    d_spend, r_spend = max(d_spend, diff), max(r_spend, rel)
                else:
                    d_lam, r_lam = max(d_lam, diff), max(r_lam, rel)
        print(f"[smoke] {name}: 1 vs {chips} chips - every decision "
              f"equal; largest spend diff {d_spend!r} (relative "
              f"{r_spend!r}), largest lambda diff {d_lam!r} (relative "
              f"{r_lam!r})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the request-mesh parity phase")
    args = ap.parse_args(argv)

    t_all = time.perf_counter()
    devs = check_device(args.chips)
    import jax

    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.experiments import build_serving_stack, serve_config
    from repro.launch.compile_cache import enable_compile_cache

    dev = devs[0]
    print(f"[smoke] device {dev.platform} '{dev.device_kind}' x{len(devs)}"
          f"; jax {jax.__version__}; compile cache "
          f"{enable_compile_cache()}")
    t0 = time.perf_counter()
    stack = build_serving_stack(serve_config(), cache=False, verbose=True)
    print(f"[smoke] build: {time.perf_counter() - t0:.1f} s "
          f"(smoke run)")
    if args.chips > 1:
        mesh_phase(stack, "tpu", devs, args.chips)
    else:
        stream_phase("plain", PLAIN, stack, "tpu", dev)
        stream_phase("geotenants", GEOTENANTS, stack, "tpu", dev)
        kernel_phase(stack, "tpu")
    print(f"[smoke] all phases passed in {time.perf_counter() - t_all:.1f}"
          f" s (smoke run)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
