"""Names the device trace and the span tree can be read by.

  * Every device program of a served window is named by the program:
    the online pass compiles as ``jit_fused_pass`` and the nearline
    update as ``jit_dual_update`` in each spec mode, on one device and
    under a request mesh; the replay source's row gathers compile as
    ``jit_replay_gather``.
  * Spans record their parent and the window index ``t`` they inherit,
    and a component built without a bundle (the replay source) records
    into the bundle whose span is open on its thread: ``arrivals``,
    ``context_rows`` and ``gather_dispatch`` nest under the stream's
    ``prep`` with its ``t``.
  * Telemetry on stays invisible: replayed decisions, prices and spends
    are bitwise those of a telemetry-off run.
"""
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
MODES = ("plain", "tenants", "geo", "geotenants")


# ---------------------------------------------------------------------------
# span parent, window id, current()
# ---------------------------------------------------------------------------


def test_spans_record_parent_and_inherited_window():
    from repro.obs.trace import Tracer

    tracer = Tracer()
    with tracer.span("serve", t=3):
        with tracer.span("dispatch", n=8):
            with tracer.span("inner", t=None):
                pass

    def worker():
        with tracer.span("prep", t=4):
            with tracer.span("arrivals"):
                pass

    th = threading.Thread(target=worker, name="chunk-prefetch")
    th.start()
    th.join(timeout=30)
    assert not th.is_alive()
    with tracer.span("block_until_ready"):
        pass

    ev = {e.name: e for e in tracer.events}
    assert (ev["serve"].parent, ev["serve"].t) == (None, 3)
    assert (ev["dispatch"].parent, ev["dispatch"].t) == ("serve", 3)
    assert (ev["inner"].parent, ev["inner"].t) == ("dispatch", 3)
    # the other thread's stack is its own
    assert (ev["prep"].parent, ev["prep"].t) == (None, 4)
    assert (ev["arrivals"].parent, ev["arrivals"].t) == ("prep", 4)
    assert (ev["block_until_ready"].parent,
            ev["block_until_ready"].t) == (None, None)

    xs = {e["name"]: e for e in tracer.chrome_trace()["traceEvents"]
          if e["ph"] == "X"}
    assert xs["dispatch"]["args"] == {"n": 8, "parent": "serve", "t": 3}
    assert xs["arrivals"]["args"] == {"parent": "prep", "t": 4}
    assert xs["serve"]["args"] == {"t": 3}
    assert "args" not in xs["block_until_ready"]
    assert not hasattr(tracer, "instant")


def test_self_time_from_the_export():
    """A span's self time is its duration minus what the spans naming it
    as parent cover, read from the exported trace alone."""
    from repro.obs.trace import Tracer

    ticks = iter(range(0, 10_000, 1000))
    tracer = Tracer(clock_ns=lambda: next(ticks))
    with tracer.span("prep", t=0):  # 0 .. 5000
        with tracer.span("arrivals"):  # 1000 .. 2000
            pass
        with tracer.span("gather_dispatch"):  # 3000 .. 4000
            pass
    xs = [e for e in tracer.chrome_trace()["traceEvents"] if e["ph"] == "X"]
    prep = next(e for e in xs if e["name"] == "prep")
    kids = [e for e in xs if e.get("args", {}).get("parent") == "prep"
            and e["tid"] == prep["tid"] and e["args"]["t"] == 0]
    assert prep["dur"] == 5.0
    assert prep["dur"] - sum(k["dur"] for k in kids) == 3.0


def test_current_is_the_bundle_of_the_open_span():
    from repro.obs import NULL_OBS, Obs, current

    obs = Obs()
    assert current() is NULL_OBS
    seen = {}
    with obs.span("prep", t=0):
        assert current() is obs

        def other():
            seen["other"] = current()

        th = threading.Thread(target=other)
        th.start()
        th.join(timeout=30)
        with current().span("arrivals"):
            assert current() is obs
    assert current() is NULL_OBS
    assert seen["other"] is NULL_OBS  # no span open on that thread
    assert [(e.name, e.parent, e.t) for e in obs.tracer.events] == [
        ("arrivals", "prep", 0), ("prep", None, 0)]


# ---------------------------------------------------------------------------
# device program names
# ---------------------------------------------------------------------------


def _pipeline(mode: str, mesh=None):
    """An untrained stack in spec ``mode``, and the kwargs of a window."""
    from repro.analysis.jaxpr_audit import build_audit_stack
    from repro.serving.pipeline import ServingPipeline
    from repro.serving.spec import (ConstraintSpec, GlobalAxis, RegionAxis,
                                    TenantAxis)

    base, window, _ = build_audit_stack("plain")
    budget = base.budget
    specs = {
        "plain": (ConstraintSpec([GlobalAxis(budget=budget)]), {}),
        "tenants": (ConstraintSpec([TenantAxis((budget / 2, budget / 2),
                                               priced=True)]),
                    {"budget": np.full(2, budget / 2, np.float32)}),
        "geo": (ConstraintSpec([RegionAxis(2), GlobalAxis(budget=budget)]),
                {"budget": np.full(2, budget / 2, np.float32),
                 "cost_scale": np.ones(2, np.float32)}),
        "geotenants": (ConstraintSpec([
            TenantAxis((budget / 2, budget / 2), priced=True),
            RegionAxis(2)]),
            {"budget": np.full(4, budget / 2, np.float32),
             "cost_scale": np.ones(2, np.float32)}),
    }
    spec, extra = specs[mode]
    pipe = ServingPipeline.from_spec(base.server, base.reward_params,
                                     base.reward_cfg, spec, mesh=mesh)
    assert pipe._cs.mode == mode
    return pipe, window, extra


def _module_names(pipe, window, extra) -> list[str]:
    """The lowered module of every (main, dual) program a window ran."""
    from repro.analysis.jaxpr_audit import _Capture

    pipe.serve_window(*window(0), **extra)
    caps = {}
    for key, fns in list(pipe._fns.items()):
        caps[key] = pipe._fns[key] = tuple(_Capture(f) for f in fns)
    pipe.serve_window(*window(1), **extra)
    names = []
    for fns in caps.values():
        for cap in fns:
            assert cap.calls
            head = cap.fn.lower(*cap.calls[0]).as_text().split(None, 2)
            names.append(head[1])
    return names


@pytest.mark.parametrize("mode", MODES)
def test_window_programs_are_named(mode):
    pipe, window, extra = _pipeline(mode)
    assert _module_names(pipe, window, extra) == ["@jit_fused_pass",
                                                  "@jit_dual_update"]


def test_window_programs_are_named_under_a_request_mesh():
    """The shard_map-wrapped programs keep their names (4 virtual CPU
    devices, in a subprocess: this process keeps one device)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(REPO, "src"),
                                           os.path.join(REPO, "tests")]))
    script = textwrap.dedent(f"""
        import jax
        from repro.launch.mesh import make_request_mesh
        from test_obs_spans import _module_names, _pipeline

        assert len(jax.devices()) == 4
        mesh = make_request_mesh(4)
        for mode in {MODES!r}:
            pipe, window, extra = _pipeline(mode, mesh=mesh)
            print(mode, *_module_names(pipe, window, extra))
    """)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = out.stdout.strip().splitlines()
    assert lines == [f"{m} @jit_fused_pass @jit_dual_update"
                     for m in MODES]


@pytest.fixture(scope="module")
def replay():
    """A replay source over an untrained stack's tables, its contexts,
    and the stack's pipeline parts."""
    from repro.analysis.jaxpr_audit import build_audit_stack
    from repro.data.request_source import TableReplaySource

    pipe, _, _ = build_audit_stack("plain")
    u = pipe.server.compact.p_sorted.shape[1]
    ctx = np.random.default_rng(5).normal(
        size=(u, pipe.reward_cfg.d_context)).astype(np.float32)
    return pipe, ctx, TableReplaySource.from_server(
        pipe.server, ctx, seed=9, device_tables=True)


def test_replay_source_dispatches_replay_gather(replay, monkeypatch):
    """Each window gathers both user-major (U, W) device tables through
    ``jit_replay_gather``, bitwise the host path's rows; the tables go
    up once, under one ``table_upload`` span, on the first window."""
    import jax.numpy as jnp

    import repro.data.request_source as rs
    from repro.obs import Obs

    pipe, ctx, _ = replay
    src = rs.TableReplaySource.from_server(pipe.server, ctx, seed=9,
                                           device_tables=True)
    calls = []
    real = rs.replay_gather

    def spy(table, users, g, cap):
        calls.append((table.shape, users.shape))
        return real(table, users, g, cap)

    monkeypatch.setattr(rs, "replay_gather", spy)
    g, _, cap = src.p_sorted.shape
    width = rs.replay_width(g, cap)
    assert width % 128 == 0 and g * cap <= width < g * cap + 128
    obs = Obs()
    for t in range(2):
        calls.clear()
        with obs.span("prep", t=t):
            users = src.arrivals(t, 24)
            chunk = src.window_for_users(users)
        assert calls == [((src.n_users, width), (24,))] * 2
        np.testing.assert_array_equal(np.asarray(chunk.tables["p"]),
                                      src.p_sorted[:, users])
        np.testing.assert_array_equal(np.asarray(chunk.tables["ck"]),
                                      src.clicks_sorted[:, users])
    uploads = [e for e in obs.tracer.events if e.name == "table_upload"]
    assert [(e.parent, e.t, e.args) for e in uploads] == [
        ("prep", 0, {"bytes": src.p_sorted.nbytes
                     + src.clicks_sorted.nbytes})]
    head = real.lower(src._dev[0], jnp.asarray(users, jnp.int32),
                      g, cap).as_text()
    assert head.split(None, 2)[1] == "@jit_replay_gather"


# ---------------------------------------------------------------------------
# spans of a replayed stream
# ---------------------------------------------------------------------------


def _geotenants_stream(replay, obs, sizes, prefetch):
    from repro.serving.pipeline import ServingPipeline
    from repro.serving.spec import (ConstraintSpec, GlobalAxis, RegionAxis,
                                    TenantAxis)
    from repro.serving.stream import run_stream

    base, ctx, src = replay
    per_req = 0.5 * float(base.chains.costs.max())
    spec = ConstraintSpec([
        TenantAxis((per_req * 24, per_req * 24), priced=True),
        RegionAxis(2), GlobalAxis(pricing="carbon")])
    bt = [np.concatenate([np.full(2, per_req * n / 2),
                          np.full(2, 0.6 * per_req * n)]).astype(np.float32)
          for n in sizes]
    sc = [np.array([1.0, 1.3], np.float32)] * len(sizes)
    pipe = ServingPipeline.from_spec(src.universe, base.reward_params,
                                     base.reward_cfg, spec, obs=obs)
    return run_stream(pipe, sizes, src, budget_trace=bt, scale_trace=sc,
                      prefetch=prefetch, obs=obs, forecast=True)


@pytest.mark.parametrize("prefetch", [0, 2])
def test_replay_prep_spans_nest_under_prep(replay, prefetch):
    from repro.obs import Obs

    obs = Obs()
    sizes = [32, 64, 32, 48]
    _geotenants_stream(replay, obs, sizes, prefetch)
    ev = obs.tracer.events
    windows = range(len(sizes))
    for name in ("arrivals", "context_rows", "gather_dispatch", "h2d",
                 "dispatch", "dual_update"):
        spans = [e for e in ev if e.name == name]
        assert sorted(e.t for e in spans) == list(windows), name
        outer = "prep" if name in ("arrivals", "context_rows",
                                   "gather_dispatch") else "serve"
        for e in spans:
            assert e.parent == outer, (name, e.parent)
            (host,) = [p for p in ev if p.name == outer and p.t == e.t]
            # nested on the same thread, inside the parent's interval
            assert host.tid == e.tid
            assert host.t0_ns <= e.t0_ns
            assert e.t0_ns + e.dur_ns <= host.t0_ns + host.dur_ns
    prep = {e.t: e for e in ev if e.name == "prep"}
    for t in windows:
        inner = sum(e.dur_ns for e in ev if e.parent == "prep" and e.t == t)
        assert inner <= prep[t].dur_ns
    if prefetch:
        assert {e.thread for e in ev if e.name == "gather_dispatch"} == {
            "chunk-prefetch"}


def test_replay_telemetry_on_is_bitwise_off(replay):
    from repro.obs import Obs

    sizes = [32, 64, 32, 48]
    off = _geotenants_stream(replay, None, sizes, 2)
    on = _geotenants_stream(replay, Obs(), sizes, 2)
    for t, (a, b) in enumerate(zip(off.windows, on.windows)):
        np.testing.assert_array_equal(a.decisions_np, b.decisions_np,
                                      err_msg=f"w{t} decisions")
        np.testing.assert_array_equal(a.revenue_np, b.revenue_np)
        np.testing.assert_array_equal(a.regions_np, b.regions_np)
        for f in ("spend", "tr_spend", "lam_after"):
            assert np.array_equal(np.asarray(getattr(a, f)),
                                  np.asarray(getattr(b, f))), (t, f)
