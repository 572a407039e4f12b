"""Observability (repro/obs/): free when off, invisible when on.

The flight recorder's two contract halves, each pinned here:

  * OFF is free: a disabled registry/tracer hands out shared stateless
    no-op singletons - no allocations, no locks - so the serving hot
    path pays one method call.
  * ON is invisible: enabling the full stack (registry + spans + JSONL
    window exporter) changes NOTHING numeric - decisions, revenues,
    spends and lambda traces are bitwise identical to a telemetry-off
    run, in the plain and geotenants pipelines, sequential and
    prefetched.

Plus the exporter schemas (Prometheus text, Chrome trace-event JSON,
window JSONL) and deterministic prep/stall/submit attribution through
the injected ``clock``.
"""
import json
import threading

import numpy as np
import pytest


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram():
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    c = reg.counter("greenflow_windows_total", "windows")
    c.inc()
    c.inc(3)
    g = reg.gauge("greenflow_lambda")
    g.labels(axis="tenant[0]").set(1.5e-5)
    g.labels(axis="region_a").set(2.0)
    h = reg.histogram("greenflow_prep_ms", "prep", "ms",
                      edges=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 3.0, 100.0):
        h.observe(v)

    snap = reg.snapshot()
    assert snap["greenflow_windows_total"]["series"][0]["value"] == 4
    lam = {tuple(s["labels"].items()): s["value"]
           for s in snap["greenflow_lambda"]["series"]}
    assert lam[(("axis", "tenant[0]"),)] == pytest.approx(1.5e-5)
    hs = snap["greenflow_prep_ms"]["series"][0]
    assert hs["count"] == 4
    assert hs["sum"] == pytest.approx(104.5)
    # le-inclusive cumulative buckets: 1.0 lands IN the le="1" bucket
    assert hs["buckets"] == {"1": 2, "2": 2, "4": 3, "+Inf": 4}


def test_registry_same_instrument_and_child_cached():
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    a = reg.counter("x_total")
    assert reg.counter("x_total") is a
    assert a.labels(bucket=128) is a.labels(bucket=128)
    with pytest.raises(ValueError):
        reg.gauge("x_total")  # kind mismatch fails loudly


def test_prometheus_text_format():
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("greenflow_requests_total", "requests").inc(7)
    reg.gauge("greenflow_spend").labels(axis="region_a").set(0.5)
    h = reg.histogram("greenflow_stall_ms", "stall", "ms",
                      edges=(1.0, 2.0))
    h.observe(1.5)
    text = reg.prometheus_text()
    lines = text.splitlines()
    assert "# TYPE greenflow_requests_total counter" in lines
    assert "greenflow_requests_total 7" in lines
    assert 'greenflow_spend{axis="region_a"} 0.5' in lines
    assert 'greenflow_stall_ms_bucket{le="2"} 1' in lines
    assert 'greenflow_stall_ms_bucket{le="+Inf"} 1' in lines
    assert "greenflow_stall_ms_sum 1.5" in lines
    assert "greenflow_stall_ms_count 1" in lines


def test_disabled_registry_is_allocation_free():
    """The zero-overhead contract: a disabled registry returns shared
    stateless singletons, and driving them over a hot loop allocates
    NOTHING that survives (no children, no lock state, no events)."""
    import gc
    import tracemalloc

    import repro.data.request_source as rs
    from repro.obs import NULL_OBS, current, get_obs
    from repro.obs.metrics import (MetricsRegistry, NULL_INSTRUMENT)
    from repro.obs.trace import NULL_SPAN

    reg = MetricsRegistry(enabled=False)
    c = reg.counter("greenflow_windows_total")
    h = reg.histogram("greenflow_prep_ms")
    assert c is NULL_INSTRUMENT and h is NULL_INSTRUMENT
    assert c.labels(bucket=128) is NULL_INSTRUMENT
    obs = get_obs(None)
    assert obs is NULL_OBS
    assert obs.span("prep") is NULL_SPAN
    # a source built without a bundle records into current(): with no
    # span open, that is the disabled bundle
    assert current() is NULL_OBS

    class Source(rs.RequestSource):
        n_users = 100

    src = Source()
    assert src._spans() is NULL_OBS

    def hot():
        for _ in range(2000):
            c.inc()
            c.inc(7)
            h.observe(3.5)
            with obs.span("prep"):
                pass
            src.arrivals(0, 4)
            with current().span("context_rows", n=4):
                pass
            with current().span("gather_dispatch", n=4):
                pass

    hot()  # warm every code path first
    # attribute allocations by site and count only what the obs module
    # RETAINS: a full test-process has unrelated background threads
    # allocating, and CPython freelists churn a few transient dicts -
    # neither may flake this.  Any real per-call state would retain
    # >= 100 KB over the 2000 iterations; allow one page of churn.
    import os

    import repro.obs as obs_pkg
    obs_dir = os.path.dirname(obs_pkg.__file__)
    tracemalloc.start(1)
    gc.collect()
    before = tracemalloc.take_snapshot()
    hot()
    gc.collect()
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    retained = sum(
        s.size_diff for s in after.compare_to(before, "lineno")
        if s.size_diff > 0
        and (s.traceback[0].filename.startswith(obs_dir)
             or s.traceback[0].filename == rs.__file__))
    assert retained < 4096, \
        f"disabled telemetry retained {retained} bytes"
    assert obs.tracer.events == []


# ---------------------------------------------------------------------------
# span tracer + Chrome trace export
# ---------------------------------------------------------------------------


def test_chrome_trace_schema(tmp_path):
    """The exported file is valid Chrome trace-event JSON: complete
    events nest, threads get distinct tids and thread_name metadata."""
    from repro.obs.trace import Tracer

    tracer = Tracer()
    with tracer.span("serve", t=0):
        with tracer.span("dispatch", n=128):
            pass

    def worker():
        with tracer.span("prep", t=1):
            pass

    th = threading.Thread(target=worker, name="chunk-prefetch")
    th.start()
    th.join()

    path = tracer.write(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert {e["name"] for e in xs} == {"serve", "dispatch", "prep"}
    for e in xs:
        assert {"name", "ph", "pid", "tid", "ts", "dur"} <= set(e)
    # two threads -> two distinct tids, both named
    assert len({e["tid"] for e in xs}) == 2
    names = {e["args"]["name"] for e in metas}
    assert {"MainThread", "chunk-prefetch"} <= names
    # nesting: dispatch sits inside serve on the same track
    serve = next(e for e in xs if e["name"] == "serve")
    disp = next(e for e in xs if e["name"] == "dispatch")
    assert disp["tid"] == serve["tid"]
    assert serve["ts"] <= disp["ts"]
    assert disp["ts"] + disp["dur"] <= serve["ts"] + serve["dur"]
    assert serve["args"] == {"t": 0}


# ---------------------------------------------------------------------------
# deterministic timing attribution (injected clock)
# ---------------------------------------------------------------------------


class _FakeResult:
    def __init__(self):
        self.prep_ms = 0.0
        self.stall_ms = 0.0
        self.h2d_bytes = 0
        self.compiles = 0
        self.bucket = None
        self.n_valid = 0
        self.revenue_np = np.zeros(0, np.float32)


class _FakePipeline:
    def serve_window(self, ctx, rows, **kw):
        return _FakeResult()


def test_fake_clock_timing_attribution():
    """With an injected deterministic clock the sequential driver's
    timing attribution is EXACT: each tick is one second, and every
    prep/submit measurement spans exactly one tick."""
    from repro.serving.stream import run_stream

    ticks = iter(range(1000))

    def clock():
        return float(next(ticks))

    def source(t, n):
        return np.zeros((n, 2), np.float32), np.zeros(n, np.int32)

    sizes = [4, 4, 4]
    st = run_stream(_FakePipeline(), sizes, source, prefetch=0,
                    clock=clock)
    # call order: t0 | prep0 | serve0 prep1 | serve1 prep2 | serve2 |
    # wall -> every measured phase is exactly one 1 s tick
    assert st.prep_ms == [1000.0, 1000.0, 1000.0]
    assert st.submit_ms == [1000.0, 1000.0, 1000.0]
    assert st.stall_ms == [0.0, 0.0, 0.0]
    assert st.dispatch_ms == [2000.0, 2000.0, 2000.0]
    # t0 is tick 0; the final wall read is tick 13 (1 + 2*len + 2*len)
    assert st.wall_s == 13.0


# ---------------------------------------------------------------------------
# telemetry on/off bitwise parity (the non-negotiable invariant)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serving_stack(system_exp, system_reward):
    from repro.cascade.engine import CascadeServer, precompute_stage_scores

    exp = system_exp
    params, rcfg = system_reward
    scores = precompute_stage_scores(exp.models, exp.world,
                                     exp.split.final_eval)
    server = CascadeServer(stage_scores=scores, chains=exp.chains,
                           clicks=exp.clicks_eval, expose=exp.cfg.expose)
    return exp, server, params, rcfg


def _gen_source(exp, *, seed=3, chunk=64, n_users=50_000, obs=None):
    from dataclasses import replace

    from repro.data.request_source import GeneratedSource
    from repro.data.synthetic import StreamingWorld

    wcfg = replace(exp.cfg.world, n_users=n_users)
    return GeneratedSource(StreamingWorld.build(wcfg), exp.models,
                           exp.chains, expose=exp.cfg.expose, seed=seed,
                           chunk=chunk, item_block=128, obs=obs)


def _full_obs(tmp_path, tag):
    from repro.obs import Obs, WindowEventLog

    return Obs(events=WindowEventLog(str(tmp_path / f"{tag}.jsonl")))


def _assert_stream_parity(a, b):
    for t, (ra, rb) in enumerate(zip(a.windows, b.windows)):
        np.testing.assert_array_equal(ra.decisions_np, rb.decisions_np,
                                      err_msg=f"w{t} decisions")
        np.testing.assert_array_equal(ra.revenue_np, rb.revenue_np,
                                      err_msg=f"w{t} revenue")
        assert np.array_equal(np.asarray(ra.spend),
                              np.asarray(rb.spend)), f"w{t} spend"
        assert np.array_equal(np.asarray(ra.lam_after),
                              np.asarray(rb.lam_after)), f"w{t} lam"


def test_obs_parity_plain(serving_stack, tmp_path):
    """Plain pipeline, sequential reference path: telemetry on vs off
    is bitwise identical, and the on-run's flight log carries one row
    per window with the right shape."""
    from repro.serving.pipeline import ServingPipeline
    from repro.serving.stream import run_stream

    exp, _, params, rcfg = serving_stack
    sizes = [32, 64, 32]
    budget = 0.5 * exp.chains.costs.max() * 32
    off = _gen_source(exp)
    obs = _full_obs(tmp_path, "plain")
    on = _gen_source(exp, obs=obs)
    st_off = run_stream(
        ServingPipeline(off.universe, params, rcfg, budget),
        sizes, off, prefetch=0)
    st_on = run_stream(
        ServingPipeline(on.universe, params, rcfg, budget, obs=obs),
        sizes, on, prefetch=0, obs=obs)
    _assert_stream_parity(st_off, st_on)

    rows = [json.loads(line)
            for line in open(obs.events.path).read().splitlines()]
    assert len(rows) == len(sizes)
    assert [r["n"] for r in rows] == sizes
    assert rows[0]["lam"].keys() == {"global"}
    assert rows[0]["spend"].keys() == {"global"}
    snap = obs.metrics.snapshot()
    assert snap["greenflow_windows_total"]["series"][0]["value"] \
        == len(sizes)
    assert snap["greenflow_requests_total"]["series"][0]["value"] \
        == sum(sizes)


def test_obs_parity_geotenants_prefetched(serving_stack, tmp_path):
    """Geotenants pipeline with prefetch>0: telemetry on vs off stays
    bitwise identical, the JSONL rows name every constraint axis, and
    the trace records the prefetch thread as its own track."""
    from repro.serving.pipeline import ServingPipeline
    from repro.serving.spec import (ConstraintSpec, GlobalAxis,
                                    RegionAxis, TenantAxis)
    from repro.serving.stream import run_stream

    exp, _, params, rcfg = serving_stack
    sizes = [48, 96, 48]
    per_req = 0.5 * float(exp.chains.costs.max())
    spec = ConstraintSpec([
        TenantAxis((per_req * 24, per_req * 24), priced=True),
        RegionAxis(2), GlobalAxis(pricing="carbon"),
    ])
    bt = [np.concatenate([np.full(2, per_req * n / 2),
                          np.full(2, 0.6 * per_req * n)]).astype(
        np.float32) for n in sizes]
    st_ = [np.array([1.0, 1.3], np.float32)] * len(sizes)

    off = _gen_source(exp, seed=11)
    obs = _full_obs(tmp_path, "geotenants")
    on = _gen_source(exp, seed=11, obs=obs)
    st_off = run_stream(
        ServingPipeline.from_spec(off.universe, params, rcfg, spec),
        sizes, off, budget_trace=bt, scale_trace=st_, prefetch=2)
    st_on = run_stream(
        ServingPipeline.from_spec(on.universe, params, rcfg, spec,
                                  obs=obs),
        sizes, on, budget_trace=bt, scale_trace=st_, prefetch=2,
        obs=obs)
    _assert_stream_parity(st_off, st_on)

    cs = spec.compile()
    rows = [json.loads(line)
            for line in open(obs.events.path).read().splitlines()]
    assert len(rows) == len(sizes)
    assert list(rows[-1]["lam"]) == list(cs.k_names)
    assert list(rows[-1]["budget"]) == list(cs.budget_names)
    assert rows[-1]["budget"]["tenant[0]"] == pytest.approx(
        float(bt[-1][0]))
    # the prefetch worker shows up as its own named track
    trace = obs.tracer.chrome_trace()
    tnames = {e["args"]["name"] for e in trace["traceEvents"]
              if e["ph"] == "M"}
    assert "chunk-prefetch" in tnames and "MainThread" in tnames
    span_names = {e["name"] for e in trace["traceEvents"]
                  if e["ph"] == "X"}
    assert {"prep", "serve", "h2d", "dispatch", "dual_update",
            "stall", "block_until_ready"} <= span_names
    # per-axis gauges landed from the final window
    snap = obs.metrics.snapshot()
    lam_axes = {s["labels"]["axis"]
                for s in snap["greenflow_lambda"]["series"]}
    assert lam_axes == set(cs.k_names)


def test_legacy_stats_views_still_derive(serving_stack):
    """The bit-compatible derived views survive the obs refactor:
    StreamStats lists, WindowResult.compiles, source cache counters."""
    from repro.serving.pipeline import ServingPipeline
    from repro.serving.stream import run_stream

    exp, _, params, rcfg = serving_stack
    sizes = [32, 32]
    budget = 0.5 * exp.chains.costs.max() * 32
    src = _gen_source(exp, seed=5)
    st = run_stream(ServingPipeline(src.universe, params, rcfg, budget),
                    sizes, src, prefetch=0)
    assert len(st.prep_ms) == len(st.stall_ms) == len(sizes)
    assert st.dispatch_ms == [p + s for p, s in zip(st.prep_ms,
                                                    st.submit_ms)]
    assert st.compiles == [int(r.compiles) for r in st.windows]
    assert st.h2d_bytes == sum(int(r.h2d_bytes) for r in st.windows)
    assert src.cache_hits + src.cache_misses > 0  # ints still count


def test_env_info_shape():
    from repro.obs.env import env_info

    info = env_info()
    assert isinstance(info["cpu_count"], int)
    assert "timestamp_utc" in info
    assert "jax" in info and "backend" in info
