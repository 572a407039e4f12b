"""Streaming request path: RequestSource parity, determinism and the
zero-recompile guarantee.

The tentpole claim is that retiring the materialized (U, J) universe
changes NOTHING observable: replaying the server's own tables through
the chunked path is bitwise identical (decisions, revenues, prices,
spends), window production is a pure function of (seed, t) however the
host chunks the work, and bucketed padding keeps the jit cache warm
across traffic spikes.
"""
import numpy as np
import pytest

import jax.numpy as jnp


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


@pytest.fixture(scope="module")
def replay_source(serving_stack):
    from repro.data.request_source import TableReplaySource

    exp, server, _, _ = serving_stack
    src = TableReplaySource.from_server(server, exp.ctx_eval, seed=7)
    assert src.device_tables  # windows gather through replay_gather
    return src


def _assert_window_parity(a, b, tag=""):
    np.testing.assert_array_equal(a.decisions_np, b.decisions_np,
                                  err_msg=f"{tag} decisions")
    np.testing.assert_array_equal(a.revenue_np, b.revenue_np,
                                  err_msg=f"{tag} revenue")
    assert np.array_equal(np.asarray(a.spend), np.asarray(b.spend)), tag
    assert np.array_equal(np.asarray(a.lam_after),
                          np.asarray(b.lam_after)), tag


# ---------------------------------------------------------------------------
# Bitwise parity: chunked replay vs the materialized universe
# ---------------------------------------------------------------------------


def test_replay_parity_bitwise_plain(serving_stack, replay_source):
    """Free-running prices over a 3x spike: the streamed chunk path and
    the materialized row path must agree BITWISE every window."""
    from repro.serving.pipeline import ServingPipeline
    from repro.serving.stream import (TrafficScenario, run_stream,
                                      scenario_windows)

    exp, server, params, rcfg = serving_stack
    src = replay_source
    b = 48
    budget = 0.5 * exp.chains.costs.max() * b
    sizes = scenario_windows(TrafficScenario("spike", 6, b,
                                             spike_mult=3.0))

    def sample(t, n):
        rows = src.arrivals(t, n)
        return exp.ctx_eval[rows], rows

    st_m = run_stream(ServingPipeline(server, params, rcfg, budget),
                      sizes, sample)
    st_s = run_stream(ServingPipeline(src.universe, params, rcfg,
                                      budget), sizes, src)
    for t, (a, b_) in enumerate(zip(st_m.windows, st_s.windows)):
        _assert_window_parity(a, b_, f"w{t}")


def test_replay_parity_bitwise_geotenants(serving_stack, replay_source):
    """The combined tenant x region pass pads in PER-TENANT blocks -
    chunk tables must land in exactly the same slots as global rows."""
    from repro.serving.pipeline import ServingPipeline
    from repro.serving.spec import (ConstraintSpec, GlobalAxis,
                                    RegionAxis, TenantAxis)
    from repro.serving.stream import run_stream

    exp, server, params, rcfg = serving_stack
    src = replay_source
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

    def sample(t, n):
        rows = src.arrivals(t, n)
        return exp.ctx_eval[rows], rows

    st_m = run_stream(
        ServingPipeline.from_spec(server, params, rcfg, spec),
        sizes, sample, budget_trace=bt, scale_trace=st_)
    st_s = run_stream(
        ServingPipeline.from_spec(src.universe, params, rcfg, spec),
        sizes, src, budget_trace=bt, scale_trace=st_)
    for t, (a, b_) in enumerate(zip(st_m.windows, st_s.windows)):
        _assert_window_parity(a, b_, f"geot w{t}")
        np.testing.assert_array_equal(a.regions_np, b_.regions_np)
        np.testing.assert_array_equal(np.asarray(a.tr_spend),
                                      np.asarray(b_.tr_spend))


def test_memmap_roundtrip_parity(serving_stack, replay_source, tmp_path):
    """save -> load(mmap=True) replays identical windows from disk."""
    from repro.data.request_source import TableReplaySource

    exp, _, _, _ = serving_stack
    src = replay_source
    src.save(str(tmp_path / "universe"))
    disk = TableReplaySource.load(str(tmp_path / "universe"),
                                  exp.chains, seed=7)
    assert disk.n_users == src.n_users
    a, b = src.window(3, 40), disk.window(3, 40)
    np.testing.assert_array_equal(a.users, b.users)
    np.testing.assert_array_equal(a.ctx, b.ctx)
    np.testing.assert_array_equal(a.tables["p"], b.tables["p"])
    np.testing.assert_array_equal(a.tables["ck"], b.tables["ck"])


@pytest.mark.parametrize("g,cap,width", [(16, 200, 3200), (16, 150, 2432),
                                         (8, 40, 384), (1, 128, 128)])
def test_replay_width_rounds_up_to_lanes(g, cap, width):
    from repro.data.request_source import replay_width

    assert replay_width(g, cap) == width


def test_device_tables_with_pad_columns_bitwise(serving_stack):
    """A universe whose G·cap is not a multiple of 128: the user-major
    device tables carry zero pad columns, and every window's chunk is
    bitwise the host path's rows, repeated users and the edge ids 0 and
    U-1 included."""
    from repro.cascade.engine import build_compact_layout
    from repro.data.request_source import TableReplaySource, replay_width

    exp, _, _, _ = serving_stack
    n_items, expose = exp.cfg.world.n_items, exp.cfg.expose
    lay = build_compact_layout(exp.chains, n_items=n_items, expose=expose)
    g, cap = lay.p_sorted.shape[0], lay.cap
    assert (g * cap) % 128  # the pad engages
    u_n = 517
    rng = np.random.default_rng(11)
    ctx = rng.normal(size=(u_n, 5)).astype(np.float32)
    p = rng.integers(0, cap, (g, u_n, cap)).astype(np.int32)
    ck = rng.random((g, u_n, cap)).astype(np.float32)
    kw = dict(n_items=n_items, expose=expose, seed=3)
    dev = TableReplaySource(ctx, p, ck, exp.chains, device_tables=True,
                            **kw)
    host = TableReplaySource(ctx, p, ck, exp.chains, device_tables=False,
                             **kw)
    windows = [dev.arrivals(0, 64), np.array([0, u_n - 1, 0, u_n - 1, 5, 5]),
               np.array([u_n - 1]), rng.integers(0, u_n, 200)]
    for t, users in enumerate(windows):
        a, b = dev.window_for_users(users), host.window_for_users(users)
        np.testing.assert_array_equal(a.ctx, b.ctx)
        for key, table in (("p", p), ("ck", ck)):
            got = np.asarray(a.tables[key])
            assert got.dtype == table.dtype, (t, key)
            np.testing.assert_array_equal(got, b.tables[key],
                                          err_msg=f"w{t} {key}")
            np.testing.assert_array_equal(got, table[:, users])
    for d, table in zip(dev._dev, (p, ck)):
        rows = np.asarray(d)
        assert rows.shape == (u_n, replay_width(g, cap))
        assert not rows[:, g * cap:].any()
        np.testing.assert_array_equal(
            rows[:, :g * cap],
            table.transpose(1, 0, 2).reshape(u_n, g * cap))


# ---------------------------------------------------------------------------
# GeneratedSource: determinism, chunk boundaries, streaming world
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def generated_source(serving_stack):
    from dataclasses import replace

    from repro.data.request_source import GeneratedSource
    from repro.data.synthetic import StreamingWorld

    exp, _, _, _ = serving_stack
    wcfg = replace(exp.cfg.world, n_users=50_000)
    return GeneratedSource(StreamingWorld.build(wcfg), exp.models,
                           exp.chains, expose=exp.cfg.expose, seed=3,
                           chunk=64, item_block=128)


def test_generated_deterministic_under_seed(serving_stack,
                                            generated_source):
    """Window t is a pure function of (seed, t): a second source with a
    DIFFERENT host chunking replays it exactly; a different seed does
    not."""
    from dataclasses import replace

    from repro.data.request_source import GeneratedSource
    from repro.data.synthetic import StreamingWorld

    exp, _, _, _ = serving_stack
    wcfg = replace(exp.cfg.world, n_users=50_000)
    other = GeneratedSource(StreamingWorld.build(wcfg), exp.models,
                            exp.chains, expose=exp.cfg.expose, seed=3,
                            chunk=17, item_block=64)
    # 100 requests: chunk 64 splits 64+36, chunk 17 splits 17*5+15 -
    # both off the chunk boundary, plus one exact-boundary window below
    a, b = generated_source.window(4, 100), other.window(4, 100)
    np.testing.assert_array_equal(a.users, b.users)
    np.testing.assert_array_equal(a.ctx, b.ctx)
    np.testing.assert_array_equal(a.tables["p"], b.tables["p"])
    np.testing.assert_array_equal(a.tables["ck"], b.tables["ck"])
    a, b = generated_source.window(5, 64), other.window(5, 64)
    np.testing.assert_array_equal(a.ctx, b.ctx)
    np.testing.assert_array_equal(a.tables["p"], b.tables["p"])

    reseeded = GeneratedSource(StreamingWorld.build(wcfg), exp.models,
                               exp.chains, expose=exp.cfg.expose,
                               seed=4, chunk=64, item_block=128)
    c = reseeded.window(4, 100)
    assert not np.array_equal(a.users[:64], c.users[:64]) or \
        not np.array_equal(generated_source.window(4, 100).ctx, c.ctx)


def test_generated_zero_and_single_request_windows(generated_source):
    z = generated_source.window(9, 0)
    assert z.n == 0 and z.ctx.shape[0] == 0
    assert z.tables["p"].shape[1] == 0
    one = generated_source.window(9, 1)
    assert one.n == 1 and one.tables["p"].shape[1] == 1


def test_streaming_world_repeat_visitors_consistent(serving_stack):
    """Hash-keyed users: the same global id materializes the SAME row
    (history, fields, clicks) in any slab it appears in."""
    from dataclasses import replace

    from repro.data.synthetic import StreamingWorld

    exp, _, _, _ = serving_stack
    w = StreamingWorld.build(replace(exp.cfg.world, n_users=1_000_000))
    ids_a = np.array([5, 999_999, 123_456, 5])
    ids_b = np.array([123_456, 5])
    sa, sb = w.user_slab(ids_a), w.user_slab(ids_b)
    np.testing.assert_array_equal(sa.hist_ids[2], sb.hist_ids[0])
    np.testing.assert_array_equal(sa.user_fields[0], sb.user_fields[1])
    np.testing.assert_array_equal(sa.hist_ids[0], sa.hist_ids[3])
    ca, cb = w.clicks_slab(ids_a, sa), w.clicks_slab(ids_b, sb)
    np.testing.assert_array_equal(ca[0], cb[1])
    np.testing.assert_array_equal(ca[2], cb[0])


def test_generated_stream_end_to_end(serving_stack, generated_source):
    """A generated swing stream serves through the fused pipeline with
    zero steady-state recompiles and positive revenue."""
    from repro.serving.pipeline import ServingPipeline
    from repro.serving.stream import run_stream

    exp, _, params, rcfg = serving_stack
    budget = 0.5 * exp.chains.costs.max() * 32
    pipe = ServingPipeline(generated_source.universe, params, rcfg,
                           budget, bucketing="pow2")
    sizes = [32, 320, 32, 320, 32]
    st = run_stream(pipe, sizes, generated_source)
    assert st.steady_compiles == 0
    assert st.compiles[2] == st.compiles[3] == st.compiles[4] == 0
    assert st.total_revenue > 0


# ---------------------------------------------------------------------------
# Recompile instrumentation + bucketing
# ---------------------------------------------------------------------------


def test_zero_steady_state_recompiles_10x_spike(serving_stack,
                                                replay_source):
    """10x spike, pow2 buckets: every (shape, padded) pair compiles on
    first sight only - repeated buckets report compiles == 0."""
    from repro.serving.pipeline import ServingPipeline
    from repro.serving.stream import run_stream

    exp, _, params, rcfg = serving_stack
    src = replay_source
    b = 32
    budget = 0.5 * exp.chains.costs.max() * b
    pipe = ServingPipeline(src.universe, params, rcfg, budget,
                           bucketing="pow2")
    sizes = [b, b, 10 * b, 10 * b, b, 10 * b, b]
    st = run_stream(pipe, sizes, src)
    assert st.steady_compiles == 0
    seen = set()
    for r in st.windows:
        if r.bucket in seen:
            assert r.compiles == 0, r.bucket
        else:
            assert r.compiles > 0, "first sight of a bucket compiles"
        seen.add(r.bucket)
    assert len(seen) == 2  # 32 -> one bucket, 320 -> one pow2 bucket


def test_pow2_bucketing_bounds_shape_count(serving_stack):
    from repro.serving.pipeline import ServingPipeline

    exp, server, params, rcfg = serving_stack
    pipe_lin = ServingPipeline(server, params, rcfg, 100.0)
    pipe_p2 = ServingPipeline(server, params, rcfg, 100.0,
                              bucketing="pow2")
    lin = {pipe_lin._bucket(n) for n in range(1, 3201)}
    p2 = {pipe_p2._bucket(n) for n in range(1, 3201)}
    assert len(p2) <= 8 and len(lin) == 100  # log vs linear in traffic
    for n in (1, 31, 32, 33, 64, 65, 1000, 3200):
        assert pipe_p2._bucket(n) >= n
    with pytest.raises(ValueError):
        ServingPipeline(server, params, rcfg, 100.0, bucketing="huh")


def test_stream_only_pipeline_requires_chunk_tables(serving_stack,
                                                    replay_source):
    from repro.serving.pipeline import ServingPipeline

    exp, _, params, rcfg = serving_stack
    pipe = ServingPipeline(replay_source.universe, params, rcfg, 100.0)
    c = replay_source.window(0, 8)
    with pytest.raises(ValueError, match="streaming universe"):
        pipe.serve_window(c.ctx, c.rows)
    res = pipe.serve_window(c.ctx, c.rows, tables=c.tables)
    assert res.n_valid == 8


# ---------------------------------------------------------------------------
# Named per-axis budget dicts (PR 5 leftover)
# ---------------------------------------------------------------------------


def test_budget_and_scale_names(serving_stack):
    from repro.serving.spec import (ConstraintSpec, GlobalAxis,
                                    RegionAxis, TenantAxis)

    plain = ConstraintSpec([GlobalAxis(budget=9.0)]).compile()
    assert plain.budget_names == ("global",)
    assert plain.scale_names == ("global",)
    ten = ConstraintSpec([TenantAxis((4.0, 5.0))]).compile()
    assert ten.budget_names == ("tenant[0]", "tenant[1]")
    assert ten.k_names == ()  # shared price: budgets outnumber prices
    geot = ConstraintSpec([
        TenantAxis((4.0, 5.0), priced=True),
        RegionAxis(2, names=("eu", "us")),
        GlobalAxis(pricing="carbon"),
    ]).compile()
    assert geot.budget_names == ("tenant[0]", "tenant[1]", "eu", "us")
    assert geot.scale_names == ("eu", "us")
    assert geot.budget_names == geot.k_names  # fully priced: equal


def test_named_budget_dict_bitwise_vs_vector(serving_stack,
                                             replay_source):
    """The named-dict budget/cost_scale form is a naming shim: same
    vectors, same bits."""
    from repro.serving.pipeline import ServingPipeline
    from repro.serving.spec import (ConstraintSpec, GlobalAxis,
                                    RegionAxis, TenantAxis)

    exp, server, params, rcfg = serving_stack
    src = replay_source
    per_req = 0.5 * float(exp.chains.costs.max())
    n = 48
    rows = src.arrivals(0, n)
    ctx = exp.ctx_eval[rows]
    spec = ConstraintSpec([
        TenantAxis((per_req * 24, per_req * 24), priced=True),
        RegionAxis(2, names=("eu", "us")),
        GlobalAxis(pricing="carbon"),
    ])
    vec_b = np.array([per_req * 24, per_req * 30, per_req * 29,
                      per_req * 28], np.float32)
    vec_s = np.array([1.0, 1.3], np.float32)
    p1 = ServingPipeline.from_spec(server, params, rcfg, spec)
    r1 = p1.serve_window(ctx, rows, budget=vec_b, cost_scale=vec_s)
    p2 = ServingPipeline.from_spec(server, params, rcfg, spec)
    r2 = p2.serve_window(ctx, rows, budget={
        "tenant[0]": vec_b[0], "tenant[1]": vec_b[1],
        "eu": vec_b[2], "us": vec_b[3]},
        cost_scale={"eu": 1.0, "us": 1.3})
    _assert_window_parity(r1, r2, "named-vs-vector")
    with pytest.raises(ValueError, match="missing"):
        p2.serve_window(ctx, rows, budget={"eu": 1.0},
                        cost_scale={"eu": 1.0, "us": 1.3})
    with pytest.raises(ValueError, match="unknown"):
        p2.serve_window(ctx, rows, budget={
            "tenant[0]": 1, "tenant[1]": 1, "eu": 1, "us": 1,
            "mars": 1}, cost_scale=vec_s)


def test_named_scalar_budget_plain_mode(serving_stack, replay_source):
    from repro.serving.pipeline import ServingPipeline

    exp, server, params, rcfg = serving_stack
    src = replay_source
    n = 32
    rows = src.arrivals(1, n)
    ctx = exp.ctx_eval[rows]
    budget = 0.5 * float(exp.chains.costs.max()) * n
    r1 = ServingPipeline(server, params, rcfg, budget).serve_window(
        ctx, rows, budget=budget * 0.7)
    r2 = ServingPipeline(server, params, rcfg, budget).serve_window(
        ctx, rows, budget={"global": budget * 0.7})
    _assert_window_parity(r1, r2, "plain-named")


# ---------------------------------------------------------------------------
# Chunked offline scoring
# ---------------------------------------------------------------------------


def test_reward_matrix_chunked_matches_full(serving_stack):
    """One-chunk inputs are bitwise the direct call; multi-chunk splits
    agree per row up to float ulps (XLA re-blocks matmuls per batch
    shape - the decision-relevant scale here is ~1.0)."""
    from repro.core.reward_model import (reward_matrix,
                                         reward_matrix_chunked)

    exp, _, params, rcfg = serving_stack
    mo = jnp.asarray(exp.chains.model_onehot)
    sh = jnp.asarray(exp.chains.scale_multihot)
    ctx = exp.ctx_eval[:150]
    full = np.asarray(reward_matrix(params, rcfg, jnp.asarray(
        ctx, jnp.float32), mo, sh))
    np.testing.assert_array_equal(
        full, reward_matrix_chunked(params, rcfg, ctx, mo, sh,
                                    chunk=4096))
    for chunk in (64, 75):  # ragged and exact splits
        part = reward_matrix_chunked(params, rcfg, ctx, mo, sh,
                                     chunk=chunk)
        np.testing.assert_allclose(full, part, rtol=3e-6, atol=1e-6,
                                   err_msg=str(chunk))
        # chunk-boundary rows are not special: the LAST padded chunk
        # agrees with the first-chunk rows of an offset call
        assert part.shape == full.shape
