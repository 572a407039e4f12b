"""Fused serving runtime: guard invariants, pipeline parity, spike runs.

Covers the ISSUE acceptance gates:
  * guard property tests - spend <= budget whenever n*c_min <= budget,
    fused (jax) decisions bit-for-bit equal to the legacy (NumPy) path,
    padding invariance, and the fixed `downgraded` counter semantics;
  * ServingPipeline produces the same decisions and revenue as the
    legacy allocate_window-style loop + CascadeServer.serve on the
    system-test config, exact chain-index equality given the same
    lambda trace;
  * a 12-window spike serve run never overshoots max(budget, n*c_min);
  * request-axis shard_map parity in a subprocess with 8 host devices.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving.guard import downgrade_guard, downgrade_guard_np

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


# ---------------------------------------------------------------------------
# Guard properties (property-style: seeded sweep over random instances)
# ---------------------------------------------------------------------------


def _random_guard_case(rng):
    j = int(rng.integers(2, 16))
    n = int(rng.integers(1, 256))
    costs = rng.uniform(1.0, 10.0, j).astype(np.float32)
    dec = rng.integers(0, j, n).astype(np.int32)
    budget = float(rng.uniform(0.2, 1.2) * np.sum(costs[dec]))
    return costs, dec, budget, int(np.argmin(costs))


def test_guard_spend_within_budget_property():
    rng = np.random.default_rng(0)
    for _ in range(100):
        costs, dec, budget, cheap = _random_guard_case(rng)
        c_min = float(costs[cheap])
        _, _, spend = downgrade_guard_np(dec, costs, budget, cheap)
        cap = budget if len(dec) * c_min <= budget else len(dec) * c_min
        # float32 cost accumulation rounds ~n*eps relative
        assert spend <= cap * (1 + 1e-6 + 1.2e-7 * len(dec))


def test_guard_fused_matches_legacy_bit_for_bit():
    rng = np.random.default_rng(1)
    for _ in range(100):
        costs, dec, budget, cheap = _random_guard_case(rng)
        d_np, k_np, s_np = downgrade_guard_np(dec, costs, budget, cheap)
        d_j, k_j, s_j = downgrade_guard(jnp.asarray(dec),
                                        jnp.asarray(costs), budget, cheap)
        np.testing.assert_array_equal(d_np, np.asarray(d_j))
        assert k_np == int(k_j)
        np.testing.assert_allclose(s_np, float(s_j), rtol=1e-5)


def test_guard_padding_invariance():
    """Padded (masked) windows decide exactly like unpadded ones."""
    rng = np.random.default_rng(2)
    for _ in range(40):
        costs, dec, budget, cheap = _random_guard_case(rng)
        pad = int(rng.integers(1, 64))
        d0, k0, s0 = downgrade_guard(jnp.asarray(dec), jnp.asarray(costs),
                                     budget, cheap)
        dec_p = np.concatenate(
            [dec, rng.integers(0, len(costs), pad).astype(np.int32)])
        valid = np.concatenate([np.ones(len(dec), np.float32),
                                np.zeros(pad, np.float32)])
        d1, k1, s1 = downgrade_guard(jnp.asarray(dec_p), jnp.asarray(costs),
                                     budget, cheap, jnp.asarray(valid))
        np.testing.assert_array_equal(np.asarray(d0),
                                      np.asarray(d1)[: len(dec)])
        assert int(k0) == int(k1)
        np.testing.assert_allclose(float(s0), float(s1), rtol=1e-5)


def test_guard_downgraded_counts_unique_changed_requests():
    """The seed overwrote the counter each pass and counted already-cheap
    requests; the fixed semantics count requests whose FINAL decision
    differs from the allocator's."""
    costs = np.asarray([1.0, 100.0])
    # below the floor: every request gets flagged every pass, but the two
    # already-cheap requests were never actually downgraded
    dec = np.asarray([1, 0, 1, 0, 1], np.int32)
    d, k, s = downgrade_guard_np(dec, costs, 2.0, 0)
    assert list(d) == [0, 0, 0, 0, 0]
    assert k == 3  # not 5 (the flagged count), not a last-pass overwrite
    d_j, k_j, _ = downgrade_guard(jnp.asarray(dec),
                                  jnp.asarray(costs, jnp.float32), 2.0, 0)
    assert int(k_j) == 3


def test_guard_extra_passes_are_noops():
    """Decisions converge in one pass; the fixed-pass fused guard and a
    single-pass guard agree (the legacy loop's early-break equivalence)."""
    rng = np.random.default_rng(3)
    for _ in range(30):
        costs, dec, budget, cheap = _random_guard_case(rng)
        d1, _, _ = downgrade_guard(jnp.asarray(dec), jnp.asarray(costs),
                                   budget, cheap, passes=1)
        d4, _, _ = downgrade_guard(jnp.asarray(dec), jnp.asarray(costs),
                                   budget, cheap, passes=4)
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d4))


def test_guard_per_tenant_vmap_respects_each_budget():
    rng = np.random.default_rng(4)
    costs = jnp.asarray(rng.uniform(1.0, 10.0, 8), jnp.float32)
    cheap = int(jnp.argmin(costs))
    dec = jnp.asarray(rng.integers(0, 8, (3, 64)), jnp.int32)
    budgets = jnp.asarray([100.0, 250.0, 400.0], jnp.float32)
    valid = jnp.ones((3, 64), jnp.float32)
    gfn = jax.vmap(lambda d, v, b: downgrade_guard(d, costs, b, cheap, v))
    _, _, spends = gfn(dec, valid, budgets)
    floor = 64 * float(costs[cheap])
    for t in range(3):
        cap = max(float(budgets[t]), floor)
        assert float(spends[t]) <= cap * (1 + 1e-5)


# ---------------------------------------------------------------------------
# Grouped reward scoring == per-chain scoring
# ---------------------------------------------------------------------------


def test_reward_matrix_grouped_matches_full(system_exp):
    from repro.core.reward_model import (RewardModelConfig,
                                         chain_prefix_plan,
                                         reward_matrix,
                                         reward_matrix_grouped,
                                         reward_model_init)

    chains = system_exp.chains
    ctx = jnp.asarray(system_exp.ctx_eval[:32], jnp.float32)
    mo = jnp.asarray(chains.model_onehot)
    sh = jnp.asarray(chains.scale_multihot)
    plan = chain_prefix_plan(chains.chain_idx[:, :, 0])
    for recursive in (True, False):
        for multi_basis in (True, False):
            cfg = RewardModelConfig(
                n_stages=chains.n_stages, max_models=2, n_scale_groups=4,
                d_context=ctx.shape[1], d_feature=32, d_hidden=32,
                d_state=16, recursive=recursive, multi_basis=multi_basis)
            params = reward_model_init(jax.random.PRNGKey(7), cfg)
            full = reward_matrix(params, cfg, ctx, mo, sh)
            grouped = reward_matrix_grouped(params, cfg, ctx, sh, plan)
            # different matmul batch shapes: equal up to f32 rounding
            np.testing.assert_allclose(np.asarray(grouped),
                                       np.asarray(full), rtol=1e-6,
                                       atol=0)


# ---------------------------------------------------------------------------
# Fused pipeline vs the legacy loop (system-test config)
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


def test_pipeline_matches_legacy_loop_exactly(serving_stack):
    """Exact chain-index + revenue equality given the same lambda trace,
    across constant and 3x spike windows (the acceptance criterion)."""
    from repro.core.budget import BudgetController
    from repro.core.reward_model import denormalize_rewards, reward_matrix
    from repro.serving.pipeline import ServingPipeline

    exp, server, params, rcfg = serving_stack
    chains = exp.chains
    b = 64
    budget = 0.6 * chains.costs.max() * b
    mo = jnp.asarray(chains.model_onehot)
    sh = jnp.asarray(chains.scale_multihot)
    score = jax.jit(lambda p, c: denormalize_rewards(
        p, reward_matrix(p, rcfg, c, mo, sh)))
    ctl = BudgetController(chains, budget)
    pipe = ServingPipeline(server, params, rcfg, budget)
    rng = np.random.default_rng(0)
    n_eval = exp.ctx_eval.shape[0]
    lam_trace = []
    for t in range(6):
        n_t = b * (3 if t in (2, 3) else 1)
        rows = rng.integers(0, n_eval, n_t)
        ctx = exp.ctx_eval[rows]
        lam_before = float(ctl.pd.lam)
        lam_trace.append(lam_before)
        rewards = np.asarray(score(params, jnp.asarray(ctx, jnp.float32)))
        dec_legacy = ctl.step_window(rewards)
        rev_legacy, flops_legacy = server.serve(rows, dec_legacy)
        res = pipe.serve_window(ctx, rows, lam=lam_before)
        np.testing.assert_array_equal(dec_legacy, res.decisions_np)
        np.testing.assert_array_equal(rev_legacy, res.revenue_np)
        assert int(res.downgraded) == ctl.stats[-1].downgraded
        np.testing.assert_allclose(float(res.spend), ctl.stats[-1].spend,
                                   rtol=1e-6)
        # free-running price agrees too (same rewards, same Algorithm 1)
        np.testing.assert_allclose(float(res.lam_after),
                                   ctl.stats[-1].lam, rtol=1e-5,
                                   atol=1e-12)


def test_pipeline_spike_run_never_overshoots(serving_stack):
    """12-window free-running serve with a 3x spike: every window's spend
    stays under max(budget, n*c_min)."""
    from repro.serving.pipeline import ServingPipeline
    from repro.serving.stream import (TrafficScenario, run_stream,
                                      scenario_windows)

    exp, server, params, rcfg = serving_stack
    chains = exp.chains
    b = 48
    budget = 0.5 * chains.costs.max() * b
    pipe = ServingPipeline(server, params, rcfg, budget)
    sc = TrafficScenario("spike", 12, b, spike_mult=3.0)
    rng = np.random.default_rng(1)
    n_eval = exp.ctx_eval.shape[0]

    def sample(t, n):
        rows = rng.integers(0, n_eval, n)
        return exp.ctx_eval[rows], rows

    st = run_stream(pipe, scenario_windows(sc), sample)
    assert len(st.windows) == 12
    assert st.overshoot(float(chains.costs.min())) <= 1e-5
    spike_windows = [r for r in st.windows if r.n_valid > b]
    assert spike_windows and any(int(r.downgraded) > 0
                                 for r in spike_windows)


def test_pipeline_tenant_budgets_shared_price(serving_stack):
    exp, server, params, rcfg = serving_stack
    from repro.serving.pipeline import ServingPipeline

    chains = exp.chains
    b = 64
    budget = 0.5 * chains.costs.max() * b
    tb = np.full(4, budget / 4, np.float32)
    pipe = ServingPipeline(server, params, rcfg, budget, tenant_budgets=tb)
    rng = np.random.default_rng(2)
    rows = rng.integers(0, exp.ctx_eval.shape[0], b)
    res = pipe.serve_window(exp.ctx_eval[rows], rows)
    # 16-request tenant blocks pad to the 32-wide bucket: the mask-aware
    # trim must still return exactly the real requests
    assert len(res.decisions_np) == b and len(res.revenue_np) == b
    floor = (b // 4) * float(chains.costs.min())
    assert res.tenant_spend is not None
    for t in range(4):
        cap = max(budget / 4, floor)
        assert float(res.tenant_spend[t]) <= cap * (1 + 1e-5)


# ---------------------------------------------------------------------------
# Request-axis sharding: subprocess with 8 fake host devices
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_pipeline_sharded_matches_unsharded():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.cascade.engine import CascadeServer
    from repro.core.action_chain import (ModelInstance, StageSpec,
                                         generate_action_chains)
    from repro.core.reward_model import (RewardModelConfig, chain_label_norm,
                                         reward_model_init)
    from repro.launch.mesh import make_request_mesh
    from repro.serving.pipeline import ServingPipeline

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
    rcfg = RewardModelConfig(n_stages=3, max_models=2, n_scale_groups=4,
                             d_context=12, d_feature=16, d_hidden=16,
                             d_state=8)
    params = dict(reward_model_init(jax.random.PRNGKey(0), rcfg))
    params["label_norm"] = jnp.asarray(
        np.linspace(1.0, 3.0, chains.n_chains).astype(np.float32))
    budget = 0.5 * float(chains.costs.max()) * 64
    mesh = make_request_mesh(8)
    pipe_s = ServingPipeline(server, params, rcfg, budget, mesh=mesh)
    pipe_u = ServingPipeline(server, params, rcfg, budget)
    rng2 = np.random.default_rng(1)
    for t, n in enumerate([64, 192, 50, 64]):  # incl. padded windows
        rows = rng2.integers(0, u, n)
        ctx = rng2.normal(size=(n, 12)).astype(np.float32)
        rs = pipe_s.serve_window(ctx, rows)
        ru = pipe_u.serve_window(ctx, rows)
        assert np.array_equal(rs.decisions_np, ru.decisions_np), t
        assert np.array_equal(rs.revenue_np, ru.revenue_np), t
        assert int(rs.downgraded) == int(ru.downgraded), t
        # per-option counts make the dual price shard-count invariant
        assert float(rs.lam_after) == float(ru.lam_after), t
    print("SHARDED SERVING PARITY OK")
    """)], capture_output=True, text=True, timeout=420, env=env)
    assert out.returncode == 0, f"stdout:{out.stdout}\nstderr:{out.stderr}"
    assert "SHARDED SERVING PARITY OK" in out.stdout
