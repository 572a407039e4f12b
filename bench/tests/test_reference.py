"""The plain reference agrees with the program on the CPU at a small
size, where both compute float32 exactly: chains, the reward model and
the nearline price update."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import build
from bench.reference import alloc, models
from bench.reference import chains as ref_chains
from bench.sources import replay
from bench.tests import tiny


@pytest.fixture(scope="module")
def setup():
    jax.config.update("jax_default_matmul_precision", "highest")
    cfg = tiny.config("greenflow-geotenants")
    ctx, _, _ = replay.replay_tables(cfg, build.chain_set(cfg), 64, 5)
    yield cfg, ctx
    jax.config.update("jax_default_matmul_precision", None)


def test_chains_match_the_programs_chain_set(setup):
    cfg = setup[0]
    prog, ref = build.chain_set(cfg), ref_chains.chains(cfg)
    assert np.array_equal(prog.costs, ref.costs)
    assert np.array_equal(prog.scale_multihot, ref.multihot)
    assert np.array_equal(prog.model_onehot, ref.onehot)
    assert np.array_equal(prog.scale_value[:, 1], ref.n2)
    assert np.array_equal(prog.scale_value[:, 2], ref.n3)
    assert prog.cheapest() == ref.cheapest


def _rewards(cfg, ctx, seed=7):
    from repro.core.reward_model import (chain_prefix_plan,
                                         denormalize_rewards,
                                         reward_matrix_grouped)

    ch, rc = build.chain_set(cfg), ref_chains.chains(cfg)
    params, rcfg = build.reward_model(cfg, ch, build.d_context(cfg), seed)
    got = denormalize_rewards(params, reward_matrix_grouped(
        params, rcfg, jnp.asarray(ctx), jnp.asarray(ch.scale_multihot),
        chain_prefix_plan(ch.chain_idx[:, :, 0])))
    want = models.reward(models.Precision(), params, jnp.asarray(ctx),
                         jnp.asarray(rc.onehot), jnp.asarray(rc.multihot))
    return got, want


def test_reward_model_matches(setup):
    got, want = _rewards(*setup)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("lam0", [[0.0, 0.0, 0.0, 0.0],
                                  [3e6, 2e5, 1e5, 0.0]])
def test_nearline_update_matches_dual_descent(setup, lam0):
    """Algorithm 1 in the reference against the program's own
    ``dual_descent`` over the spec's cost map and membership."""
    from repro.core.primal_dual import dual_descent
    from repro.serving.spec import ConstraintSpec, RegionAxis, TenantAxis

    cfg, ctx = setup
    rc = ref_chains.chains(cfg)
    r = np.asarray(_rewards(cfg, ctx)[1])
    n, t_n, r_n = len(r), 2, 2
    sc = np.array([2.0e-10, 2.6e-10])
    opt = (sc[:, None] * rc.costs[None]).reshape(-1)
    bud = np.array([0.2, 0.8, 0.6, 0.6]) * 0.6 * rc.costs.max() * n * 2.3e-10
    ten = np.repeat(np.arange(t_n), n // t_n)
    cs = ConstraintSpec([TenantAxis(tuple(bud[:t_n]), priced=True),
                         RegionAxis(r_n)]).compile()
    member = cs.dual_member(jnp.asarray(ten), n)
    got, _ = dual_descent(
        jnp.tile(jnp.asarray(r, jnp.float32), (1, r_n)),
        cs.dual_cost_map(jnp.asarray(opt, jnp.float32), rc.n),
        jnp.asarray(bud, jnp.float32), jnp.asarray(lam0, jnp.float32),
        member=member, max_iters=300, step_size=1.0, step_decay=0.98)
    want = alloc.dual_update(
        jnp.asarray(r, jnp.float32), jnp.asarray(ten, jnp.int32),
        jnp.asarray(sc, jnp.float32), jnp.asarray(rc.costs, jnp.float32),
        jnp.asarray(bud, jnp.float32), jnp.asarray(lam0, jnp.float32),
        jnp.ones(n), t_n=t_n, iters=300, step=1.0, decay=0.98)
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-6 * float(np.max(np.abs(want))))
    assert float(np.max(want)) > 0


def test_control_precision_rounds_operands():
    x = jnp.asarray([1.0, 1.0625, 3.3], jnp.float32)
    got = models.Precision(control=True).q(x)
    assert float(got[1]) == 1.0  # 1 + 2^-4 is below e4m3's spacing at 1
    assert float(got[2]) != 3.3
    assert models.Precision().q(x) is x
