"""Vectorized multi-price allocator core (paper §4.3, Algorithm 1).

This module is the ONE implementation of GreenFlow's Eq. 10 / Algorithm 1
machinery; everything that prices computation builds on it:

  * the fused serving pass  - ``serving.pipeline.ServingPipeline`` calls
    ``allocate``/``dual_descent`` inside its jitted window pass (scalar
    price, per-tenant prices, or per-region geo prices);
  * the host loops          - ``core.budget.BudgetController`` and
    ``carbon.controller.CarbonBudgetController`` are thin wrappers over
    ``window_step`` (decide -> guard -> dual) with FLOPs- or
    carbon-denominated costs;
  * the downgrade guard     - ``serving.guard.downgrade_guard`` shares
    the same scalar/vector duality (per-constraint budgets via ``k_of``).

The assignment LP (Eq. 3) couples requests through budget constraints.
With ONE global budget the Lagrangian dual is a scalar price lambda and
the inner max decomposes per request:

    x_ij = 1  iff  j = argmax_j (R_ij - lambda * c_j)          (Eq. 10)

The general form prices K >= 1 constraints at once - K ranges over
tenant x region in the serving system, but the core is agnostic:

    x_im = 1  iff  m = argmax_m (R_im - sum_k lam_k * A_imk)

where m indexes OPTIONS (chains, or chains x serving regions) and the
consumption tensor factors as  A_imk = member_ik * C_mk  with

    C      (M, K)  cost map: what option m draws from constraint k
                   (e.g. c_{j,r}(t) = flops_j * kappa * CI_r(t) in
                   column r for geo options, zero elsewhere);
    member (I, K)  which constraints request i is subject to (tenant
                   one-hot; None = every request subject to all K).

Every function below accepts BOTH forms and the scalar form is the K=1
special case executing the identical floating-point operations - the
bit-parity gate in tests/test_multi_price.py:

  * scalar: ``lam`` a scalar, ``costs`` (M,);
  * vector: ``lam`` (K,), ``costs`` (M, K) (an (M, 1) column broadcasts
    across K when ``member`` carries the constraint structure).

We provide:
  * ``allocate``      - Eq. 10 decisions for a batch of requests.
  * ``consumption``   - per-constraint spend at a given price (psum-able
    across a request mesh axis for the sharded fused pipeline).
  * ``dual_descent``  - Algorithm 1 as a lax.scan (jit-able, runs the
    whole nearline window on-device); K prices descend jointly on the
    per-constraint subgradients.
  * ``dual_bisect``   - an exact scalar oracle (single constraint =>
    consumption is a step function, non-increasing in lambda); used for
    tests, benchmarks and warm-starts.
  * ``window_step``   - the host-loop window body (decide -> NumPy guard
    -> dual update) shared by the budget controllers.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from repro.distributed.sharding import ordered_psum


def _as_cost_map(costs: jnp.ndarray) -> jnp.ndarray:
    """(M,) or (M, K) costs -> (M, K) cost map."""
    return costs if costs.ndim == 2 else costs[:, None]


def _option_prices(costs, lam, member):
    """The lagrangian price term, broadcast to (I, M) or (M,).

    Scalar lam: lam * costs (the original Eq. 10 term).  Vector lam:
    sum_k lam_k * member_ik * C_mk - an (I, K) @ (K, M) matmul when
    member is given, else the (M,) column combination C @ lam.  Both
    run at full f32 precision: at default precision a TPU rounds the
    priced costs to bf16, about three significant digits.
    """
    if jnp.ndim(lam) == 0:
        return lam * costs
    cm = _as_cost_map(costs)
    if member is None:
        if cm.shape[1] != lam.shape[0]:  # an (M, 1) column only spans K
            raise ValueError(             # constraints through member
                f"cost map with {cm.shape[1]} columns cannot be priced "
                f"by {lam.shape[0]} duals without a member matrix")
        return jnp.matmul(cm, lam, precision=jax.lax.Precision.HIGHEST)
    return jnp.matmul(member, (cm * lam[None, :]).T,
                      precision=jax.lax.Precision.HIGHEST)


@jax.jit
def allocate(rewards: jnp.ndarray, costs: jnp.ndarray,
             lam: jnp.ndarray, member: jnp.ndarray | None = None
             ) -> jnp.ndarray:
    """Eq. 10: per-request argmax of the lagrangian score.

    rewards: (I, M); costs: (M,) with scalar ``lam`` (the K=1 path,
    bit-identical to the historical scalar implementation), or (M, K)
    with ``lam`` (K,) and optional ``member`` (I, K).  Returns (I,)
    int32 option index.
    """
    if jnp.ndim(lam) == 0:
        score = rewards - lam * costs[None, :]
    else:
        price = _option_prices(costs, lam, member)
        score = rewards - (price if price.ndim == 2 else price[None, :])
    return jnp.argmax(score, axis=1).astype(jnp.int32)


def consumption(rewards: jnp.ndarray, costs: jnp.ndarray,
                lam: jnp.ndarray, mask: jnp.ndarray | None = None,
                *, member: jnp.ndarray | None = None,
                axis_name: str | None = None) -> jnp.ndarray:
    """Spend per constraint if ``lam`` is the dual price.

    Scalar ``lam``: the total (scalar) spend - unchanged semantics.
    Vector ``lam``: (K,) per-constraint spend sum_i member_ik *
    C[m*_i, k].  mask (I,) zeroes padded requests; axis_name sums across
    a request mesh axis (shard_map), so the padded + sharded fused
    pipeline sees the same window-global consumption as the host loop.

    The spend is formed from per-option request COUNTS.  A count is a
    sum of 0/1 terms, exact in float32 in any order, so the counts are
    the same however the requests are split over shards or padded;
    only the final cost-weighted (M,) sum rounds, and it runs on those
    same counts everywhere.  Given the same rewards, the dual price is
    then the same for any shard count of the request mesh, bit for bit.
    """
    j_star = allocate(rewards, costs, lam, member)
    m_n = costs.shape[0]
    hit = (j_star[:, None] == jnp.arange(m_n)[None, :]).astype(jnp.float32)
    if mask is not None:
        hit = hit * mask[:, None]

    def counts(h):
        cnt = jnp.sum(h, axis=0)  # (M,) requests per option
        return cnt if axis_name is None else ordered_psum(cnt, axis_name)

    if jnp.ndim(lam) == 0:
        return jnp.sum(counts(hit) * costs)
    cm = _as_cost_map(costs)
    cnt = None if member is not None else counts(hit)
    # one (M,) reduction per constraint: the K=1 column runs the scalar
    # path's exact expression and stays bit-identical to it
    cols = []
    for k in range(int(lam.shape[0])):
        ck = cm[:, min(k, cm.shape[1] - 1)]
        cnt_k = cnt if member is None else counts(hit * member[:, k:k + 1])
        cols.append(jnp.sum(cnt_k * ck))
    return jnp.stack(cols)


def realized_reward(rewards: jnp.ndarray, j_star: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(jnp.take_along_axis(rewards, j_star[:, None], axis=1))


# ---------------------------------------------------------------------------
# Algorithm 1 (dual descent)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class DualDescentConfig:
    max_iters: int = 200  # L in Algorithm 1
    step_size: float = 1.0  # eta (normalized internally, see below)
    step_decay: float = 0.999
    lam_init: float = 0.0


@partial(jax.jit, static_argnames=("max_iters", "axis_name"))
def dual_descent(rewards: jnp.ndarray, costs: jnp.ndarray, budget,
                 lam0: jnp.ndarray, *, mask: jnp.ndarray | None = None,
                 member: jnp.ndarray | None = None,
                 max_iters: int = 200, step_size: float = 1.0,
                 step_decay: float = 0.999, axis_name: str | None = None):
    """Algorithm 1 inner loop (steps 5-9), vectorized over all requests.

    Scalar ``lam0``/``budget``: the single-price update (bit-identical
    to the historical scalar implementation).  Vector ``lam0`` (K,) with
    ``budget`` (K,): all K prices descend jointly, each on its own
    subgradient B_k - used_k.

    The raw subgradient has the scale of the budget, while useful lambda
    values have the scale of reward-per-unit-cost; the step is therefore
    normalized by (n_k * mean_cost_k^2) so `step_size` is dimensionless
    and stable across budgets (n_k = requests subject to constraint k,
    mean_cost_k = mean over the options that draw from k).

    mask/member/axis_name (see ``consumption``) let the fused serving
    pipeline run the update on padded, request-sharded windows: n_k
    counts VALID requests only, and every shard sees the same
    (replicated) price trajectory.  Returns (lam, trace_of_gaps).
    """
    costs = costs.astype(jnp.float32)
    rewards = rewards.astype(jnp.float32)
    vector = jnp.ndim(lam0) > 0
    if mask is None:
        n_eff = jnp.float32(rewards.shape[0])
        if axis_name is not None:
            n_eff = ordered_psum(n_eff, axis_name)
    else:
        n_eff = jnp.sum(mask.astype(jnp.float32))
        if axis_name is not None:
            n_eff = ordered_psum(n_eff, axis_name)
    if not vector:
        # an all-masked (empty) window carries no information: floor
        # n_eff so the step normalization cannot explode and slam the
        # price to 0
        # gf: allow[GF003] THE scalar reference expression: the vector
        # path below reproduces this exact float program (PR 4)
        norm = jnp.maximum(n_eff, 1.0) * jnp.mean(costs) ** 2 + 1e-30
    else:
        cm = _as_cost_map(costs)
        if member is not None:
            m = member if mask is None else member * mask[:, None]
            n_k = jnp.sum(m, axis=0)
            if axis_name is not None:
                n_k = ordered_psum(n_k, axis_name)
        else:
            n_k = n_eff
        # per-constraint norm n_k * mean_k^2 where mean_k averages the
        # options that DRAW from the constraint (a geo cost map is zero
        # off its region's column).  Structured as the scalar path's
        # exact expression times a sparsity correction (M/cnt_k)^2 -
        # exactly 1.0 for fully active columns - so the K=1 case stays
        # BIT-identical to the scalar norm: folding the correction into
        # the mean instead lets XLA reassociate the constant divisor
        # chain and drift the last mantissa bits.
        active = (cm > 0).astype(jnp.float32)
        cnt = jnp.maximum(jnp.sum(active, axis=0), 1.0)
        corr = (jnp.float32(cm.shape[0]) / cnt) ** 2
        # gf: allow[GF003] deliberately the scalar path's mean times a
        # separate (M/cnt)^2 correction so K=1 stays bitwise (PR 4;
        # folding the correction INTO the mean is the hazard)
        base = jnp.maximum(n_k, 1.0) * jnp.mean(cm, axis=0) ** 2 + 1e-30
        norm = jnp.broadcast_to(base * corr, lam0.shape)

    def body(carry, _):
        lam, eta = carry
        used = consumption(rewards, costs, lam, mask, member=member,
                           axis_name=axis_name)
        grad = budget - used  # dL/dlambda (per constraint)
        lam_new = jnp.maximum(0.0, lam - eta * grad / norm)
        return (lam_new, eta * step_decay), (budget - used)

    (lam, _), gaps = jax.lax.scan(
        body, (jnp.asarray(lam0, jnp.float32), jnp.asarray(step_size)),
        None, length=max_iters)
    return lam, gaps


# ---------------------------------------------------------------------------
# Exact oracle by bisection (single constraint => monotone consumption)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("iters",))
def dual_bisect(rewards: jnp.ndarray, costs: jnp.ndarray, budget: float,
                *, iters: int = 64, lam_hi_init: float = None):
    """Smallest lambda >= 0 with consumption(lambda) <= budget.

    Single-constraint only: consumption is non-increasing in lambda
    (higher price -> cheaper chains) so bisection is exact up to float
    resolution.  If even lambda=0 fits the budget, returns 0 (budget
    slack; constraint inactive).
    """
    rewards = rewards.astype(jnp.float32)
    costs = costs.astype(jnp.float32)
    # Upper bound: the price at which every request picks its cheapest
    # chain.  Chain j beats a cheaper j' once lam > (R_j - R_j')/(c_j -
    # c_j'), so the bound must use the smallest POSITIVE cost gap (two
    # nearly-equal costs need a huge price to separate), not min cost.
    r_span = jnp.max(rewards) - jnp.min(rewards)
    sorted_c = jnp.sort(costs)
    gaps = jnp.diff(sorted_c)
    min_gap = jnp.min(jnp.where(gaps > 0, gaps, jnp.inf), initial=jnp.inf)
    min_gap = jnp.where(jnp.isfinite(min_gap), min_gap, jnp.max(costs))
    lam_hi = (r_span / jnp.maximum(min_gap, 1e-30) + 1.0) \
        if lam_hi_init is None else jnp.asarray(lam_hi_init, jnp.float32)

    def body(_, bounds):
        lo, hi = bounds
        mid = 0.5 * (lo + hi)
        fits = consumption(rewards, costs, mid) <= budget
        return jnp.where(fits, lo, mid), jnp.where(fits, mid, hi)

    lo, hi = jax.lax.fori_loop(0, iters, body,
                               (jnp.float32(0.0), lam_hi))
    # prefer 0 if the unconstrained allocation already fits
    fits0 = consumption(rewards, costs, jnp.float32(0.0)) <= budget
    return jnp.where(fits0, 0.0, hi)


# ---------------------------------------------------------------------------
# The shared host-loop window body (controllers are wrappers over this)
# ---------------------------------------------------------------------------


def window_step(rewards, costs, budget: float, lam, *, cheap: int,
                guard: bool = True,
                cfg: DualDescentConfig | None = None):
    """One host-loop serving window: Eq. 10 decide -> tail-reserve guard
    -> Algorithm 1 price update, in the single-price (scalar) form.

    ``core.budget.BudgetController`` (FLOPs costs) and
    ``carbon.controller.CarbonBudgetController`` (carbon-effective
    costs) both delegate here so the three historical copies of this
    loop stay ONE implementation.  Returns
    ``(decisions, downgraded, spend, lam_new)`` with ``decisions`` a
    host ndarray and ``lam_new`` the published (device) price.
    """
    import numpy as np

    from repro.serving.guard import downgrade_guard_np

    cfg = cfg or DualDescentConfig()  # fresh default, never import-time
    costs = np.asarray(costs)
    costs_j = jnp.asarray(costs, jnp.float32)
    rewards_j = jnp.asarray(rewards)
    decisions = np.asarray(allocate(rewards_j, costs_j, lam))
    downgraded = 0
    spend = float(np.sum(costs[decisions]))
    if guard:
        decisions, downgraded, spend = downgrade_guard_np(
            decisions, costs, budget, cheap)
    lam_new, _ = dual_descent(
        rewards_j, costs_j, budget, lam, max_iters=cfg.max_iters,
        step_size=cfg.step_size, step_decay=cfg.step_decay)
    return decisions, downgraded, spend, lam_new


# ---------------------------------------------------------------------------
# Streaming wrapper (the nearline job, Algorithm 1 outer loop)
# ---------------------------------------------------------------------------


class DynamicPrimalDual:
    """Nearline dual-price tracker.

    Every window t: observe the (R, c) samples collected from traffic,
    run L descent steps warm-started at lambda_{t-1}, publish lambda_t.
    Online decisions for window t+1 use lambda_t (paper: near-optimal
    under i.i.d. arrivals, Agrawal et al. 2014).
    """

    def __init__(self, costs, budget_per_window: float,
                 cfg: DualDescentConfig | None = None):
        self.costs = jnp.asarray(costs, jnp.float32)
        self.budget = float(budget_per_window)
        self.cfg = cfg or DualDescentConfig()
        self.lam = jnp.float32(self.cfg.lam_init)
        self.history: list[float] = []

    def update(self, rewards) -> float:
        """One nearline window: returns the new published dual price."""
        lam, _ = dual_descent(
            jnp.asarray(rewards), self.costs, self.budget, self.lam,
            max_iters=self.cfg.max_iters, step_size=self.cfg.step_size,
            step_decay=self.cfg.step_decay)
        self.lam = lam
        self.history.append(float(lam))
        return float(lam)

    def decide(self, rewards) -> jnp.ndarray:
        """Online module: Eq. 10 with the latest published price."""
        return allocate(jnp.asarray(rewards), self.costs, self.lam)

    def set_budget(self, budget: float):
        self.budget = float(budget)
