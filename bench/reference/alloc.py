"""The allocator's decision rule (paper Eq. 10) and its nearline price
update (paper Algorithm 1), for a tenant x region window.

A request of tenant t served by chain j in region r pays the per-FLOP
price (lam_tenant[t] + lam_region[r]) * s_r on the chain's c_j FLOPs,
s_r being region r's grams per FLOP; it takes the (region, chain) whose
reward minus that priced cost is largest.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def chain_gaps(rewards, decisions, price_per_flop, costs):
    """(n,) how far each served chain's priced reward lies below the
    best chain's, at the per-FLOP price ``price_per_flop`` ((n,) or
    scalar) the request's chain choice faced; float64."""
    r = np.asarray(rewards, np.float64)
    obj = r - np.asarray(price_per_flop, np.float64).reshape(-1, 1) \
        * np.asarray(costs, np.float64)[None]
    return obj.max(1) - obj[np.arange(len(r)), np.asarray(decisions)]


@partial(jax.jit, static_argnames=("t_n", "iters"))
def dual_update(rewards, tenants, scales, costs, budgets, lam0, weight, *,
                t_n: int, iters: int, step: float, decay: float):
    """Algorithm 1: ``iters`` steps of projected subgradient descent on
    the (T + R,) prices, from ``lam0``, against the window's (T + R,)
    ``budgets`` (tenant grams, then region grams).

    Every step each request takes its best (region, chain) at the
    current prices (ties to the lower region, then the lower chain);
    price k moves by step * (used_k - B_k) / norm_k, clipped at 0, where
    used_k sums the grams of its requests' choices (``weight`` (n,)
    counts each request) and norm_k = n_k * (mean gram cost of the
    options that draw from k)^2; the step decays by ``decay`` each
    iteration.  float32 at full precision.
    """
    f32 = jnp.float32
    r_n, n = scales.shape[0], rewards.shape[0]
    opt = scales[:, None].astype(f32) * costs[None, :].astype(f32)  # (R, J)
    onehot_t = (tenants[:, None] == jnp.arange(t_n)[None, :]).astype(f32)
    w = weight.astype(f32)
    n_k = jnp.concatenate([onehot_t.T @ w, jnp.full((r_n,), jnp.sum(w))])
    mean_k = jnp.concatenate([jnp.full((t_n,), jnp.mean(opt)),
                              jnp.mean(opt, axis=1)])
    norm = jnp.maximum(n_k, 1.0) * mean_k ** 2

    def used(lam):
        per_flop = (lam[:t_n][tenants][:, None] + lam[t_n:][None, :]) \
            * scales[None, :]  # (n, R)
        score = rewards[:, None, :] - per_flop[:, :, None] * costs[None, None]
        best = jnp.argmax(score.reshape(n, -1), axis=1)
        reg, ch = best // costs.shape[0], best % costs.shape[0]
        grams = opt[reg, ch] * w
        by_t = onehot_t.T @ grams
        by_r = (reg[:, None] == jnp.arange(r_n)[None, :]).astype(f32).T \
            @ grams
        return jnp.concatenate([by_t, by_r])

    def body(_, carry):
        lam, eta = carry
        lam = jnp.maximum(0.0, lam + eta * (used(lam) - budgets) / norm)
        return lam, eta * decay

    with jax.default_matmul_precision("highest"):
        lam, _ = jax.lax.fori_loop(
            0, iters, body, (lam0.astype(f32), jnp.asarray(step, f32)))
    return lam
