"""The tenant x region budget spec (``"kind": "geotenants"``): per-tenant
gram budgets and per-region gram caps priced in one pass, as ``serve
--scenario geotenants --tenant-mode priced`` prices them.

Program side (``Spec``): the ``ConstraintSpec`` with its ``TenantAxis``,
``RegionAxis`` and ``GlobalAxis``, the per-window budget row and the
cost-scale trace of a repeating carbon-intensity day.  Reference side
(``Reference``): the price per FLOP each request faced, Algorithm 1 over
the (T + R,) prices, and the spec's own compared numbers.

The tenants are equal blocks of each window in order; region r's grid
intensity peaks ``r * geo_offset_h`` hours after the first's.
"""
from __future__ import annotations

import math

import numpy as np

from bench.reference import alloc

# paper constants behind repro.core.pfec.kwh_per_flop (Eq. 1): PUE,
# device powers in W, the sustained FLOP/s that converts FLOPs into
# device-hours, and the share of those hours billed to RAM and CPU
_PUE, _P_GPU_W, _P_CPU_W, _P_RAM_W = 1.67, 250.0, 105.0, 20.0
_SUSTAINED_FLOPS, _RAM_CPU_FRAC = 2.0e13, 0.15
DAY_S = 86400.0


def grams_per_flop(ci_g_per_kwh: float) -> float:
    """kappa * CI: gCO2e per FLOP served (paper Eq. 1-2)."""
    watts_h = _P_GPU_W + (_P_CPU_W + _P_RAM_W) * _RAM_CPU_FRAC
    kwh = _PUE * watts_h / 1000.0 / _SUSTAINED_FLOPS / 3600.0
    return kwh * float(ci_g_per_kwh)


def region_ci(spec: dict) -> np.ndarray:
    """(windows_per_day, R) grid intensity of each serving window of the
    day cycle: hourly diurnal samples per region, region r's peak
    ``r * geo_offset_h`` hours after the first, each window taking the
    mean of the hourly steps it spans."""
    n_w = int(spec["windows_per_day"])
    window_s = DAY_S / n_w
    hours = np.arange(24, dtype=np.float64)
    out = np.empty((n_w, len(spec["regions"])))
    for r in range(len(spec["regions"])):
        peak = spec["ci_peak_hour"] + r * spec["geo_offset_h"]
        hourly = spec["ci_mean"] * (1.0 + spec["ci_rel_amplitude"] * np.cos(
            2.0 * np.pi * (hours - peak) / 24.0))
        for t in range(n_w):
            lo, hi = t * window_s, (t + 1) * window_s
            acc = 0.0
            for i in range(math.floor(lo / 3600.0), math.ceil(hi / 3600.0)):
                seg = min(hi, (i + 1) * 3600.0) - max(lo, i * 3600.0)
                if seg > 0:
                    acc += hourly[i % 24] * seg
            out[t, r] = acc / window_s
    return out


def cpu_cut(cfg: dict) -> None:
    """Nothing to cut for a CPU test: the budgets follow the window
    size, and two tenants and two regions are already small."""


class Spec:
    """The program's side: the constraint spec a pipeline serves under,
    and the budget and cost-scale traces its windows are driven with.
    ``chains`` is the program's chain set, ``window`` the base window
    size the per-window budget is set for."""

    def __init__(self, cfg: dict, chains, window: int):
        from repro.serving.spec import (ConstraintSpec, GlobalAxis,
                                        RegionAxis, TenantAxis)

        sp = cfg["spec"]
        c_max = float(chains.costs.max())
        flops_budget = sp["budget_frac"] * c_max * window
        self.day_ci = region_ci(sp)  # (windows_per_day, R)
        g_total = flops_budget * grams_per_flop(sp["ci_mean"])
        w = np.linspace(1.0, sp["tenant_spread"], sp["tenants"])
        tenant_g = g_total * w / w.sum()
        region_g = np.full(len(sp["regions"]),
                           sp["region_cap_frac"] * g_total)
        # (T + R,) grams of a window per axis
        self.budget_row = np.concatenate([tenant_g, region_g])
        self.constraint = ConstraintSpec([
            TenantAxis(tuple(tenant_g), priced=sp["tenant_priced"]),
            RegionAxis(len(sp["regions"]), names=tuple(sp["regions"]),
                       split=sp["region_split"]),
            GlobalAxis(pricing="carbon")])
        # the nearline update aims at the next window
        self.forecast = bool(sp["ci_forecast"])

    def traces(self, first: int, count: int):
        """(budget_trace, scale_trace) for windows first..first+count-1
        of the run: the CI day repeats every ``windows_per_day``
        windows."""
        idx = (first + np.arange(count)) % len(self.day_ci)
        scale = grams_per_flop(1.0) * self.day_ci[idx]
        budget = np.broadcast_to(self.budget_row,
                                 (count, len(self.budget_row)))
        return budget, scale

    def reported_spend(self, result) -> np.ndarray:
        """The (T, R) spend the program reports for a served window."""
        return np.asarray(result.tr_spend, np.float64)


class Reference:
    """The reference's side, over the reference's chain set ``ch``.

    A window's terms are its (T + R,) budget ``bud`` and (R,) grams per
    FLOP ``sc``; a served window ``w`` (``bench.check.Served``) carries
    its decisions, regions, prices and reported spend."""

    def __init__(self, cfg: dict, ch):
        self.cfg = cfg
        self.ch = ch
        self.t_n = cfg["spec"]["tenants"]

    def tenants(self, n: int) -> np.ndarray:
        """(n,) tenant of each request of a window."""
        return np.repeat(np.arange(self.t_n), n // self.t_n)

    def spend_numbers(self, w, bud, sc) -> tuple[float, float]:
        """(spend_over, spend_report) of window ``w``."""
        c = self.ch.costs
        over = report = 0.0
        d = np.asarray(w.decisions)
        n = len(d)
        reg = np.asarray(w.regions)
        ten = self.tenants(n)
        grams = sc[reg] * c[d]
        tr = np.zeros((self.t_n, len(sc)))
        np.add.at(tr, (ten, reg), grams)
        cheapest = sc.min() * c.min()
        for t in range(self.t_n):
            cap = max(bud[t], (ten == t).sum() * cheapest)
            over = max(over, tr[t].sum() / cap - 1.0)
        for r in range(len(sc)):
            cap = max(bud[self.t_n + r],
                      (reg == r).sum() * sc[r] * c.min())
            over = max(over, tr[:, r].sum() / cap - 1.0)
        if w.spend is not None:
            rep = np.asarray(w.spend, np.float64).reshape(tr.shape)
            report = max(report, float(np.max(np.abs(rep - tr)))
                         / max(float(np.max(np.abs(tr))), 1e-30))
        return over, report

    def region_band(self, w, rewards, sc) -> float:
        """How far above its cheapest region's the served region's priced
        cost per FLOP lies, relative, over requests the guard left on a
        chain other than the cheapest.  The price carries the router's
        tie-break floor: 1e-6 x max |reward| over the mean option cost,
        times the region's scale."""
        lam = np.asarray(w.lam_before, np.float64)
        ten = self.tenants(len(w.decisions))
        opt = (sc[:, None] * self.ch.costs[None, :]).reshape(-1)
        eps = 1e-6 * np.abs(rewards).max() / (opt.mean() + 1e-30)
        u = ((lam[:self.t_n][ten][:, None] + lam[self.t_n:][None, :])
             + eps) * sc[None, :]
        best = u.min(1)
        got = u[np.arange(len(ten)), np.asarray(w.regions)]
        keep = np.asarray(w.decisions) != self.ch.cheapest
        return float(np.max(got[keep] / best[keep] - 1)) if keep.any() \
            else 0.0

    def window_numbers(self, w, rewards, bud, sc) -> dict:
        """The spec's own numbers for window ``w``, each a worst case
        that the check takes over every served window:

        - ``spend_over``: every budget axis - the spend of the served
          decisions, recomputed in float64, over the guard's guarantee
          max(budget, requests x cheapest option), minus 1;
        - ``spend_report``: the largest relative gap between the spend
          the program reports per (tenant, region) and that
          recomputation;
        - ``region_band``: how far the priced cost per FLOP of the region
          a request was served in lies above its cheapest region's,
          relative, for requests off the cheapest chain (the router may
          round a tie within the spec's ``tie_tol``)."""
        over, report = self.spend_numbers(w, bud, sc)
        return {"spend_over": over, "spend_report": report,
                "region_band": self.region_band(w, rewards, sc)}

    def price_per_flop(self, lam, sc, n: int) -> np.ndarray:
        """The per-FLOP price each of ``n`` requests' chain choice faced
        at prices ``lam``: its cheapest region's."""
        lam = np.asarray(lam, np.float64)
        ten = self.tenants(n)
        per_flop = (lam[:self.t_n][ten][:, None]
                    + lam[self.t_n:][None, :]) * sc[None, :]
        return per_flop.min(1)

    def dual_update(self, rewards, weight, lam0, bud, sc,
                    dual: dict) -> np.ndarray:
        """Algorithm 1 on the (T + R,) prices from ``lam0`` against the
        budget ``bud`` and grams per FLOP ``sc`` it aims at; ``weight``
        (n,) counts each request; ``dual`` as the configuration's."""
        import jax.numpy as jnp

        ten = self.tenants(len(rewards))
        lam = alloc.dual_update(
            jnp.asarray(rewards, jnp.float32), jnp.asarray(ten, jnp.int32),
            jnp.asarray(sc, jnp.float32),
            jnp.asarray(self.ch.costs, jnp.float32),
            jnp.asarray(bud, jnp.float32),
            jnp.asarray(lam0, jnp.float32), jnp.asarray(weight),
            t_n=self.t_n, iters=int(dual["max_iters"]),
            step=float(dual["step_size"]), decay=float(dual["step_decay"]))
        return np.asarray(lam, np.float64)
