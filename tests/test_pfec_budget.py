import numpy as np
import pytest

from repro.core.action_chain import generate_action_chains, paper_stage_specs
from repro.core.budget import BudgetController
from repro.core.pfec import (EnergyConfig, carbon_from_energy,
                             energy_from_flops, pfec_report, revenue_at_e)


def test_energy_and_carbon_follow_paper_constants():
    cfg = EnergyConfig()
    kwh = energy_from_flops(1e15, cfg)
    assert kwh > 0
    # linear in FLOPs
    assert energy_from_flops(2e15, cfg) == pytest.approx(2 * kwh)
    # CE = EC * CI with CI = 615 g/kWh (paper Eq. 2)
    assert carbon_from_energy(kwh, cfg) == pytest.approx(kwh * 615.0)
    # PUE scales EC linearly (paper Eq. 1)
    cfg2 = EnergyConfig(pue=2 * cfg.pue)
    assert energy_from_flops(1e15, cfg2) == pytest.approx(2 * kwh)


def test_energy_config_validation():
    with pytest.raises(ValueError, match="pue"):
        EnergyConfig(pue=0.8)
    with pytest.raises(ValueError, match="p_gpu_w"):
        EnergyConfig(p_gpu_w=0.0)
    with pytest.raises(ValueError, match="sustained_flops_per_s"):
        EnergyConfig(sustained_flops_per_s=-1e12)
    with pytest.raises(ValueError, match="carbon_intensity_g_per_kwh"):
        EnergyConfig(carbon_intensity_g_per_kwh=0.0)
    with pytest.raises(ValueError, match="ram_cpu_fraction"):
        EnergyConfig(ram_cpu_fraction=-0.1)


def test_default_cfg_is_fresh_not_import_time():
    # cfg=None routes through one fresh default; the old `=EnergyConfig()`
    # default arg was evaluated once at import
    assert energy_from_flops(1e15) == energy_from_flops(1e15, EnergyConfig())
    assert carbon_from_energy(2.0) == 2.0 * 615.0


def test_pfec_report_fields():
    r = pfec_report(clicks=123.0, flops=1e12, extra="x")
    row = r.as_row()
    assert row["performance"] == 123.0
    assert row["flops"] == 1e12
    assert row["carbon_g"] == pytest.approx(row["energy_kwh"] * 615.0)
    assert row["extra"] == "x"


def test_pfec_report_meta_passthrough():
    r = pfec_report(clicks=1.0, flops=1e9, method="greenflow",
                    budget_frac=0.5, window=3)
    row = r.as_row()
    assert (row["method"], row["budget_frac"], row["window"]) == \
        ("greenflow", 0.5, 3)
    assert r.meta == {"method": "greenflow", "budget_frac": 0.5, "window": 3}
    # meta never clobbers the four PFEC columns
    assert set(row) == {"performance", "flops", "energy_kwh", "carbon_g",
                        "method", "budget_frac", "window"}


def test_revenue_at_e():
    clicks = np.zeros(50)
    clicks[[3, 7, 40]] = 1.0
    ranked = np.argsort(-clicks, kind="stable")  # clicked first
    assert revenue_at_e(clicks, ranked, e=20) == 3.0
    ranked_bad = np.arange(50)[::-1]
    assert revenue_at_e(clicks, ranked_bad, e=5) == 0.0


def test_revenue_at_e_edge_cases():
    clicks = np.zeros(10)
    clicks[[1, 4]] = 1.0
    ranked = np.argsort(-clicks, kind="stable")
    # e beyond the candidate set exposes everything ranked
    assert revenue_at_e(clicks, ranked, e=500) == 2.0
    # empty ranking exposes nothing (and must not crash on fancy-indexing)
    assert revenue_at_e(clicks, np.array([], dtype=np.int64), e=5) == 0.0
    assert revenue_at_e(clicks, [], e=5) == 0.0
    # non-contiguous / non-float labels: a strided int view and a bool view
    clicks_int = np.zeros(20, np.int32)
    clicks_int[[2, 6]] = 1
    strided = clicks_int[::2]  # items 0,2,4,...: clicks at positions 1, 3
    assert revenue_at_e(strided, np.array([1, 3, 0]), e=2) == 2.0
    assert revenue_at_e(clicks.astype(bool), ranked, e=3) == 2.0


def test_budget_controller_guard_caps_spend():
    chains = generate_action_chains(paper_stage_specs())
    rng = np.random.default_rng(0)
    n = 200
    budget = float(np.median(chains.costs)) * n * 0.7
    ctl = BudgetController(chains, budget)
    # adversarial: rewards favour the most expensive chain for everyone
    rewards = np.tile(chains.costs / chains.costs.max(), (n, 1)).astype(np.float32)
    floor_per_req = chains.costs[chains.cheapest()]
    for _ in range(4):
        decisions = ctl.step_window(rewards + rng.normal(0, 0.01, rewards.shape))
        assert ctl.stats[-1].spend <= budget * (1 + 1e-6)
    # traffic spike: 5x requests whose rewards still favour the most
    # expensive chain at the price the controller holds now, so Eq. 10
    # overspends and the guard must step in.  It caps spend at the
    # budget OR the physical floor (every request on the cheapest chain
    # - Eq. 3b serves everyone; the paper calls this "computation
    # downgrade").
    lam = float(ctl.pd.lam)
    slope = 2.0 * (lam + 1.0 / chains.costs.max())  # score_j ~ c_j
    spike = np.tile(slope * chains.costs, (5 * n, 1))
    ctl.step_window(spike.astype(np.float32))
    cap = max(budget, floor_per_req * len(spike))
    assert ctl.stats[-1].spend <= cap * (1 + 1e-6)
    assert ctl.stats[-1].downgraded > 0
