"""End-to-end offline experiment harness (paper §5.1-§5.2 protocol).

Pipeline (all steps faithful to the paper, scaled-down world documented in
DESIGN.md §8):

  1. synthetic Ali-CCP-style world + 50/25/22.5/2.5 user split;
  2. train the four cascade models (DSSM, YDNN, DIN, DIEN) on the click
     log of the cascade-train users;
  3. precompute per-stage full-corpus scores; ground-truth clicks sampled
     once per (user, item);
  4. simulate EVERY action chain per user -> revenue matrices (this is
     the paper's training-sample generation for the reward model);
  5. train the personalized reward model (+ per-stage models for CRAS);
  6. evaluate GreenFlow / CRAS-* / EQUAL-* at a sweep of budgets with
     revenue@e realized against ground truth.

Used by tests/test_system.py (small), benchmarks/ (paper tables) and
examples/.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.cascade.engine import (CascadeModels, precompute_stage_scores,
                                  simulate_revenue_matrix)
from repro.core.action_chain import (ActionChainSet, ModelInstance, StageSpec,
                                     generate_action_chains)
from repro.core.baselines import (StageActionSpace, cras_allocation,
                                  equal_allocation)
from repro.core.primal_dual import allocate, dual_bisect
from repro.core.reward_model import (RewardModelConfig, chain_label_norm,
                                     denormalize_rewards, field_rce,
                                     reward_matrix, reward_matrix_chunked,
                                     reward_model_init)
from repro.data.synthetic import (World, WorldConfig, build_world, ctr_batch,
                                  split_users)
from repro.models.recsys import dien, din, dssm, ydnn
from repro.training.optimizer import AdamW, cosine_schedule
from repro.training.trainer import build_train_step, init_state


@dataclass(frozen=True)
class ExperimentConfig:
    world: WorldConfig = WorldConfig(n_users=4000, n_items=600, hist_len=16)
    expose: int = 10  # e of revenue@e (paper: 20 at corpus 4000)
    n_scales: int = 6  # |N_2| = |N_3|
    cascade_steps: int = 250
    reward_steps: int = 600
    batch: int = 64
    seed: int = 0
    # paper split shifts mass from validation (unused offline) to final
    # eval: realized-revenue comparisons need more than a 2.5% slice at
    # mini scale (DESIGN.md §8 deviation)
    split_fracs: tuple = (0.5, 0.05, 0.25, 0.2)
    # paper Table 1 FLOPs keep the budget axis in paper units
    flops: tuple = (13e3, 123e3, 7020e3, 7098e3)


def scaled_stage_specs(cfg: ExperimentConfig) -> tuple[StageSpec, ...]:
    """Paper's chain space with item scales proportional to the corpus.

    The paper uses N2 in 20-37.5% and N3 in 1.5-5% of a 4000-item corpus
    with e=20.  At mini corpora (a few hundred items) 1.5-5% collapses to
    ~expose and the rank stage becomes a no-op (exposing top-e of e
    candidates), which degenerates every chain to the prerank ordering.
    When that happens (max N3 under the paper band < 3x expose) we
    stretch N3 to [expose, 20%] and N2 to [20%, 50%] so the computation
    axis stays meaningful at small scale; corpora large enough to keep
    the paper band non-degenerate use the paper ratios (DESIGN.md §8)."""
    i = cfg.world.n_items
    if 0.05 * i >= 3 * cfg.expose:  # paper band is non-degenerate
        n2_band, n3_band = (0.20, 0.375), (0.015, 0.05)
    else:
        n2_band, n3_band = (0.20, 0.50), (0.015, 0.20)
    n2 = tuple(sorted({int(x) for x in
                       np.linspace(n2_band[0] * i, n2_band[1] * i,
                                   cfg.n_scales)}))
    n3 = tuple(sorted({max(cfg.expose, int(x)) for x in
                       np.linspace(max(cfg.expose, n3_band[0] * i),
                                   n3_band[1] * i, cfg.n_scales)}))
    f_dssm, f_ydnn, f_din, f_dien = cfg.flops
    return (
        StageSpec("recall", (ModelInstance("DSSM", f_dssm, auc=0.525),),
                  (i,), 4),
        StageSpec("prerank", (ModelInstance("YDNN", f_ydnn, auc=0.581),),
                  n2, 4),
        StageSpec("rank", (ModelInstance("DIN", f_din, auc=0.639),
                           ModelInstance("DIEN", f_dien, auc=0.641)),
                  n3, 4),
    )


@dataclass
class Experiment:
    cfg: ExperimentConfig
    world: World
    split: object
    chains: ActionChainSet
    models: CascadeModels
    clicks_eval: np.ndarray  # (U_eval, I) ground truth
    clicks_reward: np.ndarray  # (U_reward, I)
    revenue_eval: np.ndarray  # (U_eval, J) simulated true revenue
    revenue_reward: np.ndarray  # (U_reward, J)
    ctx_eval: np.ndarray
    ctx_reward: np.ndarray
    reward_params: dict = None
    reward_cfg: RewardModelConfig = None
    history: dict = field(default_factory=dict)


# ---------------------------------------------------------------------------
# Cascade model training
# ---------------------------------------------------------------------------


def _train_model(loss_fn, params, pipe_fn, steps, batch, seed, lr=3e-3):
    opt = AdamW(weight_decay=1e-5)
    step = build_train_step(loss_fn, opt, cosine_schedule(lr, 20, steps),
                            donate=False)
    state = init_state(params, opt)
    rng = np.random.default_rng(seed)
    losses = []
    for t in range(steps):
        b = pipe_fn(rng)
        state, m = step(state, jax.tree_util.tree_map(jnp.asarray, b))
        losses.append(float(m["loss"]))
    return state.params, losses


def cascade_configs(w: WorldConfig) -> tuple:
    """The four stage models' configs (DSSM, YDNN, DIN, DIEN) for a
    world - the widths every serving stack over that world runs."""
    n_uf = w.n_user_fields
    user_vocab = n_uf * w.user_field_vocab

    # Recall tower is CATEGORY-ONLY and low-capacity on purpose: the paper's
    # stage quality ladder (DSSM 0.525 < YDNN 0.581 < DIN/DIEN ~0.64 AUC)
    # only emerges at mini scale if the recall model generalizes coarsely
    # instead of memorizing a few hundred item ids.
    dssm_cfg = dssm.DSSMConfig(user_vocab=user_vocab, item_vocab=w.n_items,
                               n_user_fields=n_uf, n_item_fields=1,
                               embed_dim=4, hidden=(16, 8), d_out=4)
    ydnn_cfg = ydnn.YDNNConfig(item_vocab=w.n_items, user_vocab=user_vocab,
                               n_user_fields=n_uf, hist_len=w.hist_len,
                               embed_dim=8, hidden=(48, 24), d_out=12)
    din_cfg = din.DINConfig(item_vocab=w.n_items, cat_vocab=w.n_cats,
                            user_vocab=user_vocab, n_user_fields=n_uf,
                            embed_dim=8, seq_len=w.hist_len,
                            attn_hidden=(16, 8), mlp_hidden=(32, 16))
    dien_cfg = dien.DIENConfig(item_vocab=w.n_items, cat_vocab=w.n_cats,
                               user_vocab=user_vocab, n_user_fields=n_uf,
                               embed_dim=8, seq_len=w.hist_len,
                               attn_hidden=(16, 8), mlp_hidden=(32, 16))
    return dssm_cfg, ydnn_cfg, din_cfg, dien_cfg


def train_cascade_models(world: World, users: np.ndarray,
                         cfg: ExperimentConfig) -> CascadeModels:
    dssm_cfg, ydnn_cfg, din_cfg, dien_cfg = cascade_configs(world.cfg)
    key = jax.random.PRNGKey(cfg.seed)

    def pipe(rng):
        b = ctr_batch(world, users, rng, cfg.batch)
        b.pop("users")
        return b

    # DSSM: two-tower on (user_fields, category) with BCE
    def dssm_loss(p, b):
        items = jnp.stack([b["item_cat"]], axis=-1)[:, None, :]
        s = dssm.score(p, dssm_cfg, b["user_fields"], items)[:, 0] * 6.0
        y = b["label"]
        return jnp.mean(jnp.maximum(s, 0) - s * y +
                        jnp.log1p(jnp.exp(-jnp.abs(s))))

    dssm_params, _ = _train_model(dssm_loss, dssm.init(key, dssm_cfg), pipe,
                                  cfg.cascade_steps, cfg.batch, cfg.seed + 1)

    def ydnn_loss(p, b):
        s = ydnn.score(p, ydnn_cfg, b["hist_ids"], b["hist_mask"],
                       b["user_fields"], b["item_id"][:, None])[:, 0]
        y = b["label"]
        return jnp.mean(jnp.maximum(s, 0) - s * y +
                        jnp.log1p(jnp.exp(-jnp.abs(s))))

    ydnn_params, _ = _train_model(ydnn_loss, ydnn.init(key, ydnn_cfg), pipe,
                                  cfg.cascade_steps, cfg.batch, cfg.seed + 2)

    # rank models get 2x the steps: they carry the cascade's quality
    # ceiling and are the paper's most accurate (and costly) stage
    din_params, _ = _train_model(
        lambda p, b: din.loss_fn(p, din_cfg, b), din.init(key, din_cfg),
        pipe, 2 * cfg.cascade_steps, cfg.batch, cfg.seed + 3)
    dien_params, _ = _train_model(
        lambda p, b: dien.loss_fn(p, dien_cfg, b), dien.init(key, dien_cfg),
        pipe, 2 * cfg.cascade_steps, cfg.batch, cfg.seed + 4)

    return CascadeModels(dssm_params, dssm_cfg, ydnn_params, ydnn_cfg,
                         din_params, din_cfg, dien_params, dien_cfg)


# ---------------------------------------------------------------------------
# Build the full experiment
# ---------------------------------------------------------------------------


def build_experiment(cfg: ExperimentConfig = ExperimentConfig(),
                     *, verbose: bool = False) -> Experiment:
    log = print if verbose else (lambda *a: None)
    world = build_world(cfg.world)
    split = split_users(world, seed=cfg.seed + 10, fracs=cfg.split_fracs)
    chains = generate_action_chains(scaled_stage_specs(cfg))
    log(f"[exp] world U={cfg.world.n_users} I={cfg.world.n_items} "
        f"J={chains.n_chains}")

    models = train_cascade_models(world, split.cascade_train, cfg)
    log("[exp] cascade models trained")

    rng = np.random.default_rng(cfg.seed + 20)
    out = {}
    for name, users in (("eval", split.final_eval),
                        ("reward", split.reward_train)):
        scores = precompute_stage_scores(models, world, users)
        clicks = world.sample_clicks(
            users, np.tile(np.arange(world.cfg.n_items), (len(users), 1)),
            rng)
        rev = simulate_revenue_matrix(scores, chains, clicks,
                                      expose=cfg.expose)
        out[name] = (scores, clicks, rev)
        log(f"[exp] simulated {name}: users={len(users)} "
            f"mean_rev={rev.mean():.3f}")

    return Experiment(
        cfg=cfg, world=world, split=split, chains=chains, models=models,
        clicks_eval=out["eval"][1], clicks_reward=out["reward"][1],
        revenue_eval=out["eval"][2], revenue_reward=out["reward"][2],
        ctx_eval=world.reward_context(split.final_eval),
        ctx_reward=world.reward_context(split.reward_train),
    )


# ---------------------------------------------------------------------------
# Reward model training (paper §4.2 on simulated chain samples)
# ---------------------------------------------------------------------------


def reward_config(chains: ActionChainSet, d_context: int, *,
                  recursive: bool = True,
                  multi_basis: bool = True) -> RewardModelConfig:
    """The reward model's widths over a chain set and context size."""
    return RewardModelConfig(
        n_stages=chains.n_stages, max_models=2, n_scale_groups=4,
        d_context=d_context, d_feature=32, d_hidden=32, d_state=16,
        recursive=recursive, multi_basis=multi_basis)


def train_reward_model(exp: Experiment, *, recursive: bool = True,
                       multi_basis: bool = True, steps: int | None = None,
                       seed: int = 0) -> tuple[dict, RewardModelConfig]:
    """Train the personalized reward model on simulated chain revenues.

    Two departures from the seed's (user, chain)-pair sampling, both
    enabled by the cheap vectorized simulator:

    * PER-CHAIN LABEL NORMALIZATION: the model fits the revenue RATIO
      y_uj = rev_uj / mean_u(rev_uj).  The per-chain mean curve (how much
      compute helps on average) is measured exactly from the simulation;
      the network only has to learn per-user DEVIATIONS from it - the
      user heterogeneity GreenFlow allocates on.  The multi-basis head is
      non-negative/monotone by construction, which a ratio target (>= 0,
      centered at 1) respects while a signed residual would not.
      Predictions are de-normalized with the stored ``label_norm``.
    * FULL-ROW BATCHES: each step draws a batch of users and regresses
      ALL J chains per user at once (one ``reward_matrix`` call), so each
      gradient sees every chain's label for the sampled users.
    """
    cfg = exp.cfg
    chains = exp.chains
    rcfg = reward_config(chains, exp.ctx_reward.shape[1],
                         recursive=recursive, multi_basis=multi_basis)
    params = reward_model_init(jax.random.PRNGKey(seed + 33), rcfg)
    steps = steps or cfg.reward_steps

    rev = exp.revenue_reward  # (U, J)
    mu = chain_label_norm(rev)  # (J,)
    labels = (rev / mu[None, :]).astype(np.float32)
    mo = jnp.asarray(chains.model_onehot)
    sh = jnp.asarray(chains.scale_multihot)

    def loss_fn(p, b):
        pred = reward_matrix(p, rcfg, b["context"], mo, sh)  # (B, J)
        return jnp.mean(jnp.square(pred - b["label"]))

    opt = AdamW(weight_decay=1e-5)
    step = build_train_step(loss_fn, opt,
                            cosine_schedule(3e-3, 20, steps), donate=False)
    state = init_state(params, opt)
    rng = np.random.default_rng(seed + 44)
    n_u = rev.shape[0]
    b_users = max(8, cfg.batch // 4)  # each user row carries all J labels
    for t in range(steps):
        ui = rng.integers(0, n_u, b_users)
        batch = {"context": jnp.asarray(exp.ctx_reward[ui]),
                 "label": jnp.asarray(labels[ui])}
        state, m = step(state, batch)
    out = dict(state.params)
    out["label_norm"] = jnp.asarray(mu)
    return out, rcfg


def predicted_rewards(exp: Experiment, params, rcfg, ctx) -> np.ndarray:
    # chunked: offline scoring stays O(chunk * J) however many users the
    # eval slice carries (bitwise equal to the full-matrix call per row)
    r = reward_matrix_chunked(params, rcfg, ctx,
                              jnp.asarray(exp.chains.model_onehot),
                              jnp.asarray(exp.chains.scale_multihot))
    return np.asarray(denormalize_rewards(params, np.asarray(r)))


def reward_model_metrics(exp: Experiment, params, rcfg) -> dict:
    """Field-RCE (paper Eq. 12; field = rank-stage scale group) + MSE on
    the held-out eval users."""
    pred = predicted_rewards(exp, params, rcfg, exp.ctx_eval)
    true = exp.revenue_eval
    k_rank = exp.chains.n_stages - 1
    groups = exp.chains.scale_multihot[:, k_rank].sum(-1).astype(int)
    fields = np.tile(groups, (true.shape[0], 1)).reshape(-1)
    rce = field_rce(true.reshape(-1), pred.reshape(-1), fields)
    mse = float(np.mean((pred - true) ** 2))
    return {"field_rce": rce, "mse": mse}


# ---------------------------------------------------------------------------
# Method evaluation (paper Fig. 4 / Tables 2-3 protocol)
# ---------------------------------------------------------------------------


def _realized(exp: Experiment, decisions: np.ndarray) -> tuple[float, float]:
    rev = exp.revenue_eval[np.arange(len(decisions)), decisions].sum()
    spend = exp.chains.costs[decisions].sum()
    return float(rev), float(spend)


def budget_at(exp: Experiment, frac: float, n: int | None = None) -> float:
    """Budget at `frac` of the FEASIBLE range [floor, max]: Eq. 3b serves
    every request one chain, so n*min(c) is the spend floor."""
    chains = exp.chains
    n = n if n is not None else exp.revenue_eval.shape[0]
    floor = chains.costs.min() * n
    return float(floor + frac * (chains.costs.max() * n - floor))


def evaluate_methods(exp: Experiment, budgets_frac=(0.3, 0.5, 0.7, 0.9),
                     *, rewards_pred: np.ndarray | None = None,
                     stage_rewards: list | None = None) -> list[dict]:
    """Evaluate all methods at budgets over the feasible [floor, max]."""
    chains = exp.chains
    n = exp.revenue_eval.shape[0]
    rows = []
    for frac in budgets_frac:
        budget = budget_at(exp, frac)
        row = {"budget_frac": frac, "budget_flops": budget}

        # oracle: allocate on TRUE revenue (upper bound)
        lam = dual_bisect(jnp.asarray(exp.revenue_eval),
                          jnp.asarray(chains.costs, jnp.float32), budget)
        dec = np.asarray(allocate(jnp.asarray(exp.revenue_eval),
                                  jnp.asarray(chains.costs, jnp.float32),
                                  lam))
        row["oracle"], row["oracle_spend"] = _realized(exp, dec)

        # GreenFlow: allocate on the reward model's predictions
        if rewards_pred is not None:
            lam = dual_bisect(jnp.asarray(rewards_pred),
                              jnp.asarray(chains.costs, jnp.float32), budget)
            dec = np.asarray(allocate(jnp.asarray(rewards_pred),
                                      jnp.asarray(chains.costs, jnp.float32),
                                      lam))
            row["greenflow"], row["greenflow_spend"] = _realized(exp, dec)

        # EQUAL-DIN / EQUAL-DIEN
        for mname in ("DIN", "DIEN"):
            j = equal_allocation(chains, budget, n, rank_model=mname)
            dec = np.full(n, j, np.int32)
            row[f"equal_{mname.lower()}"], _ = _realized(exp, dec)

        # CRAS-DIN / CRAS-DIEN / CRAS-both
        if stage_rewards is not None:
            for mname in ("DIN", "DIEN", None):
                key = f"cras_{mname.lower()}" if mname else "cras_both"
                spaces = [StageActionSpace.from_chains(chains, k)
                          for k in range(chains.n_stages)]
                dec = cras_allocation(stage_rewards, spaces, chains, budget,
                                      rank_model=mname)
                row[key], _ = _realized(exp, dec)
        rows.append(row)
    return rows


def serve_config(*, small: bool = False) -> ExperimentConfig:
    """The serving demo/benchmark world (launch/serve.py --small flag)."""
    return ExperimentConfig(
        world=WorldConfig(n_users=800 if small else 2000,
                          n_items=200 if small else 400,
                          hist_len=10, seed=11),
        expose=8, n_scales=4,
        cascade_steps=100 if small else 200,
        reward_steps=200 if small else 400, batch=48)


def _source_digest() -> str:
    """Hash of this package's Python sources, so that a pickled
    experiment is only ever read back by the code that built it."""
    import hashlib
    import os

    root = os.path.dirname(os.path.abspath(__file__))
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for name in sorted(filenames):
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                h.update(os.path.relpath(path, root).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
    return h.hexdigest()[:16]


def build_serving_stack(cfg: ExperimentConfig | None = None, *,
                        small: bool = False, cache: bool = True,
                        verbose: bool = False):
    """Experiment + trained reward model + CascadeServer over the eval
    users - the serving universe shared by ``launch/serve.py`` and
    ``benchmarks/bench_serve.py``.  Returns (exp, server, params, rcfg).

    The built experiment (not the reward model - it trains in seconds)
    is pickled under results/cache, keyed by every size-relevant field
    and by a hash of the package's sources; ``cache=False`` builds from
    the seed and touches no file.
    """
    import os
    import pickle

    from repro.cascade.engine import CascadeServer, precompute_stage_scores

    cfg = cfg or serve_config(small=small)
    exp = None
    path = None
    if cache:
        w = cfg.world
        key = (f"serve_u{w.n_users}_i{w.n_items}_h{w.hist_len}"
               f"_ws{w.seed}_s{cfg.seed}_c{cfg.cascade_steps}"
               f"_e{cfg.expose}_ns{cfg.n_scales}_b{cfg.batch}"
               f"_r{cfg.reward_steps}_src{_source_digest()}.pkl")
        root = os.path.join(os.path.dirname(__file__), "..", "..",
                            "results", "cache")
        os.makedirs(root, exist_ok=True)
        path = os.path.join(root, key)
        if os.path.exists(path):
            with open(path, "rb") as f:
                exp = pickle.load(f)
    if exp is None:
        exp = build_experiment(cfg, verbose=verbose)
        if path is not None:
            with open(path, "wb") as f:
                pickle.dump(exp, f)
    params, rcfg = train_reward_model(exp)
    scores = precompute_stage_scores(exp.models, exp.world,
                                     exp.split.final_eval)
    server = CascadeServer(stage_scores=scores, chains=exp.chains,
                           clicks=exp.clicks_eval, expose=cfg.expose)
    return exp, server, params, rcfg


def cras_stage_rewards(exp: Experiment, ctx_users: str = "eval") -> list:
    """Per-stage independent reward estimates (Yang et al. 2021 setup):
    stage-action value = mean true revenue over chains sharing the action,
    estimated from the REWARD-TRAIN users and applied per-request via a
    nearest-context lookup (an honest, simple per-stage estimator)."""
    chains = exp.chains
    rev_tr = exp.revenue_reward  # (U_tr, J)
    ctx_tr = exp.ctx_reward
    ctx_ev = exp.ctx_eval if ctx_users == "eval" else ctx_tr
    # nearest training user by context (cheap kNN, k=8)
    d = ((ctx_ev[:, None, :] - ctx_tr[None, :, :]) ** 2).sum(-1)
    nn = np.argsort(d, axis=1)[:, :8]  # (U_ev, 8)
    rev_ev_est = rev_tr[nn].mean(axis=1)  # (U_ev, J)

    out = []
    for k in range(chains.n_stages):
        sp = StageActionSpace.from_chains(chains, k)
        cols = []
        for a in range(len(sp.costs)):
            mi, si = sp.actions[a]
            mask = (chains.chain_idx[:, k, 0] == mi) & \
                   (chains.chain_idx[:, k, 1] == si)
            cols.append(rev_ev_est[:, mask].mean(axis=1))
        out.append(jnp.asarray(np.stack(cols, axis=1), jnp.float32))
    return out
