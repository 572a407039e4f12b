"""Build one cell's system under test from its configuration and traffic
files: the chain set, the reward model with seeded weights, the replay
tables and their request source, the tenant x region constraint spec
with its per-window budget and cost-scale traces, and the
``ServingPipeline`` over the source.

Everything is found by name: ``bench/configs/<config>.json`` and
``bench/traffic/<traffic>.json``.  The program is imported from
``src/``; nothing here changes it.
"""
from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass

import numpy as np

from bench import weights

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")

# paper constants behind repro.core.pfec.kwh_per_flop (Eq. 1): PUE,
# device powers in W, the sustained FLOP/s that converts FLOPs into
# device-hours, and the share of those hours billed to RAM and CPU
_PUE, _P_GPU_W, _P_CPU_W, _P_RAM_W = 1.67, 250.0, 105.0, 20.0
_SUSTAINED_FLOPS, _RAM_CPU_FRAC = 2.0e13, 0.15
DAY_S = 86400.0


def load(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``."""
    with open(os.path.join(BENCH, kind, f"{name}.json")) as f:
        return json.load(f)


def workload(name: str) -> dict:
    """The ``workloads`` entry of ``BENCHMARK.json`` called ``name``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


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


def chain_set(cfg: dict):
    from repro.core.action_chain import (generate_action_chains,
                                         paper_stage_specs)

    ch = cfg["chains"]
    f = ch["table1_flops"]
    return generate_action_chains(paper_stage_specs(
        dssm_flops=f["DSSM"], ydnn_flops=f["YDNN"], din_flops=f["DIN"],
        dien_flops=f["DIEN"], n2=tuple(ch["n2"]), n3=tuple(ch["n3"]),
        q=ch["q"]))


def reward_model(cfg: dict, chains, d_context: int, seed: int):
    """(params, RewardModelConfig): widths from the configuration,
    weights from ``seed``, the configuration's constant ``label_norm``."""
    import jax
    import jax.numpy as jnp

    from repro.core.reward_model import RewardModelConfig, reward_model_init

    r = cfg["reward"]
    rcfg = RewardModelConfig(
        n_stages=chains.n_stages, max_models=r["max_models"],
        n_scale_groups=r["n_scale_groups"], d_context=d_context,
        d_feature=r["d_feature"], d_hidden=r["d_hidden"],
        d_state=r["d_state"], d_model_emb=r["d_model_emb"],
        encoder_hidden=tuple(r["encoder_hidden"]))
    shapes = jax.eval_shape(lambda key: reward_model_init(key, rcfg),
                            jax.random.key(0))
    params = dict(weights.make(shapes, seed,
                               embed_std=cfg["assumed"]["embed_std"]))
    params["label_norm"] = jnp.full(chains.n_chains,
                                    cfg["assumed"]["label_norm"],
                                    jnp.float32)
    return params, rcfg


def d_context(cfg: dict) -> int:
    w = cfg["world"]
    return 3 + w["n_user_fields"] + w["d_latent"]


def replay_tables(cfg: dict, chains, users: int, seed: int):
    """Per-user replay tables in the ``build_compact_layout`` format,
    made on the device in one call from ``seed`` and returned on the
    host: contexts (U, d_context) float32, ``p`` (G, U, cap) int32 (each
    row a permutation of the cap survivor positions in rank-model
    order) and ``ck`` (G, U, cap) float32 clicks, whose rate falls with
    the prerank position and rises with the user's propensity."""
    import jax
    import jax.numpy as jnp

    from repro.cascade.engine import build_compact_layout

    lay = build_compact_layout(chains, n_items=cfg["world"]["n_items"],
                               expose=cfg["chains"]["expose"])
    g_n, cap = lay.p_sorted.shape[0], lay.cap
    n_f, n_z = cfg["world"]["n_user_fields"], cfg["world"]["d_latent"]

    def draw(key):
        k = jax.random.split(key, 6)
        act = jnp.exp(jax.random.normal(k[0], (users,)))
        hist = jax.random.uniform(k[1], (users, 1))
        fields = jax.random.randint(k[2], (users, n_f), 0, 64) / 64.0
        taste = jnp.abs(jax.random.normal(k[3], (users, n_z))) / 4.0
        ctx = jnp.concatenate([jnp.log1p(act)[:, None],
                               jnp.tanh(act)[:, None], hist, fields,
                               taste], axis=1)
        pos = jnp.arange(cap, dtype=jnp.float32)
        noise = 0.3 * cap * jax.random.normal(k[4], (g_n, users, cap))
        p = jnp.argsort(pos + noise, axis=-1).astype(jnp.int32)
        logit = (jnp.log(act)[None, :, None] - 0.5
                 - 3.0 * p.astype(jnp.float32) / cap)
        ck = (jax.random.uniform(k[5], (g_n, users, cap))
              < jax.nn.sigmoid(logit)).astype(jnp.float32)
        return ctx.astype(jnp.float32), p, ck

    ctx, p, ck = jax.jit(draw)(weights.key_of(seed))
    out = np.asarray(ctx), np.asarray(p), np.asarray(ck)
    del ctx, p, ck
    return out


@dataclass
class Stack:
    """One cell's system under test, ready to serve."""

    cfg: dict
    traffic: dict
    chains: object
    source: object
    pipe: object
    reward_params: dict
    forecast: bool  # the nearline update aims at the next window
    day_ci: np.ndarray  # (windows_per_day, R) grid intensity
    budget_row: np.ndarray  # (T + R,) grams of a window per axis
    replay: tuple  # (ctx, p, ck) the replayed tables

    def release(self) -> None:
        """Drop the program's state (pipeline, source and their device
        buffers) once the timed window is over; the reference keeps the
        weights and tables the benchmark made."""
        import gc

        self.pipe = self.source = None
        gc.collect()

    def traces(self, first: int, count: int):
        """(budget_trace, scale_trace) for windows first..first+count-1
        of the run: the CI day repeats every ``windows_per_day``
        windows."""
        idx = (first + np.arange(count)) % len(self.day_ci)
        scale = grams_per_flop(1.0) * self.day_ci[idx]
        budget = np.broadcast_to(self.budget_row,
                                 (count, len(self.budget_row)))
        return budget, scale


def build(cfg: dict, traffic: dict, *, seed: int, chips: int, obs=None
          ) -> Stack:
    from repro.core.primal_dual import DualDescentConfig
    from repro.serving.pipeline import ServingPipeline
    from repro.serving.spec import (ConstraintSpec, GlobalAxis, RegionAxis,
                                    TenantAxis)

    chains = chain_set(cfg)
    expose = cfg["chains"]["expose"]
    n_items = cfg["world"]["n_items"]
    n = int(traffic["window"])
    if traffic["source"] != "replay":
        raise ValueError(f"unknown source {traffic['source']!r}")
    from repro.data.request_source import TableReplaySource

    replay = replay_tables(cfg, chains, traffic["users"], seed)
    source = TableReplaySource(*replay, chains, n_items=n_items,
                               expose=expose, seed=seed, device_tables=True)
    rparams, rcfg = reward_model(cfg, chains, d_context(cfg), seed)

    sp = cfg["spec"]
    c_max = float(chains.costs.max())
    flops_budget = sp["budget_frac"] * c_max * n
    if sp["kind"] != "geotenants":
        raise ValueError(f"unknown spec kind {sp['kind']!r}")
    day_ci = region_ci(sp)
    g_total = flops_budget * grams_per_flop(sp["ci_mean"])
    w = np.linspace(1.0, sp["tenant_spread"], sp["tenants"])
    tenant_g = g_total * w / w.sum()
    region_g = np.full(len(sp["regions"]), sp["region_cap_frac"] * g_total)
    budget_row = np.concatenate([tenant_g, region_g])
    spec = ConstraintSpec([
        TenantAxis(tuple(tenant_g), priced=sp["tenant_priced"]),
        RegionAxis(len(sp["regions"]), names=tuple(sp["regions"]),
                   split=sp["region_split"]),
        GlobalAxis(pricing="carbon")])
    mesh = None
    if chips > 1:
        from repro.launch.mesh import make_request_mesh

        mesh = make_request_mesh(chips)
    pipe = ServingPipeline.from_spec(
        source.universe, rparams, rcfg, spec,
        dual_cfg=DualDescentConfig(**cfg["dual"]), mesh=mesh, obs=obs)
    return Stack(cfg, traffic, chains, source, pipe, rparams,
                 bool(sp["ci_forecast"]), day_ci, budget_row, replay)
