"""Build one cell's system under test from its configuration and traffic
files: the chain set, the reward model with seeded weights, the cell's
request source and budget spec, and the ``ServingPipeline`` over them.

Everything is found by name: ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, and the two modules the cell names -
its request source, ``bench/sources/<traffic["source"]>.py``, and its
budget spec, ``bench/specs/<cfg["spec"]["kind"]>.py``:

- a source module has ``Source(cfg, traffic, chains, *, seed)`` with
  ``program`` (the program's ``RequestSource`` to serve),
  ``contexts(users)`` (the (n, d_context) float32 host contexts the
  reference's reward model scores), ``revenue(ch, users, decisions)``
  (the reference's clicks of the served chains), ``required_flops(
  windows)`` (the model work the served requests need) and
  ``release()`` (drops the program's part once the window has closed),
  optionally ``numbers(run, seed)`` (further compared numbers, each
  with its limit in ``bench/limits/<cell>.json``); and
  ``cpu_cut(cfg, traffic)``, which cuts the cell in place to a size a
  CPU test holds;
- a spec module has ``Spec(cfg, chains, window)`` with ``constraint``
  (the program's ``ConstraintSpec``), ``forecast``, ``traces(first,
  count)`` (the driver's budget and cost-scale traces) and
  ``reported_spend(result)``; and ``Reference(cfg, ch)``, the
  reference's side over its own chain set: ``price_per_flop(lam, sc,
  n)``, ``dual_update(rewards, weight, lam0, bud, sc, dual)`` and
  ``window_numbers(w, rewards, bud, sc)``; and ``cpu_cut(cfg)``.

The program is imported from ``src/``; nothing here changes it.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass

from bench import weights

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


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


def module(kind: str, name: str, where: str = BENCH):
    """``<where>/<kind>/<name>.py`` imported: a request source (``kind``
    "sources") or a budget spec ("specs").  Exits non-zero, naming the
    file to add, where there is none."""
    path = os.path.join(where, kind, f"{name}.py")
    if not os.path.isfile(path):
        shown = os.path.relpath(path, ROOT) if where == BENCH else path
        raise SystemExit(f"bench: no {kind} module {name!r}: add {shown}")
    label = f"bench_{kind}_{name}"
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[label] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


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


@dataclass
class Stack:
    """One cell's system under test, ready to serve."""

    cfg: dict
    traffic: dict
    chains: object
    source: object  # the source module's Source
    spec: object  # the spec module's Spec
    spec_reference: type  # the spec module's Reference
    pipe: object
    reward_params: dict

    def release(self) -> None:
        """Drop the program's state (pipeline, source and their device
        buffers) once the timed window is over; the reference keeps the
        weights and tables the benchmark made."""
        import gc

        self.pipe = None
        self.source.release()
        gc.collect()


def build(cfg: dict, traffic: dict, *, seed: int, chips: int, obs=None,
          where: str = BENCH) -> Stack:
    """The cell's stack; its source and spec modules are found under
    ``where``."""
    from repro.core.primal_dual import DualDescentConfig
    from repro.serving.pipeline import ServingPipeline

    sources = module("sources", traffic["source"], where)
    specs = module("specs", cfg["spec"]["kind"], where)
    chains = chain_set(cfg)
    source = sources.Source(cfg, traffic, chains, seed=seed)
    rparams, rcfg = reward_model(cfg, chains, d_context(cfg), seed)
    spec = specs.Spec(cfg, chains, int(traffic["window"]))
    mesh = None
    if chips > 1:
        from repro.launch.mesh import make_request_mesh

        mesh = make_request_mesh(chips)
    pipe = ServingPipeline.from_spec(
        source.program.universe, rparams, rcfg, spec.constraint,
        dual_cfg=DualDescentConfig(**cfg["dual"]), mesh=mesh, obs=obs)
    return Stack(cfg, traffic, chains, source, spec, specs.Reference, pipe,
                 rparams)
