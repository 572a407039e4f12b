"""Is what the timed path served correct?  Compared with the plain
reference (``bench/reference``), once the timed window has closed.

Numbers, each against the limit in ``bench/limits/<cell>.json`` where
that file lists it (the readings each limit was set from are recorded
there too); the others are printed as readings.  Every cell has:

- ``decision_regret``, ``decision_flips``, ``decision_gap``: every
  served window, over the requests off the cheapest chain (the guard's
  target, so a request the guard downgraded is never judged) - the
  reference reward model's priced reward of the served chain against
  the best chain's, at the price the window was served with: the summed
  shortfall over the summed best reward, the share of requests that
  fell short, and the widest shortfall over its window's mean best
  reward;
- ``lam_err``: sampled windows - the published price against Algorithm
  1 run by the reference from the same entry price on the reference's
  rewards, against the budget and grams per FLOP the update aims at
  (the next window's, with the CI forecast), relative to the largest
  move of the update;
- ``price_stuck``: sampled windows - the share whose published price is
  bit for bit the price they were served with;
- ``revenue_exec``: sampled windows - served clicks against the clicks
  the served chain earns, as the cell's request source computes them.

The cell's budget spec adds its own numbers over every served window
(``window_numbers`` of ``bench/specs/<kind>.py``), and its request
source may add more (``numbers``).  The reference's contexts and clicks
come from the source module, the prices each request faced and
Algorithm 1 from the spec module.

Sampled windows are drawn from the seed among the windows served.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field, replace

import numpy as np

from bench.build import BENCH
from bench.reference import alloc
from bench.reference import chains as ref_chains

SAMPLE_WINDOWS = 4
BLOCK = 8  # windows per call of the reference reward model


@dataclass
class Verdict:
    values: dict = field(default_factory=dict)  # name -> (value, limit)
    notes: list = field(default_factory=list)

    @property
    def correct(self) -> bool:
        return bool(self.values) and all(
            np.isfinite(v) and v <= lim for v, lim in self.values.values())

    def lines(self) -> list[str]:
        out = list(self.notes)
        for name, (v, lim) in self.values.items():
            ok = "ok" if np.isfinite(v) and v <= lim else "FAIL"
            out.append(f"check {name} {float(v)!r} limit {float(lim)!r} {ok}")
        out.append(f"check correct {self.correct}")
        return out

    def numbers(self) -> dict:
        return {k: {"value": float(v), "limit": float(lim)}
                for k, (v, lim) in self.values.items()}


def limits(cell: str) -> dict:
    with open(os.path.join(BENCH, "limits", f"{cell}.json")) as f:
        entries = json.load(f)["limits"]
    return {k: float(v["limit"]) for k, v in entries.items()}


def verdict(values: dict, lim: dict, notes=()) -> Verdict:
    """The numbers the limits list, held to them; the rest as readings."""
    v = Verdict(notes=list(notes))
    for name, value in values.items():
        if name in lim:
            v.values[name] = (value, lim[name])
        else:  # read for the record, not compared (PERF.md says why)
            v.notes.append(f"reading {name} {float(value)!r}")
    return v


@dataclass
class Served:
    """One window as served: by the program, or by the control."""

    k: int
    users: np.ndarray
    decisions: np.ndarray  # (n,) chain ids
    regions: np.ndarray
    revenue: np.ndarray  # (n,)
    lam_before: np.ndarray
    lam_after: np.ndarray
    spend: np.ndarray | None = None  # reported by the program


def from_program(w, spec) -> Served:
    res = w.result
    return Served(k=w.k, users=w.users, decisions=w.decisions,
                  regions=w.regions, revenue=w.revenue,
                  lam_before=np.asarray(res.lam_before, np.float64),
                  lam_after=np.asarray(res.lam_after, np.float64),
                  spend=spec.reported_spend(res))


class Reference:
    """The reference's view of a cell: chains, rewards, inputs."""

    def __init__(self, cfg: dict, traffic: dict, stack):
        self.cfg = cfg
        self.traffic = traffic
        self.stack = stack
        self.ch = ref_chains.chains(cfg)
        self.spec = stack.spec_reference(cfg, self.ch)
        self._fns = {}

    def window_terms(self, k: int):
        """(budget, scales) of run window k and of the window its
        nearline update aims at (the next one, with the CI forecast)."""
        st = self.stack.spec
        bud, sc = st.traces(k, 2)
        nxt = 1 if st.forecast else 0
        return (bud[0], sc[0]), (bud[nxt], sc[nxt])

    # -- reference computations -----------------------------------------

    def _reward_fn(self, control: bool):
        if control not in self._fns:
            import jax
            import jax.numpy as jnp

            from bench.reference import models

            pr = models.Precision(control)
            oh = jnp.asarray(self.ch.onehot)
            mh = jnp.asarray(self.ch.multihot)
            self._fns[control] = jax.jit(
                lambda p, c: models.reward(pr, p, c, oh, mh))
        return self._fns[control]

    def rewards(self, windows: list, control: bool = False):
        """Yields (window, (n, J) float32 reference rewards), BLOCK
        windows to a call of the reward model."""
        import jax.numpy as jnp

        fn = self._reward_fn(control)
        source = self.stack.source
        for lo in range(0, len(windows), BLOCK):
            block = windows[lo:lo + BLOCK]
            ctx = np.concatenate([source.contexts(w.users) for w in block])
            r = np.asarray(fn(self.stack.reward_params, jnp.asarray(ctx)))
            at = 0
            for w in block:
                n = len(w.decisions)
                yield w, r[at:at + n]
                at += n

    def price_per_flop(self, w, n: int) -> np.ndarray:
        """The per-FLOP price each request's chain choice faced."""
        (_, sc), _ = self.window_terms(w.k)
        return self.spec.price_per_flop(w.lam_before, sc, n)

    def decisions_at_price(self, w, rewards) -> np.ndarray:
        """Eq. 10 on ``rewards`` at the price window ``w`` was served
        with: what a program computing these rewards would serve."""
        price = self.price_per_flop(w, len(rewards))
        obj = np.asarray(rewards, np.float64) \
            - price[:, None] * self.ch.costs[None]
        return np.argmax(obj, axis=1)

    def decision_terms(self, w, rewards) -> dict:
        """Sums over window ``w``'s requests off the cheapest chain."""
        d = np.asarray(w.decisions)
        gaps = alloc.chain_gaps(rewards, d, self.price_per_flop(w, len(d)),
                                self.ch.costs)
        keep = d != self.ch.cheapest
        best = np.asarray(rewards, np.float64).max(1)
        return {"short": float(gaps[keep].sum()),
                "best": float(best[keep].sum()),
                "flips": int((gaps[keep] > 0).sum()),
                "kept": int(keep.sum()),
                "widest": (float(gaps[keep].max() / best.mean())
                           if keep.any() else None)}

    def nearline(self, w, rewards, fault: str | None = None) -> np.ndarray:
        """Algorithm 1 from ``w``'s entry price on ``rewards``, aimed as
        the configuration aims it; ``fault`` plants one error."""
        dual = dict(self.cfg["dual"])
        (bud, sc), (d_bud, d_sc) = self.window_terms(w.k)
        weight = np.ones(len(rewards), np.float32)
        if fault == "forecast_ignored":  # aims at this window's grams
            d_bud, d_sc = bud, sc
        elif fault == "half_window":  # every other request, counted twice
            weight[1::2], weight[::2] = 0.0, 2.0
        elif fault == "decay_default":  # DualDescentConfig's 0.999
            dual["step_decay"] = 0.999
        return self.spec.dual_update(rewards, weight, w.lam_before, d_bud,
                                     d_sc, dual)

    def revenue_exec(self, w) -> float:
        want = self.stack.source.revenue(self.ch, w.users, w.decisions)
        return float(np.max(np.abs(np.asarray(w.revenue) - want)))


def lam_err(got, want, before) -> float:
    """max_k |got_k - want_k| over the largest move of the reference's
    update, max_k |want_k - before_k|."""
    got, want, before = (np.asarray(x, np.float64)
                         for x in (got, want, before))
    move = float(np.max(np.abs(want - before)))
    scale = max(move, 1e-6 * float(np.max(np.abs(want))), 1e-30)
    return float(np.max(np.abs(got - want))) / scale


def sample(windows: list, seed: int) -> list[int]:
    """Indices of the windows drawn from the seed among those served."""
    rng = np.random.default_rng((seed, 0xC4EC))
    k = min(SAMPLE_WINDOWS, len(windows))
    return sorted(int(i) for i in rng.choice(len(windows), k,
                                             replace=False))


def _decision_numbers(terms: list) -> dict:
    best = sum(t["best"] for t in terms)
    kept = sum(t["kept"] for t in terms)
    widest = [t["widest"] for t in terms if t["widest"] is not None]
    # nothing off the cheapest chain in any window: nothing to judge
    return {"decision_regret": (sum(t["short"] for t in terms) / best
                                if kept else float("inf")),
            "decision_flips": (sum(t["flips"] for t in terms) / kept
                               if kept else float("inf")),
            "decision_gap": max(widest) if widest else float("inf")}


def compare(ref: Reference, windows: list, seed: int) -> dict:
    """name -> value for the served ``windows``."""
    out = {}  # the spec's worst cases over every window
    picked = set(sample(windows, seed))
    terms, lam, rev = [], 0.0, 0.0
    for i, (w, r) in enumerate(ref.rewards(windows)):
        (bud, sc), _ = ref.window_terms(w.k)
        for name, v in ref.spec.window_numbers(w, r, bud, sc).items():
            out[name] = max(out.get(name, 0.0), v)
        terms.append(ref.decision_terms(w, r))
        if i in picked:
            lam = max(lam, lam_err(w.lam_after, ref.nearline(w, r),
                                   w.lam_before))
            rev = max(rev, ref.revenue_exec(w))
    out.update(_decision_numbers(terms))
    out["lam_err"] = lam
    sampled = [windows[i] for i in sorted(picked)]
    out["price_stuck"] = float(np.mean([
        np.array_equal(w.lam_before, w.lam_after) for w in sampled]))
    out["revenue_exec"] = rev
    return out


def run(run, seed: int) -> Verdict:
    """The verdict on one timed run (``measure.Run``)."""
    ref = Reference(run.cfg, run.traffic, run.stack)
    served = [from_program(w, run.stack.spec) for w in run.windows]
    run.stack.release()
    values = compare(ref, served, seed)
    own = getattr(run.stack.source, "numbers", None)
    if own is not None:
        values.update(own(run, seed))
    return verdict(values, limits(run.cell["name"]),
                   [f"check compiles_in_window {run.compiles}"])


FAULTS = ("forecast_ignored", "half_window", "decay_default")


def control(run, seed: int) -> dict:
    """The control's numbers: the reference at the control precision
    (float8 matmul operands) put in the program's place on the same
    windows - same requests, same entry prices - and compared with the
    float32 reference exactly as the program is; and ``lam_err`` of the
    reference's own update with each of ``FAULTS`` planted."""
    ref = Reference(run.cfg, run.traffic, run.stack)
    served = [from_program(w, run.stack.spec) for w in run.windows]
    picked = set(sample(served, seed))
    terms, lam = [], 0.0
    faults = {f: 0.0 for f in FAULTS}
    pairs = zip(ref.rewards(served), ref.rewards(served, control=True))
    for i, ((w, r), (_, rc)) in enumerate(pairs):
        terms.append(ref.decision_terms(
            replace(w, decisions=ref.decisions_at_price(w, rc)), r))
        if i in picked:
            want = ref.nearline(w, r)
            lam = max(lam, lam_err(ref.nearline(w, rc), want, w.lam_before))
            for f in FAULTS:
                faults[f] = max(faults[f], lam_err(
                    ref.nearline(w, r, fault=f), want, w.lam_before))
    out = _decision_numbers(terms)
    out["lam_err"] = lam
    out["faults"] = {f"lam_err.{f}": v for f, v in faults.items()}
    return out
