"""The chain space of a configuration, enumerated from its file alone.

A chain is (recall DSSM over n1, prerank YDNN keeping n2, rank model m
keeping n3, exposing e).  Chain ids follow the paper's enumeration, the
order the program's decisions index: n2 outermost, then the rank model
in the configuration's order (DIN, DIEN), then n3.  A chain's cost is
Table 1's per-item FLOPs times the items each stage scores.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

RANK_MODELS = ("DIN", "DIEN")


@dataclass(frozen=True)
class Chains:
    n2: np.ndarray  # (J,) items the prerank stage keeps
    model: np.ndarray  # (J,) rank model index into RANK_MODELS
    n3: np.ndarray  # (J,) items the rank stage keeps
    costs: np.ndarray  # (J,) float64 FLOPs (Table 1 grain)
    multihot: np.ndarray  # (J, 3, Q) scale code per stage
    onehot: np.ndarray  # (J, 3, 2) model code per stage
    expose: int
    n1: int

    @property
    def n(self) -> int:
        return len(self.costs)

    @property
    def cheapest(self) -> int:
        return int(np.argmin(self.costs))


def _group(idx: int, n_scales: int, q: int) -> int:
    return min(q - 1, idx * q // max(1, n_scales))


def chains(cfg: dict) -> Chains:
    ch = cfg["chains"]
    f = ch["table1_flops"]
    q = ch["q"]
    rows = []
    for i2, n2 in enumerate(ch["n2"]):
        for m in range(len(RANK_MODELS)):
            for i3, n3 in enumerate(ch["n3"]):
                if n3 > n2:
                    continue
                rows.append((i2, n2, m, i3, n3))
    j_n = len(rows)
    multihot = np.zeros((j_n, 3, q), np.float32)
    onehot = np.zeros((j_n, 3, 2), np.float32)
    costs = np.zeros(j_n)
    for j, (i2, n2, m, i3, n3) in enumerate(rows):
        multihot[j, 0, :1] = 1.0  # recall: one scale, group 0
        multihot[j, 1, :_group(i2, len(ch["n2"]), q) + 1] = 1.0
        multihot[j, 2, :_group(i3, len(ch["n3"]), q) + 1] = 1.0
        onehot[j, 0, 0] = onehot[j, 1, 0] = 1.0
        onehot[j, 2, m] = 1.0
        costs[j] = (f["DSSM"] * ch["n1"] + f["YDNN"] * n2
                    + f[RANK_MODELS[m]] * n3)
    arr = np.asarray(rows)
    return Chains(n2=arr[:, 1], model=arr[:, 2], n3=arr[:, 4], costs=costs,
                  multihot=multihot, onehot=onehot, expose=ch["expose"],
                  n1=ch["n1"])
