"""The clicks a chain earns from its execution tables.

An execution table row (``build_compact_layout`` format) serves every
chain of one group g = (m, n2), g = m * len(n2 list) + index of n2: the
first ``cap`` YouTube-DNN survivors of the n2 recalled items, listed in
the rank model m's order, each entry holding its position in YouTube-DNN
order (``cap`` marks an empty slot) and its click.  Chain (g, n3)
exposes the first e entries whose position is below n3, and earns the
clicks among them.
"""
from __future__ import annotations

import numpy as np


def group_of(ch, n2_list) -> np.ndarray:
    pos = {n: i for i, n in enumerate(n2_list)}
    return np.asarray([int(ch.model[j]) * len(n2_list) + pos[int(ch.n2[j])]
                       for j in range(ch.n)])


def table_revenue(ch, n2_list, p, ck, chains=None):
    """Revenue of chains served from execution tables.

    ``p``/``ck``: (G, n, cap) tables of n users.  ``chains`` (n,): one
    chain per user -> (n,); None: every chain -> (n, J)."""
    p = np.asarray(p)
    ck = np.asarray(ck, np.float64)
    g = group_of(ch, n2_list)
    n3 = np.minimum(ch.n3, p.shape[-1])
    if chains is None:
        pg, cg = p[g], ck[g]  # (J, n, cap)
        keep = pg < n3[:, None, None]
        exposed = keep & (np.cumsum(keep, axis=-1) <= ch.expose)
        return (exposed * cg).sum(-1).T
    chains = np.asarray(chains)
    rows = np.arange(p.shape[1])
    pg, cg = p[g[chains], rows], ck[g[chains], rows]  # (n, cap)
    keep = pg < n3[chains][:, None]
    exposed = keep & (np.cumsum(keep, axis=-1) <= ch.expose)
    return (exposed * cg).sum(-1)
