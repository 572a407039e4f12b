"""Replayed per-user tables (traffic ``"source": "replay"``): a cascade
ran upstream, and each request replays one of ``users`` users' contexts
and execution tables, drawn on the device from the seed.

The program serves them through ``repro.data.request_source.
TableReplaySource`` with its tables on the device; the reference reads
the same host tables: the contexts its reward model scores, and the
clicks each served chain earns.
"""
from __future__ import annotations

import numpy as np

from bench import weights, work
from bench.reference import cascade


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


def cpu_cut(cfg: dict, traffic: dict) -> None:
    """The mix at a size a CPU test holds: 512 replayed users."""
    traffic.update(users=512)


class Source:
    """The replayed tables, the program's source over them, and what
    the reference reads from them."""

    def __init__(self, cfg: dict, traffic: dict, chains, *, seed: int):
        from repro.data.request_source import TableReplaySource

        self.cfg = cfg
        self.tables = replay_tables(cfg, chains, traffic["users"], seed)
        self.program = TableReplaySource(
            *self.tables, chains, n_items=cfg["world"]["n_items"],
            expose=cfg["chains"]["expose"], seed=seed, device_tables=True)

    def release(self) -> None:
        """Drop the program's source and its device tables; the host
        tables stay for the reference."""
        self.program = None

    def contexts(self, users) -> np.ndarray:
        """(n, d_context) float32 contexts of ``users``."""
        return self.tables[0][np.asarray(users)]

    def revenue(self, ch, users, decisions) -> np.ndarray:
        """(n,) clicks that chain ``decisions[i]`` of the reference's
        chain set ``ch`` earns on ``users[i]``'s tables."""
        _, p, ck = self.tables
        users = np.asarray(users)
        n2_list = sorted(set(int(x) for x in ch.n2))
        return cascade.table_revenue(ch, n2_list, p[:, users], ck[:, users],
                                     decisions)

    def required_flops(self, windows) -> float:
        """The model work the served requests need (``bench.work``):
        the tables come from a cascade that ran upstream, so each
        request needs the reward model over every chain."""
        return float(sum(w.n for w in windows)) \
            * work.reward_request(self.cfg)
