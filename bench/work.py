"""Floating-point operations the served decisions require, counted from
the configuration's widths (a multiply-add is 2 operations).

Required work is what the chosen chain needs, not what the program
happens to compute: each stage model on the item count the chain gives
it (recall on n1, prerank on n2, the rank model on n3), DIEN's interest
GRU once per user, corpus-side towers (DSSM's item tower) not at all,
and the reward model once per request, sharing its stage cells across
chains with the same model prefix (the dedup ``reward_matrix_grouped``
relies on; the per-chain head still runs for every chain).  Replay cells
serve tables a cascade computed elsewhere, so their requests require
the reward model only (``reward_request``); ``chain_request`` counts
what a cell that runs the cascade itself would add per served chain.
"""
from __future__ import annotations

import numpy as np

from bench.reference import chains as ref_chains


def dense(d_in: int, d_out: int, bias: bool = True) -> float:
    return 2.0 * d_in * d_out + (d_out if bias else 0)


def mlp(dims) -> float:
    return sum(dense(a, b) for a, b in zip(dims[:-1], dims[1:]))


def gru(seq: int, d_in: int, d_h: int) -> float:
    """Three gates of input and recurrent matmuls plus ~9 elementwise
    operations per hidden unit, per step."""
    return (3 * (dense(d_in, d_h) + dense(d_h, d_h)) + 9.0 * d_h) * seq


def din_item(c: dict) -> float:
    """DIN, one candidate: 100-step target attention MLP and pooling,
    then the 200-80 head."""
    d = 2 * c["embed_dim"]
    t = c["seq_len"]
    attn = t * (mlp([4 * d, *c["attn_hidden"], 1]) + 4 * d)
    pool = dense(t, 1, bias=False) * d
    head = mlp([c["n_user_fields"] * c["embed_dim"] + 2 * d,
                *c["mlp_hidden"], 1])
    return attn + pool + head


def dien_user(c: dict) -> float:
    """DIEN's interest-extractor GRU, once per user."""
    d = 2 * c["embed_dim"]
    return gru(c["seq_len"], d, d)


def dien_item(c: dict) -> float:
    """DIEN, one candidate: attention over the GRU states, the AUGRU
    evolution and the head."""
    d = 2 * c["embed_dim"]
    t = c["seq_len"]
    attn = t * (mlp([4 * d, *c["attn_hidden"], 1]) + 4 * d)
    head = mlp([c["n_user_fields"] * c["embed_dim"] + 2 * d,
                *c["mlp_hidden"], 1])
    return attn + gru(t, d, d) + head


def dssm_request(c: dict, n_items: int) -> float:
    """User tower once, one d_out dot per recalled item."""
    tower = mlp([c["n_user_fields"] * c["embed_dim"], *c["hidden"],
                 c["d_out"]])
    return tower + n_items * dense(c["d_out"], 1, bias=False)


def ydnn_request(c: dict, n_items: int) -> float:
    """Mean-pooled history, user tower once, one dot per item."""
    tower = mlp([c["embed_dim"] + c["n_user_fields"] * c["embed_dim"],
                 *c["hidden"], c["d_out"]])
    return (tower + c["hist_len"] * c["embed_dim"]
            + n_items * dense(c["d_out"], 1, bias=False))


def reward_request(cfg: dict) -> float:
    """Reward model over all J chains for one request, cells shared by
    model prefix: 1 recall prefix, 1 prerank prefix, 2 rank prefixes."""
    r = cfg["reward"]
    ch = ref_chains.chains(cfg)
    d_ctx = 3 + cfg["world"]["n_user_fields"] + cfg["world"]["d_latent"]
    enc = mlp([d_ctx, *r["encoder_hidden"], r["d_feature"]])
    q, p = r["n_scale_groups"], 5  # five basis functions
    d_in = r["d_state"] + r["d_feature"] + r["d_model_emb"]
    cell = (mlp([d_in, r["d_hidden"], r["d_hidden"]])
            + dense(r["d_hidden"], r["d_state"])
            + dense(r["d_hidden"], p) + dense(r["d_hidden"], p * q))
    prefixes = 1 + 1 + len(ref_chains.RANK_MODELS)
    head = 2.0 * p * q + 3.0 * p  # Eq. 6 einsum, bases, mixture
    return enc + prefixes * cell + ch.n * 3 * head


def chain_request(cfg: dict) -> np.ndarray:
    """(J,) stage-model FLOPs one request served by chain j requires."""
    ch = ref_chains.chains(cfg)
    din_i, dien_i = din_item(cfg["din"]), dien_item(cfg["dien"])
    rank = np.where(ch.model == 0, ch.n3 * din_i,
                    ch.n3 * dien_i + dien_user(cfg["dien"]))
    return (dssm_request(cfg["dssm"], ch.n1)
            + np.asarray([ydnn_request(cfg["ydnn"], int(n)) for n in ch.n2])
            + rank)
