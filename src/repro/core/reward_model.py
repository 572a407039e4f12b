"""Personalized reward model (paper §4.2).

Recursive multi-stage design:  R_ij = sum_k dr_k with
    (dr_k, h_k) = g_k(h_{k-1}, f_i, m_k, n_k)

Each stage cell g_k enforces the paper's three mechanisms:

  * Recursive multi-stage: h_k threads stage context downstream (Fig. 3).
  * Multi-basis functions (Eq. 5-7): dr_k = sum_p w_p * phi_p(v_p) with
    B = {tanh, ln, x/sqrt(1+x^2), sigmoid, x},  w = softmax(FNN_0(z)),
    v_p = 1_Q^T (softplus(FNN_p(z)) * n_multihot).
  * Monotonic constraint: the multi-hot scale code has more ones for larger
    n_k, softplus keeps the per-group contributions positive, every basis is
    increasing and w >= 0  =>  dr_k is non-decreasing in n_k.

Ablation switches (`recursive`, `multi_basis`) reproduce paper Table 4.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L

# ---------------------------------------------------------------------------
# Basis functions (paper Eq. 7).  ln -> ln(1+x) for x>=0 numerical safety;
# still increasing, concave, phi(0)=0 (see DESIGN.md §8).
# ---------------------------------------------------------------------------

BASIS_FUNCTIONS = (
    ("tanh", jnp.tanh),
    ("ln", jnp.log1p),
    ("rsqrt1p", lambda x: x * jax.lax.rsqrt(1.0 + x * x)),
    ("sigmoid", jax.nn.sigmoid),
    ("identity", lambda x: x),
)
N_BASIS = len(BASIS_FUNCTIONS)


def apply_bases(v: jnp.ndarray) -> jnp.ndarray:
    """v: (..., P) -> phi_p(v_p) stacked on the last axis, P == N_BASIS."""
    outs = [fn(v[..., p]) for p, (_, fn) in enumerate(BASIS_FUNCTIONS)]
    return jnp.stack(outs, axis=-1)


@dataclass(frozen=True)
class RewardModelConfig:
    n_stages: int  # K: decision stages
    max_models: int  # width of the per-stage model one-hot
    n_scale_groups: int  # Q
    d_context: int  # raw context feature dim fed to the encoder
    d_feature: int = 64  # encoded f_i dim
    d_hidden: int = 64  # trunk width inside each cell
    d_state: int = 32  # h_k carried between stages
    d_model_emb: int = 8  # model-instance embedding dim
    recursive: bool = True  # ablation: thread h_k between stages
    multi_basis: bool = True  # ablation: use Eq. 5-7 vs plain MLP head
    encoder_hidden: tuple = (128,)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _cell_init(key, cfg: RewardModelConfig) -> dict:
    d_in = cfg.d_state + cfg.d_feature + cfg.d_model_emb
    k = jax.random.split(key, 5)
    p = {
        "trunk": L.mlp_init(k[0], [d_in, cfg.d_hidden, cfg.d_hidden]),
        "state": L.dense_init(k[1], cfg.d_hidden, cfg.d_state),
        "model_emb": L.normal_init(k[2], (cfg.max_models, cfg.d_model_emb)),
    }
    if cfg.multi_basis:
        # FNN_0 -> basis mixture logits; FNN_p (p=1..P) -> Q-dim group scores
        p["w_head"] = L.dense_init(k[3], cfg.d_hidden, N_BASIS)
        p["v_heads"] = L.dense_init(k[4], cfg.d_hidden,
                                    N_BASIS * cfg.n_scale_groups)
    else:
        # plain MLP head on (trunk, multi-hot code) - no monotone guarantee
        p["flat_head"] = L.mlp_init(
            k[3], [cfg.d_hidden + cfg.n_scale_groups, cfg.d_hidden, 1])
    return p


def reward_model_init(key, cfg: RewardModelConfig) -> dict:
    keys = jax.random.split(key, cfg.n_stages + 1)
    enc_dims = [cfg.d_context, *cfg.encoder_hidden, cfg.d_feature]
    return {
        "encoder": L.mlp_init(keys[0], enc_dims),
        "cells": [_cell_init(keys[1 + k], cfg) for k in range(cfg.n_stages)],
    }


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------


def encode_context(params: dict, raw_context: jnp.ndarray) -> jnp.ndarray:
    """raw_context: (..., d_context) -> f_i: (..., d_feature)."""
    return L.mlp_apply(params["encoder"], raw_context, act="relu")


def _cell_apply(cell: dict, cfg: RewardModelConfig, h: jnp.ndarray,
                f: jnp.ndarray, model_onehot: jnp.ndarray,
                scale_multihot: jnp.ndarray):
    """One g_k. Shapes: h (..., d_state), f (..., d_feature),
    model_onehot (..., max_models), scale_multihot (..., Q)."""
    m_emb = model_onehot @ cell["model_emb"]
    z = jnp.concatenate([h, f, m_emb], axis=-1)
    t = L.mlp_apply(cell["trunk"], z, act="relu", final_act="relu")
    h_new = jnp.tanh(L.dense_apply(cell["state"], t))

    if cfg.multi_basis:
        w = jax.nn.softmax(L.dense_apply(cell["w_head"], t), axis=-1)  # (...,P)
        u = jax.nn.softplus(L.dense_apply(cell["v_heads"], t))  # (...,P*Q)
        u = u.reshape(*u.shape[:-1], N_BASIS, cfg.n_scale_groups)
        v = jnp.einsum("...pq,...q->...p", u, scale_multihot)  # Eq. 6
        dr = jnp.sum(w * apply_bases(v), axis=-1)  # Eq. 5
    else:
        zz = jnp.concatenate([t, scale_multihot], axis=-1)
        dr = L.mlp_apply(cell["flat_head"], zz, act="relu")[..., 0]
        dr = jax.nn.softplus(dr)  # keep rewards non-negative for parity
    return dr, h_new


def reward_apply(params: dict, cfg: RewardModelConfig,
                 raw_context: jnp.ndarray, model_onehot: jnp.ndarray,
                 scale_multihot: jnp.ndarray) -> jnp.ndarray:
    """Reward of ONE chain per request.

    raw_context:    (B, d_context)
    model_onehot:   (B, K, max_models)
    scale_multihot: (B, K, Q)
    returns:        (B,) predicted reward R_ij (Eq. 4)
    """
    f = encode_context(params, raw_context)
    h = jnp.zeros((*f.shape[:-1], cfg.d_state), f.dtype)
    total = jnp.zeros(f.shape[:-1], f.dtype)
    for k in range(cfg.n_stages):
        dr, h_new = _cell_apply(params["cells"][k], cfg, h, f,
                                model_onehot[..., k, :],
                                scale_multihot[..., k, :])
        total = total + dr
        if cfg.recursive:
            h = h_new  # else: every stage sees the zero state (Table 4 abl.)
    return total


def reward_matrix(params: dict, cfg: RewardModelConfig,
                  raw_context: jnp.ndarray, chain_model_onehot: jnp.ndarray,
                  chain_scale_multihot: jnp.ndarray) -> jnp.ndarray:
    """Full R in R^{I x J}: every request scored against every chain.

    raw_context:          (I, d_context)
    chain_model_onehot:   (J, K, max_models)   [from ActionChainSet]
    chain_scale_multihot: (J, K, Q)
    returns:              (I, J)
    """
    f = encode_context(params, raw_context)  # encode once: (I, d_f)

    def per_chain(m1, s1):  # m1: (K, M), s1: (K, Q)
        h = jnp.zeros((f.shape[0], cfg.d_state), f.dtype)
        total = jnp.zeros((f.shape[0],), f.dtype)
        for k in range(cfg.n_stages):
            mo = jnp.broadcast_to(m1[k], (f.shape[0], m1.shape[1]))
            sh = jnp.broadcast_to(s1[k], (f.shape[0], s1.shape[1]))
            dr, h_new = _cell_apply(params["cells"][k], cfg, h, f, mo, sh)
            total = total + dr
            if cfg.recursive:
                h = h_new
        return total  # (I,)

    return jax.vmap(per_chain, in_axes=(0, 0), out_axes=1)(
        chain_model_onehot, chain_scale_multihot)


def reward_matrix_chunked(params: dict, cfg: RewardModelConfig,
                          raw_context, chain_model_onehot,
                          chain_scale_multihot, *,
                          chunk: int = 2048) -> np.ndarray:
    """``reward_matrix`` evaluated in fixed-size request chunks.

    Peak memory is O(chunk * J) instead of O(I * J) - the offline
    analogue of the streaming serve path.  Inputs that fit one chunk
    take the direct call (bitwise identical to ``reward_matrix``);
    larger inputs run a jitted per-chunk kernel, identical per row up
    to float ulps (XLA blocks matmuls differently per batch shape).
    The last chunk is padded to ``chunk`` rows and sliced back, so any
    request count reuses ONE compiled shape.  Returns numpy (the
    chunks are host-concatenated).
    """
    ctx = np.asarray(raw_context, np.float32)
    i_n = ctx.shape[0]
    if i_n <= chunk:
        return np.asarray(reward_matrix(params, cfg, jnp.asarray(ctx),
                                        chain_model_onehot,
                                        chain_scale_multihot))
    fn = jax.jit(lambda c: reward_matrix(params, cfg, c,
                                         chain_model_onehot,
                                         chain_scale_multihot))
    parts = []
    for lo in range(0, i_n, chunk):
        sl = ctx[lo:lo + chunk]
        pad = chunk - sl.shape[0]
        if pad:
            sl = np.concatenate(
                [sl, np.zeros((pad, sl.shape[1]), np.float32)])
        parts.append(np.asarray(fn(jnp.asarray(sl)))[:chunk - pad or None])
    return np.concatenate(parts, axis=0)


# ---------------------------------------------------------------------------
# Model-prefix grouped scoring (the fused serving pipeline's hot path)
# ---------------------------------------------------------------------------
#
# The recursive state h_k is a function of the MODEL choices of stages
# <= k only: _cell_apply derives h_new from trunk(h, f, m_emb), never
# from the scale multi-hot (scales enter through the basis head alone).
# A chain set enumerates the Cartesian product of per-stage choices, so
# most chains share model prefixes - the paper layout has ONE distinct
# (recall, prerank) path and two rank models, i.e. J=O(100) chains but
# only ~2 distinct trunk evaluations per stage.  ``reward_matrix_grouped``
# runs each cell once per distinct prefix and broadcasts dr across the
# chains sharing it, cutting the per-window scoring FLOPs by ~J/2 while
# producing the same matrix as ``reward_matrix``.


def chain_prefix_plan(chain_model_idx: np.ndarray) -> tuple:
    """Static dedup plan from the (J, K) per-stage model indices.

    Returns one (model_of_prefix, parent_prefix, chain_to_prefix) triple
    per stage: stage k evaluates its cell once per distinct model prefix
    (m_1..m_k); ``parent_prefix`` maps each prefix to the stage-(k-1)
    prefix it extends, ``chain_to_prefix`` maps chains to prefixes.
    """
    chain_model_idx = np.asarray(chain_model_idx)
    j_n, k_n = chain_model_idx.shape
    plan = []
    prev_rows: list[tuple] = [()]
    for k in range(k_n):
        pref, inv = np.unique(chain_model_idx[:, :k + 1], axis=0,
                              return_inverse=True)
        prev_map = {r: i for i, r in enumerate(prev_rows)}
        parent = np.asarray([prev_map[tuple(r[:-1])] for r in pref],
                            np.int32)
        plan.append((pref[:, -1].astype(np.int32), parent,
                     inv.astype(np.int32).reshape(j_n)))
        prev_rows = [tuple(r) for r in pref]
    return tuple(plan)


def reward_matrix_grouped(params: dict, cfg: RewardModelConfig,
                          raw_context: jnp.ndarray,
                          chain_scale_multihot: jnp.ndarray,
                          plan: tuple) -> jnp.ndarray:
    """R in R^{I x J} with per-stage model-prefix deduplication.

    Same values as ``reward_matrix`` (cells see identical inputs, so
    chains sharing a prefix get the shared result rather than J
    recomputations) up to float32 rounding: the two paths run their
    matmuls over different batch shapes, which XLA may block and
    accumulate differently, so results agree to a few ulps (rtol 1e-6)
    and not bit for bit.  ``plan`` comes from ``chain_prefix_plan`` on
    the chain set's ``chain_idx[:, :, 0]``.
    """
    f = encode_context(params, raw_context)  # (I, d_f)
    i_n = f.shape[0]
    j_n = chain_scale_multihot.shape[0]
    h = jnp.zeros((i_n, 1, cfg.d_state), f.dtype)
    total = jnp.zeros((i_n, j_n), f.dtype)
    for k, (model_of_prefix, parent, chain_to_prefix) in enumerate(plan):
        cell = params["cells"][k]
        # non-recursive ablation: every stage reads the zero state, which
        # is what h holds when it is never updated below
        gather = parent if h.shape[1] > 1 else np.zeros_like(parent)
        n_p = len(model_of_prefix)
        z = jnp.concatenate([
            h[:, gather, :],
            jnp.broadcast_to(f[:, None, :], (i_n, n_p, f.shape[-1])),
            jnp.broadcast_to(cell["model_emb"][model_of_prefix],
                             (i_n, n_p, cfg.d_model_emb)),
        ], axis=-1)
        t = L.mlp_apply(cell["trunk"], z, act="relu", final_act="relu")
        sh_k = chain_scale_multihot[:, k, :]  # (J, Q)
        if cfg.multi_basis:
            w = jax.nn.softmax(L.dense_apply(cell["w_head"], t), axis=-1)
            u = jax.nn.softplus(L.dense_apply(cell["v_heads"], t))
            u = u.reshape(i_n, n_p, N_BASIS, cfg.n_scale_groups)
            v = jnp.einsum("ijpq,jq->ijp", u[:, chain_to_prefix],
                           sh_k)  # Eq. 6 per chain
            dr = jnp.sum(w[:, chain_to_prefix] * apply_bases(v), axis=-1)
        else:
            zz = jnp.concatenate([
                t[:, chain_to_prefix],
                jnp.broadcast_to(sh_k[None], (i_n, j_n, sh_k.shape[-1])),
            ], axis=-1)
            dr = L.mlp_apply(cell["flat_head"], zz, act="relu")[..., 0]
            dr = jax.nn.softplus(dr)
        total = total + dr
        if cfg.recursive:
            h = jnp.tanh(L.dense_apply(cell["state"], t))
    return total


# ---------------------------------------------------------------------------
# Per-chain label normalization (ratio targets)
# ---------------------------------------------------------------------------
#
# The multi-basis head is non-negative and monotone by construction, so the
# model cannot regress SIGNED residuals.  Instead the trainer fits the ratio
# y_uj = rev_uj / mean_u(rev_uj): the per-chain mean reward curve is
# measured exactly from simulation and stored in params["label_norm"]; the
# network only learns per-user deviations (the heterogeneity GreenFlow
# allocates on), and predictions de-normalize back to revenue units.


def chain_label_norm(revenue: np.ndarray, floor: float = 1e-3) -> np.ndarray:
    """Per-chain mean revenue over training users -> (J,) norm vector."""
    return np.maximum(np.asarray(revenue).mean(axis=0), floor) \
        .astype(np.float32)


def denormalize_rewards(params: dict, r):
    """Scale ratio predictions (.., J) back to revenue units, if the
    params carry a ``label_norm`` (no-op otherwise).  Backend-agnostic:
    works on numpy arrays and inside jit on tracers alike."""
    norm = params.get("label_norm")
    if norm is None:
        return r
    return r * norm[None, :]


# ---------------------------------------------------------------------------
# Training loss + calibration metric
# ---------------------------------------------------------------------------


def reward_loss(params: dict, cfg: RewardModelConfig, batch: dict) -> jnp.ndarray:
    """MSE on realized chain rewards (clicks among top-e).

    batch = {context (B,dc), model_onehot (B,K,M), scale_multihot (B,K,Q),
             label (B,), [weight (B,)]}
    """
    pred = reward_apply(params, cfg, batch["context"], batch["model_onehot"],
                        batch["scale_multihot"])
    err = jnp.square(pred - batch["label"])
    w = batch.get("weight")
    return jnp.mean(err * w) / jnp.maximum(jnp.mean(w), 1e-8) if w is not None \
        else jnp.mean(err)


def field_rce(y_true: np.ndarray, y_pred: np.ndarray,
              field_values: np.ndarray) -> float:
    """Field-level relative calibration error (paper Eq. 12, Pan et al.).

    Field-RCE = (1/|D|) * sum_f |sum_{i in D_f} (y_i - yhat_i)|
                          / ((1/|D_f|) * sum_{i in D_f} y_i)
    """
    y_true = np.asarray(y_true, np.float64)
    y_pred = np.asarray(y_pred, np.float64)
    field_values = np.asarray(field_values)
    total = 0.0
    for f in np.unique(field_values):
        m = field_values == f
        mean_y = y_true[m].mean()
        if mean_y <= 0:
            continue
        total += abs((y_true[m] - y_pred[m]).sum()) / mean_y
    return float(total / max(1, len(y_true)))
