"""Configurable decoder-only LM covering the five assigned architectures.

One implementation, config-selected features:
  * GQA (n_kv_heads <= n_heads), RoPE (partial fraction, theta),
  * dense gated FFN (SwiGLU/GeGLU) or MoE (top-k routing, EP-sharded),
  * gemma2: local/global alternating sliding window, attn + final logit
    softcap, zero-centered RMSNorm, sandwich (pre+post) norms, GeGLU,
  * olmoe: QK-norm,
  * minicpm: embedding scale, depth-scaled residuals (mup-ish),
  * layers stacked on a leading L dim and executed with ``lax.scan``
    (compile time stays flat in depth - critical for the 512-device
    dry-run on one CPU core).

Sharding (GSPMD via ``distributed.sharding.constrain``; see DESIGN.md §6):
batch over (pod, data); attention heads + ffn hidden + vocab over 'model'
(Megatron TP); params optionally FSDP over 'data'; MoE experts over
'model' (EP) via an explicit shard_map (psum-combined, the EP-as-TP
pattern).  Entry points: ``forward`` / ``loss_fn`` (train),
``prefill`` and ``decode_step`` (serve).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import batch_spec, constrain, current_mesh
from repro.models import layers as L

# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int  # per-expert FFN hidden
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01  # load-balance loss (Switch-style)


@dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    padded_vocab: int  # multiple of 256 (shardable over 'model')
    rope_theta: float = 10_000.0
    rope_fraction: float = 1.0  # glm4 rotates half the head dim
    moe: MoEConfig | None = None
    window_pattern: tuple | None = None  # e.g. (4096, -1): local, global, ...
    attn_softcap: float | None = None
    final_softcap: float | None = None
    qk_norm: bool = False
    sandwich_norm: bool = False  # gemma2 pre+post norms
    zero_centered_norm: bool = False  # gemma (1+scale) RMSNorm
    gated_ffn: bool = True
    act: str = "silu"  # silu (llama) | gelu (gemma GeGLU)
    embed_scale: float | None = None  # gemma sqrt(d), minicpm 12.0
    residual_scale: float = 1.0  # minicpm 1.4/sqrt(40)
    logit_divisor: float = 1.0  # minicpm d_model/dim_base
    tie_embeddings: bool = True
    query_scale: float | None = None  # default 1/sqrt(d_head)
    dtype: str = "bfloat16"  # activation/compute dtype
    remat: bool = True  # checkpoint each layer in training
    fsdp: bool = True  # shard params over 'data'
    # q-block-chunked attention (python-unrolled: exact HLO flop counts,
    # bounded score memory - the jnp stand-in for the Pallas flash kernel)
    attn_chunk_q: int | None = None
    # unroll factor for the layer scan (dry-run flop-count variants set
    # this = n_layers so XLA sees every body; production leaves it 1)
    scan_unroll: int = 1
    # attention sharding axis: "heads" (Megatron TP; needs n_heads %
    # n_model_shards == 0) or "seq" (context-parallel: q stays seq-sharded,
    # kv gathers - the fix for gemma2's 8 heads / minicpm's 36 heads vs a
    # 16-way model axis, which otherwise triggers GSPMD involuntary full
    # rematerialization; see EXPERIMENTS.md SPerf iteration 1)
    attn_shard: str = "heads"
    # Megatron-style sequence parallelism: the inter-layer residual stream
    # is sharded over 'model' on the SEQ dim (norms/elementwise run
    # seq-sharded; GSPMD inserts all-gather at attention/FFN entry and
    # reduce-scatter at exit).  Cuts the per-layer activation stash (the
    # dominant train-memory term - see EXPERIMENTS.md SPerf) by the TP
    # degree.
    sequence_parallel: bool = False

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def d_q(self) -> int:
        return self.n_heads * self.d_head

    @property
    def d_kv(self) -> int:
        return self.n_kv_heads * self.d_head

    def window_for_layer(self, i: int) -> int:
        if not self.window_pattern:
            return -1
        return self.window_pattern[i % len(self.window_pattern)]

    def n_params(self) -> float:
        """Total parameter count (embedding included once if tied)."""
        d, l = self.d_model, self.n_layers
        attn = d * (self.d_q + 2 * self.d_kv) + self.d_q * d
        if self.moe:
            n_mats = 3 if self.gated_ffn else 2
            ffn = self.moe.n_experts * n_mats * d * self.moe.d_expert
            ffn += d * self.moe.n_experts  # router
        else:
            n_mats = 3 if self.gated_ffn else 2
            ffn = n_mats * d * self.d_ff
        norms = 4 * d if self.sandwich_norm else 2 * d
        emb = self.padded_vocab * d * (1 if self.tie_embeddings else 2)
        return l * (attn + ffn + norms) + emb + d

    def n_active_params(self) -> float:
        """Params touched per token (MoE: top_k of n_experts)."""
        if not self.moe:
            return self.n_params()
        d, l = self.d_model, self.n_layers
        attn = d * (self.d_q + 2 * self.d_kv) + self.d_q * d
        n_mats = 3 if self.gated_ffn else 2
        ffn = self.moe.top_k * n_mats * d * self.moe.d_expert
        ffn += d * self.moe.n_experts
        emb = self.padded_vocab * d * (1 if self.tie_embeddings else 2)
        return l * (attn + ffn) + emb + d


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(cfg: LMConfig) -> jnp.ndarray:
    rot = int(cfg.d_head * cfg.rope_fraction)
    rot -= rot % 2
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, rot, 2, jnp.float32) / rot))
    return inv  # (rot/2,)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               cfg: LMConfig) -> jnp.ndarray:
    """x (..., T, H, dh), positions (..., T) -> rotated x."""
    inv = rope_freqs(cfg)
    rot = inv.shape[0] * 2
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., T, rot/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    yr = jnp.stack([out1, out2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([yr, xp], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _layer_init(key, cfg: LMConfig) -> dict:
    d = cfg.d_model
    k = jax.random.split(key, 10)
    p = {
        "wq": L.normal_init(k[0], (d, cfg.d_q), std=0.02),
        "wk": L.normal_init(k[1], (d, cfg.d_kv), std=0.02),
        "wv": L.normal_init(k[2], (d, cfg.d_kv), std=0.02),
        "wo": L.normal_init(k[3], (cfg.d_q, d), std=0.02 / math.sqrt(2 * cfg.n_layers)),
        "ln_attn": L.rmsnorm_init(d),
        "ln_ffn": L.rmsnorm_init(d),
    }
    if cfg.sandwich_norm:
        p["ln_attn_post"] = L.rmsnorm_init(d)
        p["ln_ffn_post"] = L.rmsnorm_init(d)
    if cfg.qk_norm:
        p["q_norm"] = L.rmsnorm_init(cfg.d_head)
        p["k_norm"] = L.rmsnorm_init(cfg.d_head)
    if cfg.moe:
        e, f = cfg.moe.n_experts, cfg.moe.d_expert
        p["router"] = L.normal_init(k[4], (d, e), std=0.02)
        p["w1"] = L.normal_init(k[5], (e, d, f), std=0.02)
        p["w2"] = L.normal_init(k[6], (e, f, d), std=0.02 / math.sqrt(2 * cfg.n_layers))
        if cfg.gated_ffn:
            p["w3"] = L.normal_init(k[7], (e, d, f), std=0.02)
    else:
        f = cfg.d_ff
        p["w1"] = L.normal_init(k[5], (d, f), std=0.02)
        p["w2"] = L.normal_init(k[6], (f, d), std=0.02 / math.sqrt(2 * cfg.n_layers))
        if cfg.gated_ffn:
            p["w3"] = L.normal_init(k[7], (d, f), std=0.02)
    return p


def init(key, cfg: LMConfig) -> dict:
    """Stacked-layer params: every layer tensor gets a leading (L,) dim."""
    k_emb, k_layers, k_out = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    layers = [_layer_init(kk, cfg) for kk in layer_keys]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    params = {
        "embed": L.normal_init(k_emb, (cfg.padded_vocab, cfg.d_model), std=0.02),
        "layers": stacked,
        "ln_final": L.rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.normal_init(k_out, (cfg.padded_vocab, cfg.d_model),
                                          std=0.02)
    return params


def window_array(cfg: LMConfig) -> jnp.ndarray:
    """Per-layer sliding-window sizes (-1 = global) as a scan xs constant."""
    return jnp.asarray([cfg.window_for_layer(i) for i in range(cfg.n_layers)],
                       jnp.int32)


def param_shardings(cfg: LMConfig) -> dict:
    """PartitionSpec tree matching ``init`` (leading L dim unsharded)."""
    dp = "data" if cfg.fsdp else None
    lay = {
        "wq": P(None, dp, "model"),
        "wk": P(None, dp, "model"),
        "wv": P(None, dp, "model"),
        "wo": P(None, "model", dp),
        "ln_attn": {"scale": P(None, None)},
        "ln_ffn": {"scale": P(None, None)},
    }
    if cfg.sandwich_norm:
        lay["ln_attn_post"] = {"scale": P(None, None)}
        lay["ln_ffn_post"] = {"scale": P(None, None)}
    if cfg.qk_norm:
        lay["q_norm"] = {"scale": P(None, None)}
        lay["k_norm"] = {"scale": P(None, None)}
    if cfg.moe:
        lay["router"] = P(None, dp, None)
        lay["w1"] = P(None, "model", dp, None)
        lay["w2"] = P(None, "model", None, dp)
        if cfg.gated_ffn:
            lay["w3"] = P(None, "model", dp, None)
    else:
        lay["w1"] = P(None, dp, "model")
        lay["w2"] = P(None, "model", dp)
        if cfg.gated_ffn:
            lay["w3"] = P(None, dp, "model")
    out = {
        "embed": P("model", dp),
        "layers": lay,
        "ln_final": {"scale": P(None)},
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = P("model", dp)
    return out


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _split_heads(x, n, dh):
    return x.reshape(*x.shape[:-1], n, dh)


def _constrain_stream(cfg, x):
    """Residual-stream layout between blocks: batch over (pod,data) and,
    under sequence parallelism, seq over 'model'."""
    bspec = batch_spec()
    if cfg.sequence_parallel and x.shape[1] > 1:
        return constrain(x, bspec, "model", None)
    return constrain(x, bspec, None, None)


def _attn_mask(q_pos, k_pos, window):
    """Causal + optional sliding window.  window < 0 => global."""
    causal = k_pos[None, :] <= q_pos[:, None]
    in_window = (q_pos[:, None] - k_pos[None, :]) < jnp.where(window < 0,
                                                              jnp.iinfo(jnp.int32).max,
                                                              window)
    return causal & in_window


def _attention(cfg: LMConfig, q, k, v, mask):
    """q (B,T,H,dh), k/v (B,S,Hkv,dh), mask (T,S) or (B,T,S)."""
    scale = cfg.query_scale or 1.0 / math.sqrt(cfg.d_head)
    groups = cfg.n_heads // cfg.n_kv_heads
    b, t = q.shape[0], q.shape[1]
    qg = q.reshape(b, t, cfg.n_kv_heads, groups, cfg.d_head)
    logits = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(jnp.float32) * scale
    if cfg.attn_softcap:
        logits = cfg.attn_softcap * jnp.tanh(logits / cfg.attn_softcap)
    if mask.ndim == 2:
        mask = mask[None]
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return out.reshape(b, t, cfg.d_q)


def _attention_chunked(cfg: LMConfig, q, k, v, positions, window,
                       chunk: int):
    """Exact attention, python-unrolled over q blocks: score memory is
    bounded to (B, Hkv, G, chunk, S) and the HLO contains every block
    (no while-loop flop undercount).  TPU production uses the Pallas
    flash kernel (repro.kernels.flash_attention); this is its XLA twin."""
    t = q.shape[1]
    n_blocks = -(-t // chunk)
    k_pos = positions[0]
    outs = []
    for i in range(n_blocks):
        lo = i * chunk
        hi = min(t, lo + chunk)
        qb = q[:, lo:hi]
        mask = _attn_mask(positions[0, lo:hi], k_pos, window)
        outs.append(_attention(cfg, qb, k, v, mask))
    return jnp.concatenate(outs, axis=1)


def _attn_block(p, cfg: LMConfig, x, positions, window, kv=None, kv_pos=None):
    """x (B,T,d).  kv: optional (k_cache, v_cache) for decode."""
    bspec = batch_spec()
    q = _split_heads(x @ p["wq"].astype(x.dtype), cfg.n_heads, cfg.d_head)
    k = _split_heads(x @ p["wk"].astype(x.dtype), cfg.n_kv_heads, cfg.d_head)
    v = _split_heads(x @ p["wv"].astype(x.dtype), cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        q = L.rmsnorm_apply(p["q_norm"], q, zero_centered=cfg.zero_centered_norm)
        k = L.rmsnorm_apply(p["k_norm"], k, zero_centered=cfg.zero_centered_norm)
    q = apply_rope(q, positions, cfg)
    k = apply_rope(k, positions, cfg)
    if cfg.attn_shard == "seq" and q.shape[1] > 1:
        # context-parallel: shard queries on SEQ over 'model'; kv replicate
        q = constrain(q, bspec, "model", None, None)
        k = constrain(k, bspec, None, None, None)
        v = constrain(v, bspec, None, None, None)
    else:
        q = constrain(q, bspec, None, "model", None)
        k = constrain(k, bspec, None, None, None)  # kv heads < shards
    if kv is None:
        if cfg.attn_chunk_q and q.shape[1] > cfg.attn_chunk_q:
            out = _attention_chunked(cfg, q, k, v, positions, window,
                                     cfg.attn_chunk_q)
        else:
            mask = _attn_mask(positions[0], positions[0], window)
            out = _attention(cfg, q, k, v, mask)
        new_kv = (k, v)
    else:
        k_cache, v_cache = kv
        mask = _attn_mask(positions[0], kv_pos, window)
        out = _attention(cfg, q, k_cache, v_cache, mask)
        new_kv = (k, v)
    out = out @ p["wo"].astype(x.dtype)
    return constrain(out, bspec, None, None), new_kv


# ---------------------------------------------------------------------------
# FFN / MoE
# ---------------------------------------------------------------------------


def _dense_ffn(p, cfg: LMConfig, x):
    bspec = batch_spec()
    act = jax.nn.silu if cfg.act == "silu" else partial(jax.nn.gelu, approximate=True)
    h = act(x @ p["w1"].astype(x.dtype))
    if cfg.gated_ffn:
        h = h * (x @ p["w3"].astype(x.dtype))
    h = constrain(h, bspec, None, "model")
    return constrain(h @ p["w2"].astype(x.dtype), bspec, None, None)


def _moe_ref(p, cfg: LMConfig, x):
    """Dense reference MoE: computes every expert, exact top-k combine.
    Used on CPU (no mesh) and as the EP oracle in tests."""
    m = cfg.moe
    act = jax.nn.silu if cfg.act == "silu" else partial(jax.nn.gelu, approximate=True)
    n = x.shape[0] * x.shape[1]
    xt = x.reshape(n, cfg.d_model)
    logits = (xt @ p["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, m.top_k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(n)[:, None], top_e].set(top_w)
    h = jnp.einsum("nd,edf->nef", xt, p["w1"].astype(x.dtype))
    h = act(h)
    if cfg.gated_ffn:
        h = h * jnp.einsum("nd,edf->nef", xt, p["w3"].astype(x.dtype))
    y = jnp.einsum("nef,efd->ned", h, p["w2"].astype(x.dtype))
    out = jnp.einsum("ned,ne->nd", y, gates.astype(x.dtype))
    aux = _router_aux(probs, top_e, m)
    return out.reshape(x.shape), aux


def _router_aux(probs, top_e, m: MoEConfig):
    """Switch-style load-balance loss: E * sum_e f_e * P_e."""
    e = probs.shape[-1]
    hot = jax.nn.one_hot(top_e[..., 0], e, dtype=probs.dtype)
    f = jnp.mean(hot, axis=0)
    p_bar = jnp.mean(probs, axis=0)
    return e * jnp.sum(f * p_bar)


def _moe_ep_body(xt, router, w1, w3, w2, *, cfg: LMConfig, axis: str,
                 batch_axes: tuple = ()):
    """shard_map body: xt (n_loc, d) data-sharded / model-replicated;
    w* (E_loc, ...) expert-sharded over `axis`.  See DESIGN.md §6."""
    m = cfg.moe
    act = jax.nn.silu if cfg.act == "silu" else partial(jax.nn.gelu, approximate=True)
    s_idx = jax.lax.axis_index(axis)
    e_loc = w1.shape[0]
    n = xt.shape[0]
    logits = (xt @ router.astype(xt.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, m.top_k)
    top_w = (top_w / jnp.sum(top_w, axis=-1, keepdims=True)).astype(xt.dtype)

    flat_e = top_e.reshape(-1)  # (n*k,)
    flat_w = top_w.reshape(-1)
    tok_id = jnp.arange(n * m.top_k, dtype=jnp.int32) // m.top_k
    cap = max(1, int(m.capacity_factor * n * m.top_k / m.n_experts))

    out = jnp.zeros((n, cfg.d_model), xt.dtype)
    for e_local in range(e_loc):
        e_global = s_idx * e_loc + e_local
        sel = flat_e == e_global
        pos = jnp.cumsum(sel) - 1
        slot = jnp.where(sel & (pos < cap), pos, cap).astype(jnp.int32)
        buf = jnp.zeros((cap + 1, cfg.d_model), xt.dtype).at[slot].set(
            xt[tok_id], mode="drop")
        h = act(buf[:cap] @ w1[e_local].astype(xt.dtype))
        if cfg.gated_ffn:
            h = h * (buf[:cap] @ w3[e_local].astype(xt.dtype))
        y = h @ w2[e_local].astype(xt.dtype)  # (cap, d)
        tok_of = jnp.zeros((cap + 1,), jnp.int32).at[slot].set(tok_id, mode="drop")
        w_of = jnp.zeros((cap + 1,), xt.dtype).at[slot].set(
            flat_w * sel.astype(flat_w.dtype), mode="drop")
        out = out.at[tok_of[:cap]].add(y * w_of[:cap, None], mode="drop")

    out = jax.lax.psum(out, axis)
    aux = _router_aux(probs, top_e, m)[None]
    if batch_axes:
        aux = jax.lax.pmean(aux, batch_axes)
    return out, aux


def _moe_ep(p, cfg: LMConfig, x):
    """EP-as-TP MoE (see DESIGN.md §6): experts sharded over 'model',
    activations batch-sharded over (pod, data); combine via psum."""
    mesh = current_mesh()
    bspec = batch_spec(mesh)
    b, t, d = x.shape
    xt = x.reshape(b * t, d)
    w3 = p.get("w3", p["w1"])  # dummy when ungated
    baxes = bspec if isinstance(bspec, tuple) else ((bspec,) if bspec else ())
    body = partial(_moe_ep_body, cfg=cfg, axis="model", batch_axes=baxes)
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None), P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(bspec, None), P(None)),
        check_vma=False,
    )(xt, p["router"], p["w1"], w3, p["w2"])
    return out.reshape(b, t, d), jnp.mean(aux)


def _ffn_block(p, cfg: LMConfig, x):
    if cfg.moe is None:
        return _dense_ffn(p, cfg, x), jnp.float32(0.0)
    if current_mesh() is None:
        return _moe_ref(p, cfg, x)
    return _moe_ep(p, cfg, x)


# ---------------------------------------------------------------------------
# Block + full forward (scan over layers)
# ---------------------------------------------------------------------------


def _block(p, cfg: LMConfig, x, positions, window, kv=None, kv_pos=None):
    zc = cfg.zero_centered_norm
    h = L.rmsnorm_apply(p["ln_attn"], x, zero_centered=zc)
    attn_out, new_kv = _attn_block(p, cfg, h, positions, window, kv, kv_pos)
    if cfg.sandwich_norm:
        attn_out = L.rmsnorm_apply(p["ln_attn_post"], attn_out, zero_centered=zc)
    x = _constrain_stream(cfg, x + cfg.residual_scale * attn_out)
    h = L.rmsnorm_apply(p["ln_ffn"], x, zero_centered=zc)
    ffn_out, aux = _ffn_block(p, cfg, h)
    if cfg.sandwich_norm:
        ffn_out = L.rmsnorm_apply(p["ln_ffn_post"], ffn_out, zero_centered=zc)
    x = _constrain_stream(cfg, x + cfg.residual_scale * ffn_out)
    return x, new_kv, aux


def _embed(params, cfg: LMConfig, tokens):
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.embed_scale, x.dtype)
    return _constrain_stream(cfg, x)


def _unembed(params, cfg: LMConfig, x):
    table = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = x @ table.T.astype(x.dtype)
    logits = logits / jnp.asarray(cfg.logit_divisor, x.dtype)
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return constrain(logits, batch_spec(), None, "model")


def forward(params, cfg: LMConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    """tokens (B, T) -> logits (B, T, padded_vocab)."""
    b, t = tokens.shape
    x = _embed(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    def body(x, xs):
        layer, window = xs
        y, _, aux = _block(layer, cfg, x, positions, window)
        return y, aux

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, auxes = jax.lax.scan(body_fn, x, (params["layers"], window_array(cfg)),
                            unroll=cfg.scan_unroll)
    x = L.rmsnorm_apply(params["ln_final"], x,
                        zero_centered=cfg.zero_centered_norm)
    return _unembed(params, cfg, x), jnp.sum(auxes)


def loss_fn(params, cfg: LMConfig, batch: dict) -> jnp.ndarray:
    """batch: tokens (B,T) int32, targets (B,T) int32, mask (B,T)."""
    logits, aux = forward(params, cfg, batch["tokens"])
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, batch["targets"][..., None],
                                 axis=-1)[..., 0]
    nll = (lse - picked) * batch["mask"]
    loss = jnp.sum(nll) / jnp.maximum(jnp.sum(batch["mask"]), 1.0)
    if cfg.moe:
        loss = loss + cfg.moe.router_aux_weight * aux / cfg.n_layers
    return loss


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode with a KV cache
# ---------------------------------------------------------------------------


def init_cache(cfg: LMConfig, batch: int, max_len: int,
               dtype=None) -> dict:
    """Unified stacked (L, B, S, Hkv, dh) cache; sliding windows are applied
    via the attention mask against absolute positions.  (Baseline layout -
    bounding local-layer caches to their window is a recorded §Perf
    optimization, see EXPERIMENTS.md.)"""
    dtype = dtype or cfg.compute_dtype
    return {
        "k": jnp.zeros((cfg.n_layers, batch, max_len, cfg.n_kv_heads,
                        cfg.d_head), dtype),
        "v": jnp.zeros((cfg.n_layers, batch, max_len, cfg.n_kv_heads,
                        cfg.d_head), dtype),
        "length": jnp.zeros((), jnp.int32),
    }


def cache_shardings(cfg: LMConfig, *, seq_sharded: bool = False):
    """Cache specs: batch over (pod,data); optionally KV-seq over 'model'
    (long-context decode; see DESIGN.md §5)."""
    bspec = ("pod", "data")
    seq = "model" if seq_sharded else None
    kvh = None if seq_sharded else None  # kv heads < shards for these archs
    return {
        "k": P(None, bspec, seq, kvh, None),
        "v": P(None, bspec, seq, kvh, None),
        "length": P(),
    }


def prefill(params, cfg: LMConfig, tokens: jnp.ndarray, max_len: int):
    """tokens (B, T) -> (last-token logits (B, V), cache)."""
    b, t = tokens.shape
    x = _embed(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    cache = init_cache(cfg, b, max_len)
    s_max = cache["k"].shape[2]

    def body(x, xs):
        layer, window = xs
        y, (k, v), _ = _block(layer, cfg, x, positions, window)
        k_pad = jnp.zeros((b, s_max, cfg.n_kv_heads, cfg.d_head), k.dtype)
        k_pad = jax.lax.dynamic_update_slice(k_pad, k, (0, 0, 0, 0))
        v_pad = jnp.zeros((b, s_max, cfg.n_kv_heads, cfg.d_head), v.dtype)
        v_pad = jax.lax.dynamic_update_slice(v_pad, v, (0, 0, 0, 0))
        return y, (k_pad, v_pad)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], window_array(cfg)),
                               unroll=cfg.scan_unroll)
    x = L.rmsnorm_apply(params["ln_final"], x,
                        zero_centered=cfg.zero_centered_norm)
    logits = _unembed(params, cfg, x[:, -1:, :])[:, 0]
    cache = {"k": ks, "v": vs, "length": jnp.asarray(t, jnp.int32)}
    return logits, cache


def decode_step(params, cfg: LMConfig, token: jnp.ndarray, cache: dict):
    """One serve step: token (B,) int32 + cache -> (logits (B, V), cache).

    The KV of the new token is written at position cache.length; attention
    runs against the full cache with positions masked beyond length.
    """
    b = token.shape[0]
    pos = cache["length"]
    x = _embed(params, cfg, token[:, None])
    positions = jnp.broadcast_to(pos[None, None], (b, 1)).astype(jnp.int32)
    s_max = cache["k"].shape[2]
    kv_pos = jnp.arange(s_max, dtype=jnp.int32)

    def body(x, xs):
        layer, window, k_cache, v_cache = xs
        zc = cfg.zero_centered_norm
        h = L.rmsnorm_apply(layer["ln_attn"], x, zero_centered=zc)
        # project the single new token
        q = _split_heads(h @ layer["wq"].astype(h.dtype), cfg.n_heads, cfg.d_head)
        k = _split_heads(h @ layer["wk"].astype(h.dtype), cfg.n_kv_heads, cfg.d_head)
        v = _split_heads(h @ layer["wv"].astype(h.dtype), cfg.n_kv_heads, cfg.d_head)
        if cfg.qk_norm:
            q = L.rmsnorm_apply(layer["q_norm"], q, zero_centered=zc)
            k = L.rmsnorm_apply(layer["k_norm"], k, zero_centered=zc)
        q = apply_rope(q, positions, cfg)
        k = apply_rope(k, positions, cfg)
        k_cache = jax.lax.dynamic_update_slice(k_cache, k, (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v, (0, pos, 0, 0))
        # causal against absolute positions + optional sliding window
        valid = (kv_pos <= pos) & (
            (pos - kv_pos) < jnp.where(window > 0, window,
                                       jnp.iinfo(jnp.int32).max))
        mask = jnp.broadcast_to(valid[None, :], (1, s_max))
        attn = _attention(cfg, q, k_cache, v_cache, mask)
        attn = attn @ layer["wo"].astype(h.dtype)
        if cfg.sandwich_norm:
            attn = L.rmsnorm_apply(layer["ln_attn_post"], attn, zero_centered=zc)
        x = x + cfg.residual_scale * attn
        h = L.rmsnorm_apply(layer["ln_ffn"], x, zero_centered=zc)
        f, _ = _ffn_block(layer, cfg, h)
        if cfg.sandwich_norm:
            f = L.rmsnorm_apply(layer["ln_ffn_post"], f, zero_centered=zc)
        return x + cfg.residual_scale * f, (k_cache, v_cache)

    x, (ks, vs) = jax.lax.scan(
        body, x, (params["layers"], window_array(cfg), cache["k"], cache["v"]),
        unroll=cfg.scan_unroll)
    x = L.rmsnorm_apply(params["ln_final"], x,
                        zero_centered=cfg.zero_centered_norm)
    logits = _unembed(params, cfg, x)[:, 0]
    new_cache = {"k": ks, "v": vs, "length": pos + 1}
    return logits, new_cache


# ---------------------------------------------------------------------------
# FLOPs
# ---------------------------------------------------------------------------


def flops_per_token(cfg: LMConfig, seq_len: int, *, decode: bool = False) -> float:
    """Forward FLOPs per token (attention quadratic term included)."""
    d = cfg.d_model
    proj = 2.0 * d * (cfg.d_q + 2 * cfg.d_kv) + 2.0 * cfg.d_q * d
    kv_len = seq_len
    attn = 4.0 * cfg.n_heads * cfg.d_head * (kv_len if decode else kv_len / 2)
    if cfg.moe:
        n_mats = 3 if cfg.gated_ffn else 2
        ffn = n_mats * 2.0 * d * cfg.moe.d_expert * cfg.moe.top_k
        ffn += 2.0 * d * cfg.moe.n_experts
    else:
        n_mats = 3 if cfg.gated_ffn else 2
        ffn = n_mats * 2.0 * d * cfg.d_ff
    unembed = 2.0 * d * cfg.padded_vocab
    return cfg.n_layers * (proj + attn + ffn) + unembed
