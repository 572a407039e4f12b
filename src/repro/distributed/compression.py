"""Gradient compression for the DP all-reduce (pod x data axes).

Three modes, all expressed with jax-native collectives (shard_map +
ppermute/psum - not an NCCL emulation):

  * ``bf16``    - cast-psum-cast: 2x wire bytes vs fp32, unbiased enough
                  in practice (stochastic rounding noted as future work);
  * ``int8``    - ring reduce-scatter + all-gather via ppermute with
                  per-chunk fp32 scales re-quantized at every hop;
                  8x fewer wire bytes on the reduce phase, wire cost
                  shows up as collective-permute in the HLO (the roofline
                  harness counts it);
  * ``topk_ef`` - top-k magnitude sparsification with ERROR FEEDBACK
                  (EF21-style): the residual state carries what was not
                  transmitted into the next step, preserving convergence.

All functions operate on a single flat fp32 vector; ``compress_tree`` /
``uncompress_tree`` handle pytrees by flatten/concat (standard bucketing).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# Flat <-> tree plumbing
# ---------------------------------------------------------------------------


def flatten_tree(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    return flat


def unflatten_like(flat, tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out, off = [], 0
    for l in leaves:
        n = int(np.prod(l.shape)) if l.ndim else 1
        out.append(flat[off:off + n].reshape(l.shape).astype(l.dtype))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# int8 quantization primitive
# ---------------------------------------------------------------------------


def quantize_int8(x: jnp.ndarray, block: int = 2048):
    """Per-block symmetric int8: returns (q int8 (n,), scales f32 (n/block,))."""
    n = x.shape[0]
    pad = (-n) % block
    xp = jnp.pad(x, (0, pad)).reshape(-1, block)
    scale = jnp.max(jnp.abs(xp), axis=1, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(xp / jnp.maximum(scale, 1e-30)), -127, 127)
    return q.astype(jnp.int8), scale[:, 0]


def dequantize_int8(q: jnp.ndarray, scales: jnp.ndarray, n: int,
                    block: int = 2048):
    x = q.astype(jnp.float32).reshape(-1, block) * scales[:, None]
    return x.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# Ring all-reduce with int8 wire format (shard_map body)
# ---------------------------------------------------------------------------


def _ring_allreduce_int8_body(x, *, axis: str, size: int, block: int):
    """x: (n,) identical-shape local shard contribution. Classic 2(S-1)-step
    ring: reduce-scatter (quantized hops) then all-gather (quantized).
    ``size`` is the static axis size (the caller reads it off the mesh;
    jax.lax.axis_size is not available on every supported jax)."""
    s = size
    me = jax.lax.axis_index(axis)
    n = x.shape[0]
    chunk = -(-n // s)  # ceil
    xp = jnp.pad(x, (0, chunk * s - n)).reshape(s, chunk)

    perm = [(i, (i + 1) % s) for i in range(s)]

    # reduce-scatter: after S-1 hops, chunk (me+1)%s holds the full sum
    def rs_step(i, acc):
        send_idx = (me - i) % s
        q, sc = quantize_int8(acc[send_idx], block)
        q_r = jax.lax.ppermute(q, axis, perm)
        sc_r = jax.lax.ppermute(sc, axis, perm)
        recv = dequantize_int8(q_r, sc_r, chunk, block)
        recv_idx = (me - i - 1) % s
        return acc.at[recv_idx].add(recv)

    acc = jax.lax.fori_loop(0, s - 1, rs_step, xp)

    # all-gather: circulate each shard's reduced chunk
    def ag_step(i, acc):
        send_idx = (me - i + 1) % s
        q, sc = quantize_int8(acc[send_idx], block)
        q_r = jax.lax.ppermute(q, axis, perm)
        sc_r = jax.lax.ppermute(sc, axis, perm)
        recv = dequantize_int8(q_r, sc_r, chunk, block)
        recv_idx = (me - i) % s
        return acc.at[recv_idx].set(recv)

    acc = jax.lax.fori_loop(0, s - 1, ag_step, acc)
    return acc.reshape(-1)[:n]


def ring_allreduce_int8(flat_grads, mesh, *, axis: str = "data",
                        block: int = 2048):
    """Sum ``flat_grads`` (replicated layout, per-device distinct values is
    the caller's contract under shard_map-of-training) across ``axis``."""
    body = partial(_ring_allreduce_int8_body, axis=axis,
                   size=int(mesh.shape[axis]), block=block)
    return jax.shard_map(body, mesh=mesh, in_specs=P(axis),
                     out_specs=P(axis), check_vma=False)(flat_grads)


# ---------------------------------------------------------------------------
# bf16 psum + top-k error feedback (mesh-agnostic forms)
# ---------------------------------------------------------------------------


def psum_bf16(tree, axis_name):
    """Cast-to-bf16 all-reduce (use inside shard_map/pmap)."""
    return jax.tree_util.tree_map(
        # gf: allow[GF001] training-only gradient compression: bf16 is
        # lossy by design and never feeds a serving decision, so ring
        # order is irrelevant here
        lambda g: jax.lax.psum(g.astype(jnp.bfloat16), axis_name)
        .astype(g.dtype), tree)


@dataclass
class TopKEFState:
    residual: jnp.ndarray  # flat fp32


def topk_ef_init(params) -> TopKEFState:
    n = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    return TopKEFState(jnp.zeros((n,), jnp.float32))


def topk_ef_compress(flat_grad: jnp.ndarray, state: TopKEFState,
                     k_frac: float = 0.01):
    """Local step of EF top-k: returns (sparse-as-dense update, new state).
    The dense masked vector is what gets all-reduced; untransmitted mass
    stays in the residual."""
    g = flat_grad + state.residual
    k = max(1, int(k_frac * g.shape[0]))
    thresh = jax.lax.top_k(jnp.abs(g), k)[0][-1]
    mask = jnp.abs(g) >= thresh
    sent = jnp.where(mask, g, 0.0)
    return sent, TopKEFState(residual=g - sent)
