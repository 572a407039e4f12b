"""Embedding substrate for recsys: EmbeddingBag + sharded tables.

JAX has no native ``nn.EmbeddingBag`` and no CSR sparse — per the brief we
BUILD the lookup path:

  * ``embedding_bag``          - ragged bags via (ids, segment_ids) ->
                                  ``jnp.take`` + ``jax.ops.segment_sum/max``.
  * ``fixed_bag``              - static (B, L) bags with a pad mask (the
                                  TPU-friendly layout used by the models).
  * ``sharded_embedding_apply``- row-sharded table lookup under shard_map:
                                  shard-local take + mask + psum('model').
                                  One all-reduce of (batch, dim) per stacked
                                  table group - THE collective hot path for
                                  DLRM-class models (see EXPERIMENTS.md).

A Pallas kernel version of the fused gather+reduce lives in
``repro/kernels/embedding_bag.py``; these jnp forms are its oracle and the
default path on CPU.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Ragged EmbeddingBag (torch.nn.EmbeddingBag parity)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("num_bags", "mode"))
def embedding_bag(table: jnp.ndarray, ids: jnp.ndarray,
                  segment_ids: jnp.ndarray, num_bags: int,
                  *, mode: str = "sum",
                  per_sample_weights: jnp.ndarray | None = None):
    """table (V, D); ids (N,); segment_ids (N,) in [0, num_bags)."""
    rows = jnp.take(table, ids, axis=0)  # (N, D)
    if per_sample_weights is not None:
        rows = rows * per_sample_weights[:, None]
    if mode == "sum":
        return jax.ops.segment_sum(rows, segment_ids, num_segments=num_bags)
    if mode == "mean":
        s = jax.ops.segment_sum(rows, segment_ids, num_segments=num_bags)
        cnt = jax.ops.segment_sum(jnp.ones_like(ids, jnp.float32),
                                  segment_ids, num_segments=num_bags)
        return s / jnp.maximum(cnt, 1.0)[:, None]
    if mode == "max":
        return jax.ops.segment_max(rows, segment_ids, num_segments=num_bags)
    raise ValueError(f"unknown mode {mode!r}")


# ---------------------------------------------------------------------------
# Fixed-size bags (static shapes: the TPU layout)
# ---------------------------------------------------------------------------


def fixed_bag(table: jnp.ndarray, ids: jnp.ndarray,
              mask: jnp.ndarray | None = None, *, mode: str = "sum"):
    """table (V, D); ids (..., L) -> (..., D). mask (..., L) 1=valid."""
    rows = jnp.take(table, ids, axis=0)  # (..., L, D)
    if mask is not None:
        rows = rows * mask[..., None]
    if mode == "sum":
        return jnp.sum(rows, axis=-2)
    if mode == "mean":
        denom = (jnp.sum(mask, axis=-1, keepdims=True)
                 if mask is not None else ids.shape[-1])
        return jnp.sum(rows, axis=-2) / jnp.maximum(denom, 1.0)
    if mode == "max":
        if mask is not None:
            rows = jnp.where(mask[..., None] > 0, rows, -jnp.inf)
        return jnp.max(rows, axis=-2)
    raise ValueError(f"unknown mode {mode!r}")


def hash_bucket(ids: jnp.ndarray, vocab: int, *, salt: int = 0x9E3779B9):
    """Quotient-free hashing trick for unbounded id spaces."""
    h = (ids.astype(jnp.uint32) * jnp.uint32(salt)) ^ (ids.astype(jnp.uint32) >> 16)
    return (h % jnp.uint32(vocab)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Row-sharded lookup (model-parallel embedding tables)
# ---------------------------------------------------------------------------


def shard_local_lookup(table_shard: jnp.ndarray, ids: jnp.ndarray,
                       shard_idx: jnp.ndarray, rows_per_shard: int,
                       axis_name: str, out_dtype=None):
    """Body to run under shard_map: every shard owns rows
    [shard_idx*rows_per_shard, ...); misses contribute zeros; psum merges.

    table_shard (V/S, D); ids (...,) GLOBAL row ids (replicated).
    Returns (..., D) replicated across the axis.
    """
    lo = shard_idx * rows_per_shard
    local = ids - lo
    hit = (local >= 0) & (local < rows_per_shard)
    local = jnp.clip(local, 0, rows_per_shard - 1)
    rows = jnp.take(table_shard, local, axis=0)
    if out_dtype is not None:
        # bf16 on the wire: halves the psum here AND the table-grad
        # all-reduce in backward (cotangents inherit this dtype)
        rows = rows.astype(out_dtype)
    rows = jnp.where(hit[..., None], rows, jnp.zeros((), rows.dtype))
    return jax.lax.psum(rows, axis_name)


def sharded_embedding_apply(table: jnp.ndarray, ids: jnp.ndarray, mesh,
                            *, axis: str = "model",
                            batch_axes: tuple[str, ...] = (),
                            out_dtype=None):
    """Row-shard ``table`` over ``axis`` and look up GLOBAL ``ids``.

    Usable inside jit (shard_map nests under pjit).  ids may themselves be
    sharded over ``batch_axes``; the psum only runs over the table axis so
    each batch shard reduces its own rows.
    """
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[axis]
    vocab = table.shape[0]
    if vocab % n_shards != 0:
        raise ValueError(f"vocab {vocab} must divide by {n_shards} shards "
                         f"(pad the table)")
    rows_per_shard = vocab // n_shards

    batch_spec = P(batch_axes if batch_axes else None)

    def body(tbl, local_ids):
        shard_idx = jax.lax.axis_index(axis)
        return shard_local_lookup(tbl, local_ids, shard_idx, rows_per_shard,
                                  axis, out_dtype)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), batch_spec),
        out_specs=batch_spec,
        check_vma=False,
    )(table, ids)


def sharded_embedding_apply_2d(table: jnp.ndarray, ids: jnp.ndarray, mesh,
                               *, axes: tuple = ("model", "data"),
                               out_dtype=None):
    """TorchRec-style row-wise sharding over TWO mesh axes: every row is
    owned by exactly ONE device, so the table GRADIENT never crosses the
    wire (scatter-add stays shard-local).  The forward routes activations
    instead: ids replicate (ints, cheap) and the bag values psum over both
    axes.  For DLRM train this trades a ~1.3 GB fp32 grad all-reduce for a
    ~0.2-0.4 GB activation psum - see EXPERIMENTS.md §Perf iteration 3.

    table (V, D) with spec P(axes, None); ids (N,) GLOBAL row ids
    (replicated in-body).  Returns (N, D) replicated.
    """
    from jax.sharding import PartitionSpec as P

    axes = tuple(a for a in axes if a in mesh.axis_names)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    vocab = table.shape[0]
    if vocab % n_shards != 0:
        raise ValueError(f"vocab {vocab} must divide by {n_shards} shards")
    rows_per_shard = vocab // n_shards

    batch_axes = axes[1:]  # ids are batch-ordered: scatter back over these

    def body(tbl, all_ids):
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        lo = idx * rows_per_shard
        local = all_ids - lo
        hit = (local >= 0) & (local < rows_per_shard)
        local = jnp.clip(local, 0, rows_per_shard - 1)
        rows = jnp.take(tbl, local, axis=0)
        if out_dtype is not None:
            rows = rows.astype(out_dtype)
        rows = jnp.where(hit[..., None], rows, jnp.zeros((), rows.dtype))
        # reduction order matters for the wire (EXPERIMENTS.md §Perf iter 3):
        # psum_scatter over the batch axes FIRST (slices the result back to
        # each data shard's own bags - 1/|data| the bytes), THEN the small
        # psum over 'model'.
        for a in batch_axes:
            rows = jax.lax.psum_scatter(rows, a, scatter_dimension=0,
                                        tiled=True)
        return jax.lax.psum(rows, axes[0])

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axes, None), P(None)),
        out_specs=P(batch_axes if batch_axes else None, None),
        check_vma=False,
    )(table, ids)


def pad_vocab(vocab: int, n_shards: int) -> int:
    """Round a table's row count up so it row-shards evenly."""
    return ((vocab + n_shards - 1) // n_shards) * n_shards
