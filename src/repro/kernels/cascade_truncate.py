"""Pallas gather+cumsum truncation kernel for the survivor-compaction
round (cascade/engine.py CompactPlan execution).

One grid step per request.  The (G, U, cap) tables are viewed as
(G*U, 1, cap) row stacks, and the request's flat row id ``g*U + u`` is
SCALAR-PREFETCHED to drive the table BlockSpecs' index_map, so the
pipeline DMAs exactly the two (1, cap) rows the request needs - the
TPU-idiomatic gather (same structure as ``embedding_bag``).  A (1, cap)
block spans the last two dims of a (G*U, 1, cap) array, which is what
the TPU tiling rule asks of a block whose dims are not multiples of
(8, 128); the row axis itself is squeezed out of the kernel.  Inside
the kernel the truncation round is pure VPU/MXU work:

    mask   = p_row < n3[b]                 (survivors of the n3 cut)
    q      = inclusive cumsum of mask      (survivor prefix position)
    keep   = mask & (q <= expose)          (the exposed set)
    out[b] = sum(keep * clicks_row)        (revenue@expose)

The cumsum runs as a triangular-ones matmul so it maps onto the MXU
without any lane-wise scan support; counts are small integers, exact in
float32.  Each step writes its revenue across one lane-dense
(1, 128) output row; lane 0 is read back.  ``interpret=True`` (CPU)
runs the same body under the Pallas interpreter - that path is
CI-tested against the lax.scan engine path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


@functools.partial(jax.jit, static_argnames=("expose", "interpret"))
def compact_truncate_revenue(p_sorted: jnp.ndarray,
                             clicks_sorted: jnp.ndarray,
                             groups: jnp.ndarray, rows: jnp.ndarray,
                             n3: jnp.ndarray, *, expose: int,
                             interpret: bool = False) -> jnp.ndarray:
    """Revenue@expose per request from CompactPlan tables.

    p_sorted (G, U, C) int32 (sentinel >= cap for invalid slots),
    clicks_sorted (G, U, C) float32, groups/rows/n3 (B,) int32.
    Returns (B,) float32.  Matches engine._revenue_compact up to the
    association order of the final click sum.
    """
    g_n, u_n, c_n = p_sorted.shape
    b_n = groups.shape[0]
    # pad the lane axis to the TPU tile width; padded slots carry the
    # sentinel (>= any n3 <= cap) so they never survive the n3 cut
    c_pad = max(_LANES, ((c_n + _LANES - 1) // _LANES) * _LANES)
    if c_pad != c_n:
        p_sorted = jnp.pad(p_sorted, ((0, 0), (0, 0), (0, c_pad - c_n)),
                           constant_values=c_n)
        clicks_sorted = jnp.pad(clicks_sorted,
                                ((0, 0), (0, 0), (0, c_pad - c_n)))
    p_rows = p_sorted.reshape(g_n * u_n, 1, c_pad)
    c_rows = clicks_sorted.reshape(g_n * u_n, 1, c_pad)
    flat = groups.astype(jnp.int32) * u_n + rows.astype(jnp.int32)

    def kernel(f_ref, n3_ref, p_ref, c_ref, o_ref):
        b = pl.program_id(0)
        m = (p_ref[...] < n3_ref[b]).astype(jnp.float32)  # (1, c_pad)
        # inclusive cumsum as a triangular matmul: q[c] = sum_{c'<=c} m
        r_ids = jax.lax.broadcasted_iota(jnp.int32, (c_pad, c_pad), 0)
        c_ids = jax.lax.broadcasted_iota(jnp.int32, (c_pad, c_pad), 1)
        tri = (r_ids <= c_ids).astype(jnp.float32)
        q = jnp.dot(m, tri, preferred_element_type=jnp.float32)
        keep = m * (q <= expose).astype(jnp.float32)
        rev = jnp.sum(keep * c_ref[...], axis=1, keepdims=True)  # (1, 1)
        o_ref[...] = jnp.broadcast_to(rev, (1, _LANES))

    row = pl.BlockSpec((pl.Squeezed(), 1, c_pad),
                       lambda b, f, n3: (f[b], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b_n,),
        in_specs=[row, row],
        out_specs=pl.BlockSpec((pl.Squeezed(), 1, _LANES),
                               lambda b, f, n3: (b, 0, 0)),
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b_n, 1, _LANES), jnp.float32),
        interpret=interpret,
    )(flat, n3.astype(jnp.int32), p_rows, c_rows)
    return out[:, 0, 0]
