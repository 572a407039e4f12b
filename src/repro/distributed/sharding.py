"""Sharding helpers shared by the model zoo and the launchers.

Models never hard-code a mesh: they call ``constrain(x, axes...)`` which is
an identity outside a mesh context (CPU unit tests) and a
``with_sharding_constraint`` under ``jax.set_mesh`` (dry-run / production).

Axis-name conventions (see launch/mesh.py):
  pod    - slowest axis, crosses ICI-over-DCN boundaries (multi-pod DP)
  data   - in-pod data parallel (+ FSDP for LM params)
  model  - tensor / expert / vocab / embedding-row parallel
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

BATCH_AXES = ("pod", "data")  # logical batch axis = pod x data

# Serving request axis: the fused ServingPipeline shard_maps its window
# pass over a 1-D ("req",) mesh (launch.mesh.make_request_mesh); guard
# prefix sums and dual consumption stitch across it with
# all_gather/psum.  One name, shared by mesh builders and the pipeline.
REQUEST_AXIS = "req"


def ordered_psum(x, axis_name: str):
    """Cross-shard sum with a shard-ORDER-DETERMINISTIC reduction.

    ``jax.lax.psum`` lowers to the backend allreduce, whose reduction
    order (and therefore float rounding) differs between XLA's
    in-process collectives and the cross-process gloo/NCCL rings - the
    same window summed by an 8-device single process and by 8
    one-device processes can disagree in the last ulp, which breaks
    the serving path's bitwise lambda/spend parity gates.  Gathering
    first and summing locally pins one association: ``all_gather`` is
    pure data movement (no arithmetic), and the local ``jnp.sum`` over
    the leading shard axis compiles to the same reduction everywhere,
    so every host of a multi-process mesh - and the identically-sharded
    single-process reference - produces bit-identical replicated sums.

    Use at the serving path's cross-shard seams (guard spends, dual
    consumption, per-axis spend reports) where bitwise multi-host
    agreement matters more than allreduce bandwidth; ``pmax``/``pmin``
    are order-invariant and need no such pinning.
    """
    return jnp.sum(jax.lax.all_gather(x, axis_name), axis=0)


def current_mesh():
    """The ambient mesh set by ``jax.set_mesh``, or None."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def mesh_axes(mesh=None) -> tuple:
    m = mesh or current_mesh()
    return tuple(m.axis_names) if m is not None else ()


def batch_spec(mesh=None) -> tuple:
    """The tuple of axes the batch dim shards over, filtered to the mesh."""
    names = mesh_axes(mesh)
    got = tuple(a for a in BATCH_AXES if a in names)
    return got if got else None


def constrain(x, *spec):
    """with_sharding_constraint that degrades to identity w/o a mesh.

    spec entries: None, an axis name, or a tuple of axis names.  Axis names
    not present in the ambient mesh are dropped (lets the same model code
    run on the single-pod and multi-pod meshes).
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names)
            return kept if kept else None
        return entry if entry in names else None

    return jax.lax.with_sharding_constraint(x, P(*(keep(s) for s in spec)))


def constrain_batch(x):
    """Shard the leading dim over (pod, data); replicate the rest."""
    mesh = current_mesh()
    if mesh is None:
        return x
    bs = batch_spec(mesh)
    return constrain(x, bs, *([None] * (x.ndim - 1)))


def named_sharding(mesh, *spec):
    return jax.sharding.NamedSharding(mesh, P(*spec))
