"""Production mesh definitions.

Axis semantics (DESIGN.md §6):
  pod   - crosses pod boundaries (DCN-connected); pure DP traffic only
  data  - in-pod data parallel + FSDP
  model - tensor / expert / vocab / embedding-row parallel (ICI-local)

``make_production_mesh`` is a FUNCTION (never called at import time) so
importing this module touches no jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P


def make_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with Auto axis types: shardings propagate as
    under ``jit``, and ``shard_map`` bodies see plain local arrays."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_num_shards(mesh) -> int:
    """GLOBAL device count of a mesh (1 for ``None``).

    ``mesh.shape`` spans every process of a multi-process mesh, so this
    is the count the serving pipeline's pad quantum and window bucketing
    MUST key off: padded shapes derive from (n, quantum) only, so every
    host computes the same bucket for the same window and per-shard
    slices divide evenly.  Host-local array building (how many rows
    THIS process materializes) keys off ``mesh_local_shards`` instead -
    conflating the two breaks pow2 bucketing the moment a second
    process joins (local count 1, global count P).
    """
    if mesh is None:
        return 1
    import numpy as np

    return int(np.prod(list(mesh.shape.values())))


def mesh_local_shards(mesh) -> int:
    """Shards of ``mesh`` owned by THIS process (1 for ``None``).

    Equal to ``mesh_num_shards`` in a single-process mesh; in a
    ``jax.distributed`` mesh it is the addressable-device count -
    what sizes the host-local slice of a request-sharded array.
    """
    if mesh is None:
        return 1
    pid = jax.process_index()
    return sum(1 for d in mesh.devices.flat if d.process_index == pid)


def process_shard_rows(mesh, b: int) -> list[tuple[int, int]]:
    """Row slices of a (b,)-request-sharded array held by THIS process.

    One ``[lo, hi)`` pair per addressable device, in mesh order: shard
    ``s`` of the 1-D request mesh holds rows ``[s*b/S, (s+1)*b/S)`` of
    the globally padded window (``S = mesh_num_shards``).  This is the
    routing table of the multi-host window protocol: each host builds
    exactly these rows of every window and never ships a request.
    """
    n_shards = mesh_num_shards(mesh)
    if b % n_shards:
        raise ValueError(f"b={b} not divisible by {n_shards} shards")
    per = b // n_shards
    pid = jax.process_index()
    return [(pos * per, (pos + 1) * per)
            for pos, d in enumerate(mesh.devices.flat)
            if d.process_index == pid]


def make_request_mesh(n_shards: int | None = None):
    """1-D mesh over the serving request axis (sharding.REQUEST_AXIS).

    The fused ServingPipeline shard_maps its window pass over this axis:
    per-request work (scoring, Eq. 10, cascade execution) stays local
    while the guard and the dual update stitch global sums.  Defaults to
    ALL devices - in a ``jax.distributed`` run ``jax.devices()`` spans
    every process, so the default mesh is the process-spanning request
    mesh (each host contributes its local devices; pass
    ``repro.distributed.multihost.initialize`` first).
    """
    from repro.distributed.sharding import REQUEST_AXIS

    n = n_shards if n_shards is not None else len(jax.devices())
    return make_mesh((n,), (REQUEST_AXIS,))


def resolve_spec(spec, mesh):
    """Drop axis names not present in ``mesh`` from a PartitionSpec.

    Lets one spec tree (written against the multi-pod axis set) serve both
    the (data, model) and (pod, data, model) meshes.
    """
    if spec is None:
        return None
    names = set(mesh.axis_names)

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names)
            return kept if kept else None
        return entry if entry in names else None

    return P(*(keep(e) for e in spec))


def tree_named_shardings(spec_tree, mesh):
    """PartitionSpec pytree -> NamedSharding pytree (specs resolved)."""
    is_spec = lambda x: isinstance(x, P) or x is None

    def conv(s):
        if s is None:
            return jax.sharding.NamedSharding(mesh, P())
        return jax.sharding.NamedSharding(mesh, resolve_spec(s, mesh))

    return jax.tree_util.tree_map(conv, spec_tree, is_leaf=is_spec)


def device_bytes_estimate(arg_specs, spec_tree, mesh) -> int:
    """Per-device input bytes from shapes + shardings (backup for
    backends whose compiled.memory_analysis() is unavailable)."""
    import numpy as np

    is_spec = lambda x: isinstance(x, P) or x is None
    specs_flat = jax.tree_util.tree_leaves(spec_tree, is_leaf=is_spec)
    args_flat = jax.tree_util.tree_leaves(arg_specs)
    total = 0
    for arr, spec in zip(args_flat, specs_flat):
        if not hasattr(arr, "shape"):
            continue
        shards = 1
        spec = resolve_spec(spec, mesh) if spec is not None else P()
        for entry in (spec or P()):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                shards *= mesh.shape[a]
        size = int(np.prod(arr.shape)) if arr.shape else 1
        total += size * arr.dtype.itemsize // max(1, shards)
    return total
