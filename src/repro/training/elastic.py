"""Elastic scaling + failure handling.

The failure model at 1000+ nodes: hosts disappear (preemption, hardware),
the job controller re-forms a smaller (or larger) mesh and relaunches.
SPMD/JAX handles this as RESHARD-ON-RESTORE, not in-band recovery:

  1. Trainer checkpoints atomically every N steps (training/checkpoint.py)
     and on SIGTERM (graceful eviction).
  2. On relaunch the controller calls ``remesh_restore`` with the NEW mesh;
     every leaf is device_put against its PartitionSpec on that mesh -
     the specs are mesh-shape-agnostic (axis NAMES, not sizes).
  3. The data pipeline seeks to the restored step (deterministic batches:
     no replay, no skew between hosts).

Straggler posture (documented, partially simulatable on one host):
  * synchronous SPMD absorbs micro-stragglers at every collective;
  * static shapes everywhere (padded sampler budgets, bucketed n_k) make
    step time data-independent - the main source of macro-stragglers in
    recsys/GNN workloads is eliminated by construction;
  * persistent macro-stragglers are handled by eviction + this restore
    path, which is the production-standard answer (borg/k8s).
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.training import checkpoint as ckpt_lib


@dataclass
class ElasticEvent:
    kind: str  # "shrink" | "grow" | "restart"
    old_shape: tuple
    new_shape: tuple
    step: int


class ElasticController:
    """Forms meshes, restores state across mesh changes, logs events."""

    def __init__(self, axis_names=("data", "model")):
        self.axis_names = tuple(axis_names)
        self.events: list[ElasticEvent] = []

    def make_mesh(self, shape: tuple):
        from repro.launch.mesh import make_mesh
        return make_mesh(tuple(shape), self.axis_names)

    def remesh_restore(self, ckpt_dir: str, target_state, shardings,
                       old_shape: tuple, new_shape: tuple):
        """Restore the latest checkpoint onto a new mesh shape.

        ``shardings`` is a PartitionSpec pytree matching ``target_state``;
        axis names must exist in both meshes (sizes may differ).
        """
        new_mesh = self.make_mesh(new_shape)
        state, manifest = ckpt_lib.restore(
            ckpt_dir, target_state, mesh=new_mesh, shardings=shardings)
        n_old, n_new = _n(old_shape), _n(new_shape)
        kind = ("shrink" if n_new < n_old
                else "grow" if n_new > n_old else "reshape")
        self.events.append(ElasticEvent(
            kind=kind,
            old_shape=tuple(old_shape), new_shape=tuple(new_shape),
            step=manifest["step"]))
        return state, new_mesh, manifest


def _n(shape):
    out = 1
    for s in shape:
        out *= s
    return out
