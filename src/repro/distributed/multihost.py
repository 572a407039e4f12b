"""Multi-host request mesh: bring-up + cross-host window routing.

The serving pipeline scales past one host by sharding the REQUEST axis
across a ``jax.distributed`` process group: ``initialize`` wires this
process into the group (gloo collectives on CPU, NCCL/ICI elsewhere),
``make_request_mesh`` then spans every process's devices, and
``MultihostSource`` routes each traffic window so that every host
GENERATES its own slice instead of receiving it over the wire.

The routing protocol (the tentpole invariant of the multi-host path):

  1. Window t's arrival list is a pure function of ``(seed, t)``
     (``RequestSource.arrivals``), so every host computes the FULL
     global list for free - no request is ever shipped between hosts.
  2. Every host derives the same padded layout from ``(n, bucket)``
     alone (``serving.pipeline.window_layout``), so the global row ->
     request permutation agrees bitwise everywhere.
  3. ``launch.mesh.process_shard_rows`` tells this host which padded
     row ranges its devices own; the host materializes contexts and
     compact score tables for exactly those requests
     (``RequestSource.window_for_users``) and sentinel-fills its pad
     rows the same way ``ServingPipeline._pad_chunk_tables`` does.
  4. The fused pass runs as one SPMD program over the process-spanning
     mesh: per-request work stays device-local while the guard
     prefix-sums, per-axis spends and the nearline dual update stitch
     globally over deterministic ``ordered_psum``/``all_gather``
     collectives - every host agrees BITWISE on lambda and on every
     decision, with zero steady-state recompiles per host.

Elasticity follows training/elastic.py's reshard-on-restore posture:
``jax.distributed`` cannot change world size in-band, so a host
join/leave checkpoints the tiny stream state (window cursor + dual
chain + seed), re-forms the mesh at the new size and REPLAYS the
in-flight window - windows are pure ``(seed, t)`` functions, so the
resumed stream picks up exactly where the old one stopped.

Single-host remains the default everywhere: ``initialize`` is a no-op
without a coordinator, and nothing in this module imports at serve
time unless multi-host is requested.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass

import numpy as np

# -- process-group bring-up -------------------------------------------------

_ENV_COORD = "GREENFLOW_COORDINATOR"
_ENV_NPROC = "GREENFLOW_NUM_PROCESSES"
_ENV_PID = "GREENFLOW_PROCESS_ID"


def initialize(*, coordinator: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None,
               local_device_ids=None) -> bool:
    """Join the ``jax.distributed`` process group (no-op single-host).

    Arguments default to the ``GREENFLOW_COORDINATOR`` /
    ``GREENFLOW_NUM_PROCESSES`` / ``GREENFLOW_PROCESS_ID`` environment
    variables, so a launcher can configure children purely through the
    environment.  Returns True when the group was joined (after which
    ``jax.devices()`` spans every process and ``make_request_mesh``
    builds the process-spanning request mesh), False when running
    single-process.  MUST be called before any other jax API touches
    the backend.
    """
    if coordinator is None:
        coordinator = os.environ.get(_ENV_COORD) or None
    if num_processes is None:
        num_processes = int(os.environ.get(_ENV_NPROC, "1"))
    if process_id is None:
        pid_env = os.environ.get(_ENV_PID)
        process_id = int(pid_env) if pid_env is not None else None
    if coordinator is None or int(num_processes) <= 1:
        return False
    import jax

    # CPU backends stitch cross-host collectives over gloo; accelerators
    # keep their own transport.  Read from the platform setting, since
    # the backend itself must not come up before the group is joined.
    if "cpu" in (jax.config.jax_platforms or "").split(","):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=int(num_processes),
        process_id=None if process_id is None else int(process_id),
        local_device_ids=local_device_ids)
    return True


def host_report() -> dict:
    """This process's view of the group: identity + device census.

    The per-host provenance block of BENCH_multihost.json and the
    ``host`` label on flight-recorder rows both come from here.
    """
    import jax

    local = jax.local_devices()
    return {
        "process_index": int(jax.process_index()),
        "process_count": int(jax.process_count()),
        "local_devices": len(local),
        "global_devices": len(jax.devices()),
        "platform": local[0].platform if local else "none",
    }


def host_label(index: int | None = None) -> str:
    """Canonical per-host label (``host0``, ``host1``, ...) used by the
    flight recorder's JSONL rows and Perfetto process names."""
    if index is None:
        import jax

        index = jax.process_index()
    return f"host{int(index)}"


# -- cross-host window routing ---------------------------------------------


@dataclass
class HostWindowSlice:
    """This host's slice of one globally laid-out window.

    ``valid``/``k_of`` cover the host's LOCAL padded rows (one
    contiguous ``b/S`` block per addressable device, in mesh order);
    ``n``/``b`` are the GLOBAL request count and padded bucket every
    host agrees on.  ``ServingPipeline.serve_window(..., shard=...)``
    consumes this instead of re-deriving the layout from ``len(rows)``.
    """

    n: int  # global request count of the window
    b: int  # global padded bucket (window_bucket(n))
    valid: np.ndarray  # (local_rows,) float32 1=real request, 0=pad
    k_of: np.ndarray | None = None  # (local_rows,) int32 tenant ids
    rows_global: np.ndarray | None = None  # (local_rows,) global row ids

    @property
    def local_rows(self) -> int:
        return int(len(self.valid))


class MultihostSource:
    """Route an inner ``RequestSource`` across the request mesh.

    Wraps any source with ``arrivals``/``window_for_users`` (generated
    or replayed) and a mesh-attached ``ServingPipeline``; ``window(t,
    n)`` produces the WindowChunk for THIS host's slice of the global
    window - contexts and compact tables for only the rows its devices
    own, sentinel-padded exactly like the single-process padded path,
    plus the ``HostWindowSlice`` that tells ``serve_window`` the global
    layout.  Drop-in for ``run_stream``'s ``source`` argument.
    """

    def __init__(self, inner, pipeline):
        from repro.launch.mesh import mesh_num_shards

        if pipeline.mesh is None:
            raise ValueError("MultihostSource needs a mesh-attached "
                             "pipeline (ServingPipeline(mesh=...))")
        if getattr(pipeline, "_cap", None) is None:
            raise ValueError("multihost routing needs the compact (k3) "
                             "table layout; this pipeline runs the "
                             "generic scan kernel")
        self.inner = inner
        self.pipeline = pipeline
        self.mesh = pipeline.mesh
        self.n_shards = mesh_num_shards(self.mesh)
        # forwarded so run_stream/launchers treat this like any source
        self.chains = getattr(inner, "chains", None)
        self.expose = getattr(inner, "expose", None)
        self.seed = getattr(inner, "seed", None)

    @property
    def universe(self):
        return self.inner.universe

    def arrivals(self, t: int, n: int) -> np.ndarray:
        return self.inner.arrivals(t, n)

    def window(self, t: int, n: int):
        """THIS host's chunk of global window t (see module docstring
        for the routing protocol)."""
        from repro.data.request_source import WindowChunk
        from repro.launch.mesh import process_shard_rows
        from repro.serving.pipeline import window_layout

        pipe = self.pipeline
        users = np.asarray(self.inner.arrivals(t, n))
        b = pipe.window_bucket(n)
        t_n = (None if pipe.tenant_budgets is None
               else len(pipe.tenant_budgets))
        perm, valid, k_of = window_layout(n, b, t_n)

        slices = process_shard_rows(self.mesh, b)
        rows_global = np.concatenate(
            [np.arange(lo, hi, dtype=np.intp) for lo, hi in slices])
        valid_l = valid[rows_global]
        mask = valid_l > 0
        perm_l = perm[rows_global]

        # materialize ONLY this host's real requests, then scatter them
        # into the sentinel-padded local rows (pad rows: zero context,
        # cap-filled p, zero ck - the same fill _pad_chunk_tables uses,
        # masked out by ``valid`` before anything reads them)
        part = self.inner.window_for_users(users[perm_l[mask]])
        ctx_m = np.asarray(part.ctx, np.float32)
        p_m = np.asarray(part.tables["p"], np.int32)
        ck_m = np.asarray(part.tables["ck"], np.float32)

        n_local = len(rows_global)
        ctx_l = np.zeros((n_local, ctx_m.shape[1]), np.float32)
        ctx_l[mask] = ctx_m
        g_n, _, cap = p_m.shape
        p_l = np.full((g_n, n_local, cap), pipe._cap, np.int32)
        p_l[:, mask, :] = p_m
        ck_l = np.zeros((g_n, n_local, cap), np.float32)
        ck_l[:, mask, :] = ck_m

        # per-device LOCAL row ids: each shard gathers within its own
        # b/S-row table slice, so rows restart at 0 on every device
        per = b // self.n_shards
        rows_l = np.tile(np.arange(per, dtype=np.int32), len(slices))

        shard = HostWindowSlice(
            n=int(n), b=int(b), valid=valid_l.astype(np.float32),
            k_of=None if k_of is None else
            np.asarray(k_of[rows_global], np.int32),
            rows_global=rows_global.astype(np.int64))
        return WindowChunk(ctx=ctx_l, rows=rows_l,
                           tables={"p": p_l, "ck": ck_l},
                           users=None,
                           h2d_bytes=int(getattr(part, "h2d_bytes", 0)),
                           shard=shard)


class ShiftedSource:
    """``inner`` with its window clock shifted by ``t0``.

    ``run_stream`` always counts windows from 0; a resumed stream
    serves ``sizes[t0:]``, so the source must map local window t back
    to GLOBAL window ``t + t0`` - arrivals are pure ``(seed, t)``
    functions, so the shifted source replays exactly the traffic the
    interrupted run would have served next.  Wrap the inner source
    BEFORE handing it to ``MultihostSource``.
    """

    def __init__(self, inner, t0: int):
        self.inner = inner
        self.t0 = int(t0)
        self.chains = getattr(inner, "chains", None)
        self.expose = getattr(inner, "expose", None)
        self.seed = getattr(inner, "seed", None)

    @property
    def universe(self):
        return self.inner.universe

    def arrivals(self, t: int, n: int) -> np.ndarray:
        return self.inner.arrivals(t + self.t0, n)

    def window(self, t: int, n: int):
        return self.inner.window(t + self.t0, n)

    def window_for_users(self, users: np.ndarray):
        return self.inner.window_for_users(users)


# -- elastic re-sharding (reshard-on-restore for the stream) ---------------


@dataclass
class StreamCheckpoint:
    """The tiny durable state of a streaming run: everything a NEW
    process group (any size) needs to resume bitwise-consistently.

    Windows are pure ``(seed, t)`` functions, so no request data is
    saved - only the cursor of the next window to serve, the dual
    chain (host values of lambda_t and the recorded entry price), and
    the source seed.  The in-flight window at checkpoint time is NOT in
    ``t_next``: the restarted group replays it (same seed, same t ->
    same traffic, same decisions).
    """

    t_next: int
    lam: object  # host pytree of the published price chain
    lam_rec: object
    seed: int
    n_shards: int  # world size that WROTE this (provenance only)


def _host_value(arr):
    """A (possibly multi-process, fully-replicated) array's host value."""
    if getattr(arr, "is_fully_addressable", True):
        return np.asarray(arr)
    return np.asarray(arr.addressable_shards[0].data)


def checkpoint_stream(path: str, pipeline, *, t_next: int,
                      seed: int) -> str:
    """Write a ``StreamCheckpoint`` for ``pipeline``'s dual chain.

    Atomic (write + rename) and host-independent: every host holds the
    same replicated chain, so any ONE host's write is the truth - in a
    multi-host run call on process 0 only, or on all (last rename
    wins, bytes identical).
    """
    import jax

    from repro.launch.mesh import mesh_num_shards

    to_list = lambda x: np.asarray(_host_value(x), np.float64).tolist()
    blob = {
        "t_next": int(t_next),
        "lam": jax.tree_util.tree_map(to_list, pipeline.lam),
        "lam_rec": jax.tree_util.tree_map(to_list, pipeline._lam_rec),
        "seed": int(seed),
        "n_shards": int(mesh_num_shards(pipeline.mesh)),
    }
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(blob, f)
    os.replace(tmp, path)
    return path


def restore_stream(path: str, pipeline) -> StreamCheckpoint:
    """Load a ``StreamCheckpoint`` into ``pipeline``'s dual chain.

    The new group may be ANY size (reshard-on-restore): lambda is
    replicated state, so restoring it onto a different mesh is a plain
    broadcast - the pipeline re-replicates lazily on the next
    multi-host window.  Returns the checkpoint; the caller resumes the
    stream at ``t_next`` (serving windows ``sizes[t_next:]``).
    """
    import jax
    import jax.numpy as jnp

    with open(path) as f:
        blob = json.load(f)
    back = lambda old, saved: jnp.asarray(
        np.asarray(saved, np.float32).reshape(np.shape(_host_value(old))))
    pipeline.lam = jax.tree_util.tree_map(
        lambda old, saved: back(old, saved), pipeline.lam, blob["lam"])
    pipeline._lam_rec = jax.tree_util.tree_map(
        lambda old, saved: back(old, saved), pipeline._lam_rec,
        blob["lam_rec"])
    pipeline._mh_lam = False  # re-replicate on the next multi-host window
    return StreamCheckpoint(
        t_next=int(blob["t_next"]), lam=blob["lam"],
        lam_rec=blob["lam_rec"], seed=int(blob["seed"]),
        n_shards=int(blob["n_shards"]))
