"""RequestSource: generate, score and serve request windows on the fly.

The materialized serving path precomputes the whole per-user universe
up front - four (U, I) stage-score matrices, (M, U, I) orderings, a
(U, I) click realization and the (G, U, cap) CompactPlan tables - and
every window merely indexes into it.  That tops out at a few thousand
users: at U >= 100k those tables are hundreds of MB to GB of host RSS
before the first request arrives.

A ``RequestSource`` inverts the dataflow.  Each window is produced on
demand as a ``WindowChunk``: sampled arrivals, their reward contexts,
and a PER-WINDOW (G, n, cap) slice of compact execution tables - the
decision-independent cascade arithmetic for exactly the users who
showed up.  The fused ``ServingPipeline`` pass consumes the chunk
unchanged (its tables are a traced argument, so bucketed padding keeps
the jit cache warm), and host memory scales with the WINDOW size, never
with the universe size.

Two sources cover the two serving regimes:

  * ``GeneratedSource`` - the open-world path: arrivals sampled from an
    unbounded user universe (``data.synthetic.StreamingWorld``), user
    rows hash-generated on demand, stage models scored per window in
    fixed-shape chunks (one jit cache entry regardless of traffic), and
    clicks realized per (user, item) so repeat visitors are consistent.
    This is what drives ``benchmarks/bench_scale.py`` at U >= 100k.
  * ``TableReplaySource`` - the fixed-replay path: per-user tables
    precomputed once (in memory, or memmapped ``.npy`` files via
    ``save``/``load`` so only the touched rows page in), windows gather
    row slices.  Built ``from_server`` it is BITWISE identical to
    serving the materialized ``CascadeServer`` - the parity gate in
    tests/test_request_source.py.

``source.universe`` is the server-shaped handle a streaming
``ServingPipeline`` is constructed over: the chain set and compact
LAYOUT (group maps + row width) without any per-user tables; every
``serve_window`` call must then carry a chunk's tables.
"""
from __future__ import annotations

import json
import os
import threading
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.cascade.engine import (CascadeModels, CompactPlan, _k3_layout,
                                  _compact_group_tables,
                                  _compact_group_tables_jax, _user_batch,
                                  build_compact_layout)
from repro.data.synthetic import StreamingWorld, World
from repro.obs import current


_LANES = 128  # a TPU tile's minor dimension


def replay_width(g: int, cap: int) -> int:
    """Row width of a user-major replay table: G·cap rounded up to whole
    128-lane tiles.  At such a width the chip lays a (U, W) table out
    row-major, so a gather of its rows copies nothing else; at another
    width its layout puts the user axis minor, and every gather would
    first re-lay the whole table out."""
    return -(-g * cap // _LANES) * _LANES


@partial(jax.jit, static_argnames="width")
def replay_rows(table, width):
    """A (G, U, cap) replay table as (U, width): user u's G rows side by
    side, zero-padded to ``width`` columns."""
    g, u, cap = table.shape
    rows = jnp.transpose(table, (1, 0, 2)).reshape(u, g * cap)
    return jnp.pad(rows, ((0, 0), (0, width - g * cap)))


@partial(jax.jit, static_argnames=("g", "cap"))
def replay_gather(table, users, g, cap):
    """Rows ``users`` of a device-resident (U, W) ``replay_rows`` table,
    as the chunk's (G, n, cap); compiled as ``jit_replay_gather``, the
    name the device trace shows."""
    rows = jnp.take(table, users, axis=0)[:, :g * cap]
    return jnp.transpose(rows.reshape(-1, g, cap), (1, 0, 2))


@dataclass
class WindowChunk:
    """One window's worth of requests, self-contained.

    ``rows`` are LOCAL indices into ``tables`` (0..n-1): a chunk carries
    its own (G, n, cap) compact tables, so the fused pass gathers within
    the chunk instead of a global user axis.  ``users`` keeps the global
    ids for logging/attribution only - nothing downstream indexes them.
    """

    ctx: np.ndarray  # (n, d_context) float32 reward contexts
    rows: np.ndarray  # (n,) int32 local row indices (arange)
    tables: dict  # {"p": (G, n, cap) int32, "ck": (G, n, cap) float32}
    users: np.ndarray | None = None  # (n,) global user ids
    h2d_bytes: int = 0  # host->device bytes this chunk's production cost
    shard: object | None = None  # HostWindowSlice in a multi-host stream

    @property
    def n(self) -> int:
        if self.shard is not None:
            return int(self.shard.n)
        return int(len(self.rows))


@dataclass
class StreamUniverse:
    """The server-shaped handle a streaming pipeline builds against:
    chain set + compact layout (``build_compact_layout``: group maps and
    row width, EMPTY per-user tables).  ``stream_only`` marks that every
    ``serve_window`` call must bring a chunk's tables."""

    chains: object
    compact: CompactPlan
    expose: int
    stream_only: bool = True


class RequestSource:
    """Base: arrival sampling + per-window chunk production.

    Subclasses set ``chains``, ``expose``, ``n_users``, ``seed`` and
    implement ``window(t, n)``.  Window t is a pure function of
    (seed, t) - re-running a stream replays identical traffic.
    """

    chains = None
    expose: int = 0
    n_users: int = 0
    seed: int = 0
    obs = None  # a source built without a bundle records into current()

    def _spans(self):
        """The bundle this source's spans go to: its own if it records,
        else the one whose span is open on the calling thread."""
        obs = self.obs
        return obs if obs is not None and obs.enabled else current()

    def arrivals(self, t: int, n: int) -> np.ndarray:
        """(n,) sampled user ids for window t (uniform arrivals)."""
        with self._spans().span("arrivals", n=n):
            rng = np.random.default_rng((self.seed, t))
            return rng.integers(0, self.n_users, size=n)

    def window(self, t: int, n: int) -> WindowChunk:
        raise NotImplementedError

    def window_for_users(self, users: np.ndarray) -> WindowChunk:
        """Chunk for an EXPLICIT arrival list (rows = arange(len)).

        The multi-host routing layer depends on this split of
        ``window``: every host can compute the full ``arrivals(t, n)``
        cheaply (a pure (seed, t) function), then materialize contexts
        and score tables for ONLY the slice of users it serves.
        """
        raise NotImplementedError

    @property
    def universe(self) -> StreamUniverse:
        lay = build_compact_layout(self.chains, n_items=self._n_items(),
                                   expose=self.expose)
        if lay is None:
            raise ValueError(
                "streaming sources need the k3 cascade layout (single "
                "recall/prerank model pools); this chain set compiles "
                "to the generic scan kernel, which has no chunked form")
        return StreamUniverse(self.chains, lay, self.expose)

    def _n_items(self) -> int:
        raise NotImplementedError


class GeneratedSource(RequestSource):
    """On-the-fly request generation from a ``StreamingWorld``.

    Per window: sample arrivals, hash-materialize exactly those user
    rows, score the four stage models over the corpus in FIXED-SHAPE
    chunks (padded to ``chunk`` users - one compiled shape for any
    traffic level), realize per-(user, item) clicks, and compact the
    (n, I) scores into the (G, n, cap) execution tables.  Peak host
    memory is O(chunk * I) transient + O(n * G * cap) for the chunk
    tables - independent of ``cfg.n_users``.

    With ``device_tables=True`` (the default) the stage scores never
    leave the device: compaction runs as a jitted pass
    (``_compact_group_tables_jax``, bitwise equal to the host builder)
    at the fixed chunk shape, ``WindowChunk.tables`` hold jax arrays
    end-to-end (the pipeline pads them on device), and a slab-keyed
    LRU cache of ``table_cache`` chunk tables lets repeat-visitor
    chunks skip hashing/scoring entirely (``cache_hits``/
    ``cache_misses`` count lookups).  ``workers`` > 1 scores a
    window's chunks on a thread pool - each chunk is a pure function
    of its arrival ids, so the parallel window is bitwise identical
    to the sequential one.  ``device_tables=False`` keeps the PR 6
    host-built numpy tables (the parity reference).
    """

    def __init__(self, world: StreamingWorld, models: CascadeModels,
                 chains, *, expose: int, seed: int = 0, chunk: int = 512,
                 item_block: int = 256, device_tables: bool = True,
                 table_cache: int = 64, workers: int | None = None,
                 obs=None):
        self.world = world
        self.models = models
        self.chains = chains
        self.expose = int(expose)
        self.seed = int(seed)
        self.chunk = int(chunk)
        self.item_block = int(item_block)
        self.n_users = int(world.cfg.n_users)
        self._lay = _k3_layout(chains, n_items=world.cfg.n_items)
        if self._lay is None:
            raise ValueError("GeneratedSource needs the k3 cascade layout")
        self._score_fns = None  # built lazily (jax import cost)
        self.device_tables = bool(device_tables)
        if workers is None:
            workers = max(1, min(4, (os.cpu_count() or 2) - 1))
        self.workers = int(workers)
        self._table_fn = None  # jitted device compaction (lazy)
        self._cache: OrderedDict = OrderedDict()  # slab key -> tables
        self._cache_cap = int(table_cache)
        self._lock = threading.Lock()
        self._pool = None
        # the plain ints stay authoritative (bench/report reads survive
        # a disabled registry); the obs counters mirror them
        self.cache_hits = 0
        self.cache_misses = 0
        from repro.obs import get_obs
        self.obs = get_obs(obs)
        self._hits_c = self.obs.metrics.counter(
            "greenflow_table_cache_hits_total",
            "slab-table cache hits (a hit IS the chunk result)")
        self._misses_c = self.obs.metrics.counter(
            "greenflow_table_cache_misses_total",
            "slab-table cache misses (chunk scored + compacted)")

    def _n_items(self) -> int:
        return int(self.world.cfg.n_items)

    @property
    def d_context(self) -> int:
        return self.world.d_context

    # -- fixed-shape stage scoring ---------------------------------------

    def _build_score_fns(self):
        """One jitted closure per stage model at the FIXED chunk shape -
        the per-window scoring analogue of the pipeline's bucketed
        padding: any window size reuses the same compiled kernels."""

        from repro.models.recsys import dien, din, dssm, ydnn

        models = self.models
        n_items = self._n_items()
        item_ids = jnp.arange(n_items, dtype=jnp.int32)
        item_cats = jnp.asarray(self.world.item_cat, jnp.int32)
        if models.dssm_cfg.n_item_fields == 1:
            dssm_item_fields = jnp.stack([item_cats], axis=-1)
        else:
            dssm_item_fields = jnp.stack([item_ids, item_cats], axis=-1)

        @jax.jit
        def dssm_all(uf):
            v = dssm.item_tower(models.dssm_params, models.dssm_cfg,
                                dssm_item_fields)
            u = dssm.user_tower(models.dssm_params, models.dssm_cfg, uf)
            return u @ v.T

        @jax.jit
        def ydnn_all(hist, mask, uf):
            u = ydnn.user_vector(models.ydnn_params, models.ydnn_cfg,
                                 hist, mask, uf)
            v = models.ydnn_params["out_emb"]["table"][:n_items]
            return u @ v.T

        @jax.jit
        def din_block(batch, cand_ids, cand_cats):
            return din.score(models.din_params, models.din_cfg, batch,
                             cand_ids, cand_cats)

        @jax.jit
        def dien_block(batch, cand_ids, cand_cats):
            return dien.score(models.dien_params, models.dien_cfg, batch,
                              cand_ids, cand_cats)

        self._score_fns = {"DSSM": dssm_all, "YDNN": ydnn_all,
                           "DIN": din_block, "DIEN": dien_block}
        self._item_ids = item_ids
        self._item_cats = item_cats

    def _score_slab(self, slab: World, n_real: int) -> dict:
        """{name: (n_real, I) float np} stage scores for a slab, padded
        to the fixed chunk shape for the jitted kernels."""
        if self._score_fns is None:
            self._build_score_fns()
        c = self.chunk
        ub = _user_batch(slab, np.arange(n_real))
        pad = c - n_real
        if pad:
            ub = {k: jnp.concatenate(
                [v, jnp.zeros((pad, *v.shape[1:]), v.dtype)])
                for k, v in ub.items()}
        scores = {
            "DSSM": np.asarray(self._score_fns["DSSM"](
                ub["user_fields"]))[:n_real],
            "YDNN": np.asarray(self._score_fns["YDNN"](
                ub["hist_ids"], ub["hist_mask"],
                ub["user_fields"]))[:n_real],
        }
        n_items = self._n_items()
        for name in ("DIN", "DIEN"):
            fn = self._score_fns[name]
            cols = []
            for lo in range(0, n_items, self.item_block):
                hi = min(n_items, lo + self.item_block)
                ids = jnp.broadcast_to(self._item_ids[lo:hi], (c, hi - lo))
                cats = jnp.broadcast_to(self._item_cats[lo:hi],
                                        (c, hi - lo))
                cols.append(np.asarray(fn(ub, ids, cats))[:n_real])
            scores[name] = np.concatenate(cols, axis=1)
        return scores

    def _score_slab_dev(self, slab: World, n_real: int):
        """Device twin of ``_score_slab``: the same jitted kernels at the
        same fixed chunk shape, but the (chunk, I) score slabs STAY jax
        arrays (no ``np.asarray`` sync, no host copy) - rows past
        ``n_real`` carry padding garbage the caller slices off on
        device.  Returns ({name: (chunk, I) jax f32}, h2d_bytes)."""
        if self._score_fns is None:
            self._build_score_fns()
        c = self.chunk
        ub = _user_batch(slab, np.arange(n_real))
        pad = c - n_real
        if pad:
            ub = {k: jnp.concatenate(
                [v, jnp.zeros((pad, *v.shape[1:]), v.dtype)])
                for k, v in ub.items()}
        h2d = sum(int(v.size) * v.dtype.itemsize for v in ub.values())
        scores = {
            "DSSM": self._score_fns["DSSM"](ub["user_fields"]),
            "YDNN": self._score_fns["YDNN"](ub["hist_ids"],
                                            ub["hist_mask"],
                                            ub["user_fields"]),
        }
        n_items = self._n_items()
        for name in ("DIN", "DIEN"):
            fn = self._score_fns[name]
            cols = []
            for lo in range(0, n_items, self.item_block):
                hi = min(n_items, lo + self.item_block)
                ids = jnp.broadcast_to(self._item_ids[lo:hi], (c, hi - lo))
                cats = jnp.broadcast_to(self._item_cats[lo:hi],
                                        (c, hi - lo))
                cols.append(fn(ub, ids, cats))
            scores[name] = (cols[0] if len(cols) == 1
                            else jnp.concatenate(cols, axis=1))
        return scores, h2d

    # -- device chunk tables (jitted compaction + slab cache) --------------

    def _build_table_fn(self):
        lay = self._lay

        @jax.jit
        def build(scores, clicks):
            return _compact_group_tables_jax(scores, lay, clicks)

        self._table_fn = build

    def _chunk_tables(self, ids: np.ndarray):
        """One scoring chunk -> (ctx, p_dev, ck_dev, h2d_bytes), via the
        slab cache when these exact arrivals were produced before (a
        chunk is a pure function of its ids, so a hit IS the result)."""
        key = (len(ids), ids.tobytes())
        with self._lock:
            hit = self._cache.get(key)
            if hit is not None:
                self._cache.move_to_end(key)
                self.cache_hits += 1
                self._hits_c.inc()
                return (*hit, 0)
            self.cache_misses += 1
            self._misses_c.inc()
        m = len(ids)
        slab = self.world.user_slab(ids)
        ctx = slab.reward_context(np.arange(m))
        scores, h2d = self._score_slab_dev(slab, m)
        if self._table_fn is None:
            self._build_table_fn()
        clicks = self.world.clicks_slab(ids, slab, pad_rows=self.chunk)
        h2d += clicks.nbytes
        p, ck = self._table_fn(scores, jnp.asarray(clicks))
        if m != self.chunk:  # static device slice to the real rows
            p, ck = p[:, :m], ck[:, :m]
        with self._lock:
            self._cache[key] = (ctx, p, ck)
            while len(self._cache) > self._cache_cap:
                self._cache.popitem(last=False)
        return ctx, p, ck, h2d

    # -- window production -----------------------------------------------

    def window(self, t: int, n: int) -> WindowChunk:
        if n == 0:
            lay = build_compact_layout(self.chains,
                                       n_items=self._n_items(),
                                       expose=self.expose)
            g_n, cap = lay.p_sorted.shape[0], lay.cap
            return WindowChunk(
                ctx=np.zeros((0, self.d_context), np.float32),
                rows=np.zeros(0, np.int32),
                tables={"p": np.zeros((g_n, 0, cap), np.int32),
                        "ck": np.zeros((g_n, 0, cap), np.float32)},
                users=np.zeros(0, np.int64))
        return self.window_for_users(self.arrivals(t, n), _t=t)

    def window_for_users(self, users: np.ndarray,
                         _t: int | None = None) -> WindowChunk:
        users = np.asarray(users)
        n = len(users)
        if not self.device_tables:  # host-built numpy tables (PR 6 path)
            ctx_parts, p_parts, ck_parts = [], [], []
            for lo in range(0, n, self.chunk):
                ids = users[lo:lo + self.chunk]
                slab = self.world.user_slab(ids)
                ctx_parts.append(slab.reward_context(np.arange(len(ids))))
                scores = self._score_slab(slab, len(ids))
                clicks = self.world.clicks_slab(ids, slab)
                p, ck, _cap = _compact_group_tables(
                    scores, self._lay, clicks, expose=self.expose)
                p_parts.append(p.astype(np.int32))
                ck_parts.append(ck.astype(np.float32))
            return WindowChunk(
                ctx=np.concatenate(ctx_parts, axis=0),
                rows=np.arange(n, dtype=np.int32),
                tables={"p": np.concatenate(p_parts, axis=1),
                        "ck": np.concatenate(ck_parts, axis=1)},
                users=users)
        chunk_ids = [users[lo:lo + self.chunk]
                     for lo in range(0, n, self.chunk)]
        with self._spans().span("chunk_tables", t=_t, n=n,
                                chunks=len(chunk_ids)):
            if self.workers > 1 and len(chunk_ids) > 1:
                if self._pool is None:
                    self._pool = ThreadPoolExecutor(
                        max_workers=self.workers,
                        thread_name_prefix="chunk-score")
                parts = list(self._pool.map(self._chunk_tables,
                                            chunk_ids))
            else:
                parts = [self._chunk_tables(ids) for ids in chunk_ids]
        if len(parts) == 1:
            ctx, p, ck, h2d = parts[0]
        else:
            ctx = np.concatenate([pt[0] for pt in parts], axis=0)
            p = jnp.concatenate([pt[1] for pt in parts], axis=1)
            ck = jnp.concatenate([pt[2] for pt in parts], axis=1)
            h2d = sum(pt[3] for pt in parts)
        return WindowChunk(ctx=np.asarray(ctx, np.float32),
                           rows=np.arange(n, dtype=np.int32),
                           tables={"p": p, "ck": ck}, users=users,
                           h2d_bytes=int(h2d))


class TableReplaySource(RequestSource):
    """Fixed replay over precomputed per-user tables.

    The scoring-input tables (contexts + compact execution rows) are
    computed ONCE - by a materialized ``CascadeServer`` or a prior
    ``save`` - and windows gather per-arrival slices.  With
    ``load(..., mmap=True)`` the tables stay on disk as memmapped
    ``.npy`` files and only the rows a window touches page in, so
    replaying a large fixed universe costs O(window), not O(U).

    Built ``from_server`` over the same arrivals, the streamed path is
    bit-identical to indexing the materialized universe: the chunk
    tables are row-gathers of the server's own tables and the contexts
    are the same array rows.

    ``device_tables`` uploads the full tables to the device ONCE and
    turns each window into a device-side row gather - no per-window
    (G, n, cap) host->device copy.  On the device each table is held
    user-major, (U, W) (``replay_rows``), so that a window's gather
    reads only its own rows.  Default: on for in-memory tables, off for
    memmapped ones (whose point is that untouched rows never leave the
    disk).
    """

    def __init__(self, ctx: np.ndarray, p_sorted: np.ndarray,
                 clicks_sorted: np.ndarray, chains, *, n_items: int,
                 expose: int, seed: int = 0,
                 device_tables: bool | None = None):
        if ctx.shape[0] != p_sorted.shape[1]:
            raise ValueError(
                f"ctx rows ({ctx.shape[0]}) must match table users "
                f"({p_sorted.shape[1]})")
        self.ctx = ctx
        self.p_sorted = p_sorted
        self.clicks_sorted = clicks_sorted
        self.chains = chains
        self.n_items = int(n_items)
        self.expose = int(expose)
        self.seed = int(seed)
        self.n_users = int(ctx.shape[0])
        if device_tables is None:
            device_tables = not isinstance(p_sorted, np.memmap)
        self.device_tables = bool(device_tables)
        self._dev = None  # one-time device upload (lazy)
        lay = build_compact_layout(chains, n_items=self.n_items,
                                   expose=self.expose)
        if lay is None or lay.cap != p_sorted.shape[2]:
            raise ValueError(
                f"tables (cap={p_sorted.shape[2]}) do not match the "
                f"chain set's compact layout at n_items={self.n_items}")

    @classmethod
    def from_server(cls, server, ctx: np.ndarray, *, seed: int = 0,
                    device_tables: bool | None = None
                    ) -> "TableReplaySource":
        """Replay source over a materialized CascadeServer's universe
        (``ctx`` row u = the reward context of table row u)."""
        if server.compact is None:
            raise ValueError("from_server needs a CompactPlan server "
                             "(the k3 cascade layout)")
        return cls(np.asarray(ctx, np.float32),
                   np.asarray(server.compact.p_sorted, np.int32),
                   np.asarray(server.compact.clicks_sorted, np.float32),
                   server.chains, n_items=server.clicks.shape[1],
                   expose=server.compact.expose, seed=seed,
                   device_tables=device_tables)

    def _n_items(self) -> int:
        return self.n_items

    @property
    def d_context(self) -> int:
        return int(self.ctx.shape[1])

    def window(self, t: int, n: int) -> WindowChunk:
        return self.window_for_users(self.arrivals(t, n))

    def window_for_users(self, users: np.ndarray) -> WindowChunk:
        """Chunk for ``users``; spans ``context_rows`` (the host context
        gather) and ``gather_dispatch`` (the id upload and the two
        ``replay_gather`` dispatches)."""
        users = np.asarray(users)
        n = len(users)
        obs = self._spans()
        with obs.span("context_rows", n=n):
            ctx = np.asarray(self.ctx[users], np.float32)
        if self.device_tables:
            h2d = 0
            if self._dev is None:  # one-time universe upload
                h2d = self._upload(obs)
            g, _, cap = self.p_sorted.shape
            with obs.span("gather_dispatch", n=n):
                u = jnp.asarray(users.astype(np.int32))
                tables = {"p": replay_gather(self._dev[0], u, g, cap),
                          "ck": replay_gather(self._dev[1], u, g, cap)}
            h2d += int(u.nbytes)
            return WindowChunk(
                ctx=ctx, rows=np.arange(n, dtype=np.int32), tables=tables,
                users=users, h2d_bytes=h2d)
        return WindowChunk(
            ctx=ctx,
            rows=np.arange(n, dtype=np.int32),
            tables={"p": np.ascontiguousarray(self.p_sorted[:, users]),
                    "ck": np.ascontiguousarray(
                        self.clicks_sorted[:, users])},
            users=users)

    def _upload(self, obs) -> int:
        """Put both tables on the device as user-major ``replay_rows``
        tables, one at a time: each (G, U, cap) original is freed once
        its re-layout has run.  Span ``table_upload``; returns the bytes
        sent."""
        g, _, cap = self.p_sorted.shape
        sent = 4 * (self.p_sorted.size + self.clicks_sorted.size)
        with obs.span("table_upload", bytes=sent):
            self._dev = tuple(
                replay_rows(jnp.asarray(np.asarray(host, dtype)),
                            replay_width(g, cap))
                for host, dtype in ((self.p_sorted, np.int32),
                                    (self.clicks_sorted, np.float32)))
        return sent

    # -- on-disk (memmap) form -------------------------------------------

    def save(self, path: str) -> None:
        """Write the tables as raw ``.npy`` (memmap-loadable) + meta."""
        os.makedirs(path, exist_ok=True)
        np.save(os.path.join(path, "ctx.npy"),
                np.asarray(self.ctx, np.float32))
        np.save(os.path.join(path, "p_sorted.npy"),
                np.asarray(self.p_sorted, np.int32))
        np.save(os.path.join(path, "clicks_sorted.npy"),
                np.asarray(self.clicks_sorted, np.float32))
        with open(os.path.join(path, "meta.json"), "w") as f:
            json.dump({"expose": self.expose, "n_items": self.n_items,
                       "n_users": self.n_users}, f)

    @classmethod
    def load(cls, path: str, chains, *, seed: int = 0,
             mmap: bool = True) -> "TableReplaySource":
        """Open a saved universe; ``mmap=True`` keeps tables on disk."""
        mode = "r" if mmap else None
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        return cls(np.load(os.path.join(path, "ctx.npy"), mmap_mode=mode),
                   np.load(os.path.join(path, "p_sorted.npy"),
                           mmap_mode=mode),
                   np.load(os.path.join(path, "clicks_sorted.npy"),
                           mmap_mode=mode),
                   chains, n_items=int(meta["n_items"]),
                   expose=int(meta["expose"]), seed=seed)
