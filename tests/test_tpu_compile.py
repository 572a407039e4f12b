"""The serving path's programs compile for a TPU v5e, at real widths.

Each test lowers and compiles one program of the main serving path for
a described v5e chip that is not attached: the Pallas truncation
kernel, the fused window pass (main + nearline dual) of the plain and
the geotenants spec at their real bucket sizes, the generated source's
stage scorers and its device table compactor at the real chunk shape,
and the replay source's table re-layout and row gather at the benchmark
cell's universe.  Widths are those of ``experiments.serve_config()``;
weights are untrained (a compile needs shapes, not values).  Nothing
runs: these tests catch what the chip's compiler refuses, not wrong
results.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

CHUNK = 512  # GeneratedSource's scoring chunk
BATCH = 4096  # requests per CascadeServer.serve call in chip_smoke
REPLAY_USERS = 131072  # users replayed by the benchmark's replay cell


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: a
    program compiled for it could not be read back here."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def widths():
    """serve_config()'s chain set, world and untrained models."""
    from repro.cascade.engine import CascadeModels
    from repro.core.action_chain import generate_action_chains
    from repro.core.reward_model import reward_model_init
    from repro.data.synthetic import StreamingWorld
    from repro.experiments import (cascade_configs, reward_config,
                                   scaled_stage_specs, serve_config)
    from repro.models.recsys import dien, din, dssm, ydnn

    cfg = serve_config()
    chains = generate_action_chains(scaled_stage_specs(cfg))
    world = StreamingWorld.build(cfg.world)
    key = jax.random.PRNGKey(0)
    mcfg = cascade_configs(cfg.world)
    models = CascadeModels(*(x for mod, c in zip((dssm, ydnn, din, dien),
                                                 mcfg)
                             for x in (mod.init(key, c), c)))
    rcfg = reward_config(chains, world.d_context)
    params = dict(reward_model_init(key, rcfg))
    params["label_norm"] = jnp.ones(chains.n_chains, jnp.float32)
    return cfg, chains, world, models, rcfg, params


def _shape(x, sharding):
    return jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype,
                                sharding=sharding)


def _compile(fn, *args):
    return fn.lower(*args).compile()


def test_truncation_kernel_compiles_for_v5e(one_chip, widths):
    from repro.cascade.engine import build_compact_layout
    from repro.kernels.cascade_truncate import compact_truncate_revenue

    cfg, chains, *_ = widths
    lay = build_compact_layout(chains, n_items=cfg.world.n_items,
                               expose=cfg.expose)
    g_n, cap = lay.p_sorted.shape[0], lay.cap
    u_n = int(cfg.world.n_users * cfg.split_fracs[3])  # eval users
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    compiled = compact_truncate_revenue.lower(
        sds((g_n, u_n, cap), jnp.int32), sds((g_n, u_n, cap), jnp.float32),
        sds((BATCH,), jnp.int32), sds((BATCH,), jnp.int32),
        sds((BATCH,), jnp.int32), expose=cfg.expose).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("spec,bucket", [("plain", 288),
                                         ("geotenants", 192)])
def test_fused_pass_compiles_for_v5e(one_chip, widths, spec, bucket):
    """serve_window's main and dual programs at the bucket the chip
    smoke's largest window pads to."""
    from repro.cascade.engine import build_compact_layout
    from repro.data.request_source import StreamUniverse
    from repro.serving.pipeline import ServingPipeline
    from repro.serving.spec import (ConstraintSpec, GlobalAxis, RegionAxis,
                                    TenantAxis)

    cfg, chains, world, _, rcfg, params = widths
    lay = build_compact_layout(chains, n_items=cfg.world.n_items,
                               expose=cfg.expose)
    universe = StreamUniverse(chains, lay, cfg.expose)
    budget = 0.6 * float(chains.costs.max()) * 96
    if spec == "plain":
        pipe = ServingPipeline(universe, params, rcfg, budget)
        window = (np.float32(budget), np.float32(1.0))
        k_of = ()
    else:
        pipe = ServingPipeline.from_spec(universe, params, rcfg,
                                         ConstraintSpec([
                                             TenantAxis((0.2, 0.8),
                                                        priced=True),
                                             RegionAxis(2),
                                             GlobalAxis(pricing="carbon")]))
        window = (np.ones(4, np.float32), np.ones(2, np.float32))
        k_of = (np.zeros(bucket, np.int32),)
    g_n, cap = lay.p_sorted.shape[0], lay.cap
    on = lambda x: _shape(x, one_chip)
    tables = {"p": np.zeros((g_n, bucket, cap), np.int32),
              "ck": np.zeros((g_n, bucket, cap), np.float32),
              "g_of": lay.group_of_chain, "n3_of": lay.n3_of_chain}
    valid = np.ones(bucket, np.float32)
    lam = np.asarray(pipe.lam)
    main = _compile(pipe._build_main_fn(bucket, True),
                    jax.tree_util.tree_map(on, params),
                    jax.tree_util.tree_map(on, tables),
                    on(np.zeros((bucket, world.d_context), np.float32)),
                    on(np.zeros(bucket, np.int32)), on(valid),
                    *map(on, k_of), on(lam), *map(on, window))
    assert main.memory_analysis() is not None
    rewards = np.zeros((bucket, chains.n_chains), np.float32)
    _compile(pipe._build_dual_fn(bucket, True), on(rewards), on(valid),
             *map(on, k_of), on(lam), *map(on, window))


def test_stage_scorers_compile_for_v5e(one_chip, widths):
    """GeneratedSource's four jitted stage scorers at the chunk shape."""
    from repro.cascade.engine import _user_batch
    from repro.data.request_source import GeneratedSource

    cfg, chains, world, models, *_ = widths
    src = GeneratedSource(world, models, chains, expose=cfg.expose,
                          chunk=CHUNK)
    src._build_score_fns()
    on = lambda x: _shape(x, one_chip)
    ub = {k: on(v) for k, v in _user_batch(
        world.user_slab(np.arange(CHUNK)), np.arange(CHUNK)).items()}
    fns = src._score_fns
    _compile(fns["DSSM"], ub["user_fields"])
    _compile(fns["YDNN"], ub["hist_ids"], ub["hist_mask"],
             ub["user_fields"])
    blk = on(np.zeros((CHUNK, src.item_block), np.int32))
    for name in ("DIN", "DIEN"):
        _compile(fns[name], ub, blk, blk)


def test_table_compactor_compiles_for_v5e(one_chip, widths):
    """The device twin of the compact-table builder at the chunk shape."""
    from repro.data.request_source import GeneratedSource

    cfg, chains, world, models, *_ = widths
    src = GeneratedSource(world, models, chains, expose=cfg.expose,
                          chunk=CHUNK)
    src._build_table_fn()
    slab = jax.ShapeDtypeStruct((CHUNK, cfg.world.n_items), jnp.float32,
                                sharding=one_chip)
    scores = {k: slab for k in ("DSSM", "YDNN", "DIN", "DIEN")}
    _compile(src._table_fn, scores, slab)


def _hlo_buffers(text, op):
    """Element counts of the buffers that ``op`` instructions output in
    compiled HLO text."""
    pat = re.compile(r"= \w+\[([\d,]*)\]\S* " + op + r"\(")
    return [int(np.prod([int(d) for d in m.group(1).split(",") if d]))
            for m in pat.finditer(text)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
@pytest.mark.parametrize("g,cap", [(16, 200), (16, 150)])
def test_replay_gather_copies_no_table_for_v5e(one_chip, g, cap, dtype):
    """The replay source's device tables at the cell's universe: the
    one-time re-layout to (U, W) and the window's row gather.  The chip
    keeps a (U, W) table row-major once W is whole 128-lane tiles, so
    the gather copies nothing of the table's size and needs less
    scratch than the window it returns; at G·cap = 2400 the pad to
    2432 is what keeps it so."""
    from repro.data.request_source import (replay_gather, replay_rows,
                                           replay_width)

    width = replay_width(g, cap)
    table_elems = g * REPLAY_USERS * cap
    sds = lambda s, d=dtype: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    rows = replay_rows.lower(sds((g, REPLAY_USERS, cap)),
                             width=width).compile()
    if g * cap == width:  # lane-dense already: the re-layout is one copy
        assert rows.memory_analysis().temp_size_in_bytes == 0
    gather = replay_gather.lower(sds((REPLAY_USERS, width)),
                                 sds((BATCH,), jnp.int32), g=g,
                                 cap=cap).compile()
    text = gather.as_text()
    assert REPLAY_USERS * width in _hlo_buffers(text, "parameter")
    moved = _hlo_buffers(text, "copy") + _hlo_buffers(text, "transpose")
    assert moved and max(moved) < table_elems
    window_bytes = g * BATCH * cap * np.dtype(dtype).itemsize
    assert gather.memory_analysis().temp_size_in_bytes < window_bytes
