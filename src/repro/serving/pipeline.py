"""ServingPipeline: the fused score->decide->guard->execute window pass.

The legacy loop (``GreenFlowAllocator.allocate_window`` +
``CascadeServer.serve``) crosses the host/device boundary four times per
window and runs the downgrade guard as a multi-pass NumPy loop.  Here
the whole window is ONE jitted pass:

  1. reward scoring   - ``reward_matrix_grouped`` (model-prefix dedup:
     the recursive state depends on model choices only, so the paper
     layout runs ~2 trunk evaluations per stage instead of J);
  2. Eq. 10 decisions - ``allocate`` with the window's entry price(s);
  3. downgrade guard  - ``serving.guard.downgrade_guard`` (vectorized
     cumsum tail-reserve, mask-aware, per-constraint budgets);
  4. cascade execute  - CompactPlan threshold arithmetic (gathers over
     cap-wide rows instead of the item axis) with the lax.scan
     ``_revenue_requests`` kernel as the generic-layout fallback;
  5. nearline update  - ``dual_descent`` (Algorithm 1) on the window's
     rewards publishes the next window's price(s).

Steps 1-4 are the ONLINE response path: one jitted dispatch whose
latency is what a request sees.  Step 5 is NEARLINE exactly as in the
paper (the price "reacts within one window", it never blocks a
response): it is dispatched as a second device computation that reuses
the window's reward matrix on-device, and the next window's decisions
simply depend on its output - the host never blocks on it.  Keeping the
two graphs separate also sidesteps an XLA:CPU scheduling cliff where
fusing the 200-step dual scan into the serving graph doubles its wall
time.

WHAT is budgeted is declared by a ``serving.spec.ConstraintSpec`` -
the pipeline's front door is ``ServingPipeline.from_spec``:

  * [GlobalAxis]                 - one budget, one dual price (the
    paper's system; the K=1 case of the core, bit-identical);
  * [TenantAxis(shared)]         - T equal-size tenant blocks per
    window, ONE dual price, the guard enforcing each tenant's own
    budget (k_of path);
  * [TenantAxis(priced)]         - a (T,) PRICE VECTOR inside the same
    fused pass: each tenant's price descends on its own
    consumption-vs-budget subgradient;
  * [RegionAxis]                 - the geo router: each request chooses
    (chain, serving region) by the same priced argmax over J*R options
    with region-dependent effective costs c_{j,r}(t) = flops_j *
    scale_r(t) (carbon: scale_r = kappa * CI_r(t)), (R,) per-region
    budgets/prices, the guard downgrading within a request's region;
  * [TenantAxis + RegionAxis]    - the COMBINED system: per-tenant
    gram budgets and per-region gram budgets priced together, a
    (T + R,) price vector (priced tenants) where a tenant-t request
    pays (lam_tenant[t] + lam_region[r]) * c_{j,r} for option (j, r),
    and the guard chains a tenant walk with a per-region walk.

The legacy keyword constructor (``tenant_budgets``/``tenant_mode``/
``n_regions``) survives as a thin shim that builds the equivalent spec
(``serving.spec.spec_from_legacy``) - bit-identical to the historical
flag paths.

Region ties: the proportional cost structure (c_{j,r} = s_r * flops_j)
makes every request indifferent between regions at once at the dual
equilibrium, so a pure argmax bang-bangs whole windows.
``RegionAxis(split="flow")`` (the default for new specs) resolves the
degenerate window exactly: tied requests are divided deterministically
in arrival order, each tied region receiving a share of the window's
FLOPs mass proportional to its remaining budget capacity - the
flow-splitting primal rounding of the fractional LP optimum.
``split="argmax"`` keeps the historical knife-edge behavior (and the
bit-exact reduction to a pinned pipeline when regions are identical).

Request-axis sharding: pass a 1-D mesh (``launch.mesh.make_request_mesh``)
and the pass runs under ``shard_map`` over axis "req" - per-request work
stays local while the guard stitches per-constraint prefix spends with
all_gather/psum and the dual update psums per-constraint consumption.
Tenant blocks compose with sharding (blocks may span shard boundaries;
the per-k prefix stitching keeps the walk exact), and so does the flow
split (the arrival-order FLOPs prefix is stitched the same way).

Uneven windows: arrivals are padded up to a small set of bucket sizes
(multiples of ``pad_quantum``) with a validity mask, so a 3x traffic
spike reuses a handful of compiled shapes instead of recompiling.

CI-forecast warm-start: ``serve_window(dual_budget=..,
dual_cost_scale=..)`` runs the nearline update against the NEXT
window's (known or forecast) budget and cost scale while the online
pass uses the current ones - the published price then lands where the
next window needs it instead of lagging a CI swing by one window
(``run_stream(forecast=True)`` threads this automatically).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.cascade.engine import CascadeServer, _revenue_compact, \
    _revenue_requests
from repro.core.budget import WindowStats
from repro.core.primal_dual import DualDescentConfig, allocate, dual_descent
from repro.core.reward_model import (RewardModelConfig, chain_prefix_plan,
                                     denormalize_rewards,
                                     reward_matrix_grouped)
from repro.distributed.sharding import REQUEST_AXIS as AXIS
from repro.distributed.sharding import ordered_psum
from repro.serving.guard import (_exclusive_shard_offset, downgrade_guard,
                                 downgrade_guard_chain)
from repro.serving.spec import ConstraintSpec, spec_from_legacy


def _local_np(arr) -> np.ndarray:
    """Device array -> THIS process's rows, as numpy.

    Single-process (fully addressable) arrays convert wholesale.  A
    request-sharded global array of a multi-process mesh yields the
    concatenation of its ADDRESSABLE shards in request order: each host
    reads exactly the window rows it serves, and the read never moves
    data across hosts.
    """
    if getattr(arr, "is_fully_addressable", True):
        return np.asarray(arr)
    shards = sorted(arr.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    return np.concatenate([np.asarray(s.data) for s in shards])


def window_layout(n: int, b: int, t_n: int | None = None):
    """The canonical padded layout of an n-request window in a b slot
    bucket: ``(perm, valid, k_of)``.

    ``perm[pos]`` is the ORIGINAL request index at padded position
    ``pos`` (0 on padding slots), ``valid`` masks real requests, and
    ``k_of`` maps positions to tenants (``None`` without tenants).
    Plain windows pad at the end; tenant windows carry ``t_n`` equal
    blocks of ``b // t_n`` slots each, padded at the end of EACH block
    so per-tenant guard walks stay aligned with their budgets.

    Extracted to module level because the multi-host window protocol
    depends on every host deriving the SAME layout from ``(n, b)``
    alone: each host materializes only its own contiguous slice of
    these positions and the stitched collectives see one consistent
    global window.
    """
    if t_n is None:
        valid = np.zeros(b, np.float32)
        valid[:n] = 1.0
        perm = np.concatenate(
            [np.arange(n, dtype=np.intp), np.zeros(b - n, np.intp)])
        return perm, valid, None
    if n % t_n:
        raise ValueError(f"window size {n} not divisible by "
                         f"{t_n} tenants")
    if b % t_n:
        raise ValueError(f"bucket {b} not divisible by {t_n} tenants")
    n_t, bt = n // t_n, b // t_n
    valid = np.zeros((t_n, bt), np.float32)
    valid[:, :n_t] = 1.0
    perm = np.zeros((t_n, bt), np.intp)
    perm[:, :n_t] = (np.arange(t_n)[:, None] * n_t
                     + np.arange(n_t)[None, :])
    k_of = np.repeat(np.arange(t_n, dtype=np.int32), bt)
    return perm.reshape(b), valid.reshape(b), k_of


@dataclass
class WindowResult:
    """One served window; arrays stay on device until read.

    ``budget``/``spend`` are in the window's ACTIVE cost units - FLOPs by
    default, gCO2e when a carbon ``cost_scale`` was applied (see
    ``serve_window``); ``flops`` is always the realized FLOPs, so carbon
    ledgers and PFEC reports meter the same quantity either way.
    ``lam_before``/``lam_after`` are scalars in the single-price modes
    and (K,) vectors otherwise (``spec.k_names`` order: priced tenant
    entries first, region entries after).  In the combined
    tenant x region mode ``tr_spend`` carries the full (T, R)
    per-(tenant, region) spend whose marginals are ``tenant_spend`` and
    ``region_spend``.
    """

    n_valid: int
    budget: float
    lam_before: jnp.ndarray
    lam_after: jnp.ndarray
    decisions: jnp.ndarray  # (B,) padded CHAIN index
    revenue: jnp.ndarray  # (B,) padded (0 on padding)
    spend: jnp.ndarray
    downgraded: jnp.ndarray
    valid: np.ndarray = None  # (B,) 1.0 on real requests
    tenant_spend: jnp.ndarray | None = None  # (T,) per-tenant spend
    flops: jnp.ndarray | None = None  # realized FLOPs (unit-independent)
    cost_scale: float = 1.0  # active-units per FLOP (1.0 = FLOPs mode)
    regions: jnp.ndarray | None = None  # (B,) serving region (geo mode)
    region_spend: jnp.ndarray | None = None  # (R,) per-region spend
    k_budget: np.ndarray | None = None  # per-constraint budgets
    tr_spend: jnp.ndarray | None = None  # (T, R) per-(tenant, region)
    compiles: int = 0  # jit cache misses this window (0 = warm bucket)
    bucket: tuple | None = None  # the (b, padded, chunked) shape key
    h2d_bytes: int = 0  # host->device bytes dispatched for this window
    prep_ms: float = 0.0  # host chunk production (set by run_stream)
    stall_ms: float = 0.0  # host wait for a prefetched chunk (run_stream)

    @property
    def decisions_np(self) -> np.ndarray:
        return _local_np(self.decisions)[self.valid > 0]

    @property
    def revenue_np(self) -> np.ndarray:
        return _local_np(self.revenue)[self.valid > 0]

    @property
    def regions_np(self) -> np.ndarray | None:
        if self.regions is None:
            return None
        return _local_np(self.regions)[self.valid > 0]

    def stats(self) -> WindowStats:
        return WindowStats(
            n_requests=self.n_valid, spend=float(np.sum(np.asarray(
                self.spend))), budget=self.budget,
            lam=float(np.max(np.asarray(self.lam_after))),
            downgraded=int(self.downgraded))


class ServingPipeline:
    """Fused per-window serving pass over a CascadeServer's universe.

    The front door is ``ServingPipeline.from_spec(server, params, cfg,
    spec)`` with a declarative ``serving.spec.ConstraintSpec``; the
    keyword constructor below is the LEGACY shim - every historical
    flag combination builds its equivalent spec via
    ``spec_from_legacy`` and is bit-identical to the pre-spec paths.

    Parameters
    ----------
    server: executes chains for the serving users (its CompactPlan - or
        scan-kernel fallback - becomes the fused execute step).
    reward_params / reward_cfg: the trained reward model (must carry
        ``label_norm`` if trained on ratio labels).
    budget_per_window: B_t for the guard and the dual update (the
        TOTAL budget; per-tenant/per-region caps refine it below).
    mesh: optional 1-D request mesh -> shard_map over axis "req"
        (composes with every pricing mode).
    tenant_budgets / tenant_mode / n_regions: legacy flags, see
        ``spec_from_legacy`` for the mapping.
    donate_dual: thread the nearline lambda update through
        ``jax.jit(..., donate_argnums=...)`` so the steady-state price
        chain updates its device buffer IN PLACE (allocation-free);
        records stay readable via a bitwise device copy, so results
        are bit-identical either way.
    spec: a ConstraintSpec - overrides the legacy flags entirely.
    """

    def __init__(self, server: CascadeServer, reward_params: dict,
                 reward_cfg: RewardModelConfig, budget_per_window: float,
                 *, dual_cfg: DualDescentConfig | None = None,
                 guard: bool = True, mesh=None, pad_quantum: int = 32,
                 bucketing: str = "linear",
                 tenant_budgets=None, tenant_mode: str = "shared",
                 n_regions: int | None = None,
                 lam_init: float = 0.0, ledger=None,
                 donate_dual: bool = True,
                 spec: ConstraintSpec | None = None, obs=None,
                 multihost: bool | None = None):
        if spec is None:
            spec = spec_from_legacy(
                float(budget_per_window), tenant_budgets=tenant_budgets,
                tenant_mode=tenant_mode, n_regions=n_regions)
        cs = spec.compile()
        self.spec = spec
        self._cs = cs
        self.server = server
        self.ledger = ledger  # optional CarbonLedger (lazy metering hook)
        from repro.obs import get_obs
        self.obs = get_obs(obs)  # host spans only; never touches numerics
        self.chains = server.chains
        self.reward_params = reward_params
        self.reward_cfg = reward_cfg
        self.budget = cs.total_budget
        self.dual_cfg = dual_cfg or DualDescentConfig()
        self.guard = guard
        self.mesh = mesh
        # multi-process request mesh (repro.distributed.multihost): the
        # window pass runs over GLOBAL arrays assembled from each host's
        # slice; auto-detected from jax.distributed state, overridable
        # for tests
        self.multihost = (bool(multihost) if multihost is not None
                          else mesh is not None
                          and jax.process_count() > 1)
        if self.multihost and mesh is None:
            raise ValueError("multihost serving needs a request mesh")
        self._params_mh = None  # replicated global params (built lazily)
        self._layout_mh = None  # replicated global g_of/n3_of tables
        self._mh_lam = False  # lam chain converted to a global array?
        # legacy-compatible views of the compiled spec
        self.tenant_mode = "priced" if cs.tenant_priced else "shared"
        self.tenant_budgets = (
            None if cs.tenants is None
            else np.asarray(cs.tenants.budgets, np.float32))
        self.n_regions = cs.r_n
        self.region_split = cs.split
        from repro.launch.mesh import mesh_num_shards
        self._n_shards = mesh_num_shards(mesh)
        q = math.lcm(int(pad_quantum), self._n_shards)
        if self.tenant_budgets is not None:
            q = math.lcm(q, len(self.tenant_budgets))
        self.pad_quantum = q
        if bucketing not in ("linear", "pow2"):
            raise ValueError(f"bucketing must be 'linear' or 'pow2', "
                             f"got {bucketing!r}")
        self.bucketing = bucketing

        chains = self.chains
        self._prefix_plan = chain_prefix_plan(chains.chain_idx[:, :, 0])
        self._sh = jnp.asarray(chains.scale_multihot)
        self._costs = jnp.asarray(chains.costs, jnp.float32)
        self._cheap = int(chains.cheapest())
        # a streaming universe (``data.request_source.StreamUniverse``)
        # carries the compact LAYOUT only - every serve_window call must
        # bring its own chunk tables
        self._stream_only = bool(getattr(server, "stream_only", False))
        self._cap = None
        if server.compact is not None:
            c = server.compact
            self._tables = {
                "p": jnp.asarray(c.p_sorted),
                "ck": jnp.asarray(c.clicks_sorted),
                "g_of": jnp.asarray(c.group_of_chain),
                "n3_of": jnp.asarray(c.n3_of_chain),
            }
            self._expose = c.expose
            self._cap = int(c.cap)
        else:  # generic layout: the lax.scan kernel path
            self._tables = {
                "orders": server._orders, "ranks": server._ranks,
                "clicks": server._clicks,
                "slots": jnp.asarray(server._slots),
                "keeps": jnp.asarray(server._keeps),
            }
            self._expose = server.expose
        # K price components in spec.k_names order (priced tenants
        # first, regions after); scalar for the single-price modes
        if cs.n_prices:
            self.lam = jnp.full(cs.n_prices, lam_init, jnp.float32)
        else:
            self.lam = jnp.float32(lam_init)
        if mesh is not None and not self.multihost:
            # the dual fn publishes a mesh-replicated price: start the
            # chain there, so window 1 reuses window 0's compiled pass
            self.lam = jax.device_put(
                self.lam, jax.sharding.NamedSharding(mesh, P()))
        # with donation the chain buffer ``self.lam`` is consumed by the
        # next window's dual dispatch; ``_lam_rec`` is its always-
        # readable twin (a bitwise device copy) that WindowResult
        # records point at
        self.donate_dual = bool(donate_dual)
        self._lam_rec = jnp.copy(self.lam) if donate_dual else self.lam
        self._h2d_window = 0
        self.stats: list[WindowResult] = []
        self._fns: dict = {}
        self._built: list = []  # every jitted fn ever built (compile count)

    @classmethod
    def from_spec(cls, server: CascadeServer, reward_params: dict,
                  reward_cfg: RewardModelConfig, spec: ConstraintSpec,
                  *, dual_cfg: DualDescentConfig | None = None,
                  guard: bool = True, mesh=None, pad_quantum: int = 32,
                  bucketing: str = "linear", lam_init: float = 0.0,
                  ledger=None,
                  donate_dual: bool = True, obs=None,
                  multihost: bool | None = None) -> "ServingPipeline":
        """Build the pipeline from a declarative ConstraintSpec (the
        compiled total budget seeds ``budget_per_window``)."""
        return cls(server, reward_params, reward_cfg,
                   spec.compile().total_budget, dual_cfg=dual_cfg,
                   guard=guard, mesh=mesh, pad_quantum=pad_quantum,
                   bucketing=bucketing, lam_init=lam_init, ledger=ledger,
                   donate_dual=donate_dual, spec=spec, obs=obs,
                   multihost=multihost)

    # -- fused pass -----------------------------------------------------------

    def _execute(self, tables, dec, rows, valid):
        if "p" in tables:
            rev = _revenue_compact(
                tables["p"], tables["ck"], tables["g_of"][dec], rows,
                tables["n3_of"][dec], expose=self._expose)
        else:
            rev = _revenue_requests(
                tables["orders"], tables["ranks"], tables["clicks"],
                tables["slots"][dec], tables["keeps"][dec], rows,
                n_stages=self.chains.n_stages)
        return rev * valid

    def _flow_split(self, flops_mass, share, axis):
        """Deterministic proportional rounding of a degenerate window:
        walk the (masked) FLOPs mass in arrival order and hand region r
        the ``share[r]`` fraction of it (a Bresenham-style interval
        assignment on the cumulative mass - exact up to one request per
        region, shard-stitched like every guard prefix)."""
        edges = jnp.cumsum(share)  # (R,) interval right edges in (0, 1]
        prefix = jnp.cumsum(flops_mass)
        local_total = prefix[-1] if flops_mass.shape[0] \
            else jnp.float32(0.0)
        if axis is not None:
            total = ordered_psum(local_total, axis)
            prefix = prefix + _exclusive_shard_offset(local_total, axis)
        else:
            total = local_total
        pos = (prefix - 0.5 * flops_mass) / jnp.maximum(total, 1e-30)
        return jnp.sum((pos[:, None] > edges[None, :-1])
                       .astype(jnp.int32), axis=1)

    def _build_main_fn(self, b: int, padded: bool):
        """Online response path: score -> decide -> guard -> execute.

        ``budget`` and ``scale`` ride through as TRACED values, so
        per-window budgets (traffic reshaping) and per-window cost
        scales (carbon pricing: costs become c_j(t) = flops_j * kappa *
        CI(t); geo pricing: an (R,) scale vector, one per region's
        CI_r(t)) reuse the compiled pass instead of recompiling.
        ``scale`` = 1.0 multiplies bit-exactly, keeping the FLOPs path
        unchanged.  Every variant compiles as ``jit_fused_pass``, the
        program name the device trace shows.
        """
        axis = AXIS if self.mesh is not None else None
        costs, cheap = self._costs, self._cheap
        j_n = int(costs.shape[0])
        cs = self._cs
        tb = self.tenant_budgets
        r_n = self.n_regions
        mode = cs.mode
        # chunk tables ride REQUEST-SHARDED through a multi-process mesh
        # (each host uploads only its own rows; ``rows`` then index the
        # shard-local slice) - the single-process path keeps replicated
        # tables + the padded-perm gather.  Both gather identical
        # values, so results stay bitwise equal across the two layouts.
        tspec = ({"p": P(None, AXIS, None), "ck": P(None, AXIS, None),
                  "g_of": P(), "n3_of": P()}
                 if self.multihost else P())

        if mode == "geotenants":
            t_n = len(tb)
            priced = cs.tenant_priced
            flow = cs.split == "flow"
            tie_tol = cs.tie_tol

            def fused_pass(params, tables, ctx, rows, valid, k_of, lam,
                           budgets, scales):
                rewards = denormalize_rewards(
                    params, reward_matrix_grouped(
                        params, self.reward_cfg, ctx, self._sh,
                        self._prefix_plan))
                # option axis m = r*J + j: region-major tiling
                opt_costs = (scales[:, None] * costs[None, :]).reshape(-1)
                if priced:
                    lam_t, lam_r = lam[:t_n], lam[t_n:]
                    lam_ti = lam_t[k_of]  # (b,)
                else:  # shared tenants: region prices only, tenant
                    lam_r = lam  # budgets enforced by the guard walk
                    lam_ti = jnp.zeros(rewards.shape[0], jnp.float32)
                # per-flop priced cost of serving request i in region r
                q_ir = (lam_ti[:, None] + lam_r[None, :]) \
                    * scales[None, :]  # (b, R)
                r_max = jnp.max(jnp.abs(rewards))
                if axis is not None:  # shard-invariant scale
                    r_max = jax.lax.pmax(r_max, axis)
                # gf: allow[GF003] tie-break scale only: eps_green
                # orders regions at lam=0 and never enters the dual
                # update, so reassociation cannot drift the price
                eps_green = 1e-6 * r_max / (jnp.mean(opt_costs) + 1e-30)
                u_ir = q_ir + eps_green * scales[None, :]  # green floor
                r0 = jnp.argmin(u_ir, axis=1)  # (b,)
                # the per-flop price factors out of the chain argmax, so
                # chains compete at the chosen region's price (Eq. 10)
                p_i = jnp.take_along_axis(q_ir, r0[:, None],
                                          axis=1)[:, 0]
                dec = jnp.argmax(rewards - p_i[:, None] * costs[None, :],
                                 axis=1).astype(jnp.int32)
                f = jnp.take(costs, dec) * valid
                if flow:
                    u_min = jnp.take_along_axis(u_ir, r0[:, None],
                                                axis=1)[:, 0]
                    tied_ir = u_ir <= u_min[:, None] * (1.0 + tie_tol)
                    is_tied = jnp.sum(tied_ir.astype(jnp.int32),
                                      axis=1) > 1
                    # region capacity left after the untied requests
                    oh_r0 = (r0[:, None] == jnp.arange(r_n)[None, :]
                             ).astype(jnp.float32)
                    fixed = jnp.sum(
                        f[:, None] * oh_r0
                        * (1.0 - is_tied.astype(jnp.float32))[:, None],
                        axis=0)
                    # flow shares only cover regions inside some tied
                    # request's tie band (tie sets are per-tenant, so
                    # with R > 2 a far-overpriced region must not soak
                    # up tied mass just because capacity remains there)
                    any_tied = jnp.any(tied_ir & is_tied[:, None],
                                       axis=0).astype(jnp.float32)
                    if axis is not None:
                        fixed = ordered_psum(fixed, axis)
                        any_tied = jax.lax.pmax(any_tied, axis)
                    cap = jnp.maximum(
                        budgets[t_n:] / jnp.maximum(scales, 1e-30)
                        - fixed, 0.0) * any_tied
                    total_cap = jnp.sum(cap)
                    share = cap / (total_cap + 1e-30)
                    r_flow = self._flow_split(
                        f * is_tied.astype(jnp.float32), share, axis)
                    # a request never leaves its OWN tie band (the
                    # union share may point outside it when R > 2),
                    # and exhausted capacity (share all-zero) falls
                    # back to the priced argmin instead of dumping
                    # the window into the last region
                    ok = jnp.take_along_axis(tied_ir, r_flow[:, None],
                                             axis=1)[:, 0]
                    region = jnp.where(is_tied & ok & (total_cap > 0),
                                       r_flow, r0)
                else:
                    region = r0
                dec_m = (region * j_n + dec).astype(jnp.int32)
                mask = valid if padded else None
                if self.guard:
                    # tenant walk downgrades to the globally cheapest
                    # priced option (greenest region's cheap chain),
                    # then the region walk re-caps within each region -
                    # later walks only lower earlier spends
                    cheap_m = jnp.argmin(opt_costs).astype(jnp.int32)
                    cheap_k = jnp.arange(r_n) * j_n + cheap
                    dec_m, dg, _ = downgrade_guard_chain(
                        dec_m, opt_costs,
                        [(budgets[:t_n], cheap_m, k_of),
                         (budgets[t_n:], cheap_k, lambda d: d // j_n)],
                        mask, axis_name=axis)
                else:
                    dg = jnp.int32(0)
                dec = dec_m % j_n
                region = dec_m // j_n
                # per-(tenant, region) spends of the FINAL decisions
                cd = jnp.take(opt_costs, dec_m) * valid
                oh_t = (k_of[:, None] == jnp.arange(t_n)[None, :]
                        ).astype(jnp.float32)
                oh_r = (region[:, None] == jnp.arange(r_n)[None, :]
                        ).astype(jnp.float32)
                # full f32 matmul: at default precision a TPU rounds the
                # gram costs to bf16 (~3 digits) and the reported spend
                # would no longer be the one the guard enforced
                tr_spend = jnp.matmul(
                    (oh_t * cd[:, None]).T, oh_r,
                    precision=jax.lax.Precision.HIGHEST)  # (T, R)
                if axis is not None:
                    tr_spend = ordered_psum(tr_spend, axis)
                spend = jnp.sum(tr_spend)
                flops = jnp.sum(jnp.take(costs, dec) * valid)
                if axis is not None:
                    flops = ordered_psum(flops, axis)
                rev = self._execute(tables, dec, rows, valid)
                return (rewards, dec, rev, spend, flops, dg,
                        jnp.sum(tr_spend, axis=1), region,
                        jnp.sum(tr_spend, axis=0), tr_spend)

            if self.mesh is not None:
                fused_pass = jax.shard_map(
                    fused_pass, mesh=self.mesh, check_vma=False,
                    in_specs=(P(), tspec, P(AXIS), P(AXIS), P(AXIS),
                              P(AXIS), P(), P(), P()),
                    out_specs=(P(AXIS), P(AXIS), P(AXIS), P(), P(), P(),
                               P(), P(AXIS), P(), P()))
            return jax.jit(fused_pass)

        if r_n is not None:
            flow = cs.split == "flow"
            tie_tol = cs.tie_tol

            def fused_pass(params, tables, ctx, rows, valid, lam, budgets,
                           scales):
                rewards = denormalize_rewards(
                    params, reward_matrix_grouped(
                        params, self.reward_cfg, ctx, self._sh,
                        self._prefix_plan))
                # option axis m = r*J + j: region-major tiling
                opt_costs = (scales[:, None] * costs[None, :]).reshape(-1)
                r_max = jnp.max(jnp.abs(rewards))
                if axis is not None:  # shard-invariant scale
                    r_max = jax.lax.pmax(r_max, axis)
                # gf: allow[GF003] tie-break scale only: eps_green
                # orders regions at lam=0 and never enters the dual
                # update, so reassociation cannot drift the price
                eps_green = 1e-6 * r_max / (jnp.mean(opt_costs) + 1e-30)
                if flow:
                    # per-flop priced cost per region; the eps_green
                    # floor routes slack (lam = 0) windows green
                    u = (lam + eps_green) * scales  # (R,)
                    r0 = jnp.argmin(u)
                    price_best = (lam[r0] * scales[r0]) * costs  # (J,)
                    dec = jnp.argmax(rewards - price_best[None, :],
                                     axis=1).astype(jnp.int32)
                    f = jnp.take(costs, dec) * valid
                    tied = u <= jnp.min(u) * (1.0 + tie_tol)
                    cap = jnp.where(
                        tied, budgets / jnp.maximum(scales, 1e-30), 0.0)
                    total_cap = jnp.sum(cap)
                    share = cap / (total_cap + 1e-30)
                    region = self._flow_split(f, share, axis)
                    # zero remaining capacity (share all-zero): fall
                    # back to the priced argmin instead of dumping the
                    # window into the last region
                    region = jnp.where(total_cap > 0, region, r0)
                    dec_m = (region * j_n + dec).astype(jnp.int32)
                else:
                    # The joint argmax over (chain, region) factors: the
                    # reward is region-free, so each (request, chain)
                    # first picks its cheapest-PRICED region, then
                    # chains compete by the usual Eq. 10 argmax
                    # (first-index ties, exactly the scalar semantics).
                    # The region argmin runs at lam + eps_green - an
                    # infinitesimal price floor, ~1e-6 of the natural
                    # reward-per-cost scale - so a slack window
                    # (lam = 0, every price 0) still routes to the
                    # GREENER region instead of tie-breaking
                    # arbitrarily, while any meaningful price dwarfs
                    # it.  Equal regions keep equal floors, so ties
                    # still resolve to region 0 and the pinned-pipeline
                    # reduction stays bit-exact.
                    price_r = lam[:, None] * (scales[:, None]
                                              * costs[None, :])  # (R, J)
                    price_irj = jnp.broadcast_to(
                        price_r[None], (rewards.shape[0], r_n, j_n))
                    tie = price_irj + eps_green * (
                        scales[:, None] * costs[None, :])[None]
                    r_star = jnp.argmin(tie, axis=1)  # (I, J)
                    price_best = jnp.take_along_axis(
                        price_irj, r_star[:, None, :], axis=1)[:, 0, :]
                    dec = jnp.argmax(rewards - price_best,
                                     axis=1).astype(jnp.int32)
                    dec_m = (jnp.take_along_axis(
                        r_star, dec[:, None], axis=1)[:, 0] * j_n + dec)
                mask = valid if padded else None
                if not self.guard:
                    dg = jnp.int32(0)
                    region_spend = None
                    spend = jnp.sum(jnp.take(opt_costs, dec_m) * valid)
                    if axis is not None:
                        spend = ordered_psum(spend, axis)
                else:
                    cheap_k = jnp.arange(r_n) * j_n + cheap
                    dec_m, dg, region_spend = downgrade_guard(
                        dec_m, opt_costs, budgets, cheap_k, mask,
                        k_of=dec_m // j_n, axis_name=axis)
                    spend = jnp.sum(region_spend)
                dec = dec_m % j_n
                regions = dec_m // j_n
                flops = jnp.sum(jnp.take(costs, dec) * valid)
                if axis is not None:
                    flops = ordered_psum(flops, axis)
                rev = self._execute(tables, dec, rows, valid)
                return (rewards, dec, rev, spend, flops, dg, None,
                        regions, region_spend)

            if self.mesh is not None:
                fused_pass = jax.shard_map(
                    fused_pass, mesh=self.mesh, check_vma=False,
                    in_specs=(P(), tspec, P(AXIS), P(AXIS), P(AXIS),
                              P(), P(), P()),
                    out_specs=(P(AXIS), P(AXIS), P(AXIS), P(), P(), P(),
                               P(), P(AXIS), P()))
            return jax.jit(fused_pass)

        if tb is not None:
            t_n = len(tb)
            priced = cs.tenant_priced

            def fused_pass(params, tables, ctx, rows, valid, k_of, lam,
                           budgets, scale):
                rewards = denormalize_rewards(
                    params, reward_matrix_grouped(
                        params, self.reward_cfg, ctx, self._sh,
                        self._prefix_plan))
                costs_eff = costs * scale  # active units (FLOPs or gCO2e)
                if priced:
                    member = self._cs.tenant_member(k_of)
                    dec = allocate(rewards, costs_eff[:, None], lam,
                                   member)
                else:
                    dec = allocate(rewards, costs_eff, lam)
                mask = valid if padded else None
                tenant_spend = None
                if not self.guard:
                    dg = jnp.int32(0)
                    spend = jnp.sum(jnp.take(costs_eff, dec) * valid)
                    if axis is not None:
                        spend = ordered_psum(spend, axis)
                else:
                    dec, dg, tenant_spend = downgrade_guard(
                        dec, costs_eff, budgets, cheap, mask, k_of=k_of,
                        axis_name=axis)
                    spend = jnp.sum(tenant_spend)
                flops = jnp.sum(jnp.take(costs, dec) * valid)
                if axis is not None:
                    flops = ordered_psum(flops, axis)
                rev = self._execute(tables, dec, rows, valid)
                return (rewards, dec, rev, spend, flops, dg, tenant_spend,
                        None, None)

            if self.mesh is not None:
                fused_pass = jax.shard_map(
                    fused_pass, mesh=self.mesh, check_vma=False,
                    in_specs=(P(), tspec, P(AXIS), P(AXIS), P(AXIS),
                              P(AXIS), P(), P(), P()),
                    out_specs=(P(AXIS), P(AXIS), P(AXIS), P(), P(), P(),
                               P(), P(), P()))
            return jax.jit(fused_pass)

        def fused_pass(params, tables, ctx, rows, valid, lam, budget,
                       scale):
            rewards = denormalize_rewards(params, reward_matrix_grouped(
                params, self.reward_cfg, ctx, self._sh, self._prefix_plan))
            costs_eff = costs * scale  # active units (FLOPs or gCO2e)
            dec = allocate(rewards, costs_eff, lam)
            mask = valid if padded else None
            if not self.guard:
                dg = jnp.int32(0)
                spend = jnp.sum(jnp.take(costs_eff, dec) * valid)
                if axis is not None:
                    spend = ordered_psum(spend, axis)
            else:
                dec, dg, spend = downgrade_guard(
                    dec, costs_eff, budget, cheap, mask, axis_name=axis)
            flops = jnp.sum(jnp.take(costs, dec) * valid)
            if axis is not None:
                flops = ordered_psum(flops, axis)
            rev = self._execute(tables, dec, rows, valid)
            return rewards, dec, rev, spend, flops, dg, None, None, None

        if self.mesh is not None:
            fused_pass = jax.shard_map(
                fused_pass, mesh=self.mesh, check_vma=False,
                in_specs=(P(), tspec, P(AXIS), P(AXIS), P(AXIS), P(), P(),
                          P()),
                out_specs=(P(AXIS), P(AXIS), P(AXIS), P(), P(), P(), P(),
                           P(), P()))
        return jax.jit(fused_pass)

    def _build_dual_fn(self, b: int, padded: bool):
        """Nearline price update: Algorithm 1 on the window's rewards,
        against a traced (budget, scale) pair - by default this window's,
        or the NEXT window's when the driver forecasts (CI warm-start).
        In carbon mode the published price is reward-per-gCO2e.

        The (M, K) dual cost map and (I, K) membership come from the
        compiled ConstraintSpec (``dual_cost_map``/``dual_member``) -
        tenant columns draw a request's spend wherever it is served,
        region columns only from their own region's options.

        With ``donate_dual`` the lambda argument is DONATED: the update
        aliases its output onto the incoming price buffer (same shape/
        dtype, so XLA reuses it in place) and the steady-state chain
        lambda_0 -> lambda_1 -> ... runs allocation-free.  The donated
        buffer is dead afterwards - ``serve_window`` keeps
        ``self._lam_rec`` as the readable twin for records.  Every
        variant compiles as ``jit_dual_update``."""
        axis = AXIS if self.mesh is not None else None

        def _jit(fn, lam_argnum):
            if self.donate_dual:
                return jax.jit(fn, donate_argnums=(lam_argnum,))
            return jax.jit(fn)
        cfg = self.dual_cfg
        costs = self._costs
        j_n = int(costs.shape[0])
        cs = self._cs
        r_n = self.n_regions
        priced = cs.tenant_priced
        t_n = None if self.tenant_budgets is None else len(
            self.tenant_budgets)

        if cs.mode == "geotenants":
            def dual_update(rewards, valid, k_of, lam, budgets, scales):
                mask = valid if padded else None
                opt_costs = (scales[:, None] * costs[None, :]).reshape(-1)
                cost_map = cs.dual_cost_map(opt_costs, j_n)
                member = cs.dual_member(k_of, rewards.shape[0])
                bud = budgets if priced else budgets[t_n:]
                lam_new, _ = dual_descent(
                    jnp.tile(rewards, (1, r_n)), cost_map, bud, lam,
                    mask=mask, member=member, max_iters=cfg.max_iters,
                    step_size=cfg.step_size, step_decay=cfg.step_decay,
                    axis_name=axis)
                return lam_new

            if self.mesh is not None:
                dual_update = jax.shard_map(
                    dual_update, mesh=self.mesh, check_vma=False,
                    in_specs=(P(AXIS), P(AXIS), P(AXIS), P(), P(), P()),
                    out_specs=P())
            return _jit(dual_update, 3)

        if r_n is not None:
            def dual_update(rewards, valid, lam, budgets, scales):
                mask = valid if padded else None
                opt_costs = (scales[:, None] * costs[None, :]).reshape(-1)
                cost_map = cs.region_cost_map(opt_costs, j_n)
                lam_new, _ = dual_descent(
                    jnp.tile(rewards, (1, r_n)), cost_map, budgets, lam,
                    mask=mask, max_iters=cfg.max_iters,
                    step_size=cfg.step_size, step_decay=cfg.step_decay,
                    axis_name=axis)
                return lam_new

            if self.mesh is not None:
                dual_update = jax.shard_map(
                    dual_update, mesh=self.mesh, check_vma=False,
                    in_specs=(P(AXIS), P(AXIS), P(), P(), P()),
                    out_specs=P())
            return _jit(dual_update, 2)

        if priced:
            def dual_update(rewards, valid, k_of, lam, budgets, scale):
                mask = valid if padded else None
                member = cs.tenant_member(k_of)
                lam_new, _ = dual_descent(
                    rewards, (costs * scale)[:, None], budgets, lam,
                    mask=mask, member=member, max_iters=cfg.max_iters,
                    step_size=cfg.step_size, step_decay=cfg.step_decay,
                    axis_name=axis)
                return lam_new

            if self.mesh is not None:
                dual_update = jax.shard_map(
                    dual_update, mesh=self.mesh, check_vma=False,
                    in_specs=(P(AXIS), P(AXIS), P(AXIS), P(), P(), P()),
                    out_specs=P())
            return _jit(dual_update, 3)

        def dual_update(rewards, valid, lam, budget, scale):
            mask = valid if padded else None
            lam_new, _ = dual_descent(
                rewards, costs * scale, budget, lam, mask=mask,
                max_iters=cfg.max_iters, step_size=cfg.step_size,
                step_decay=cfg.step_decay, axis_name=axis)
            return lam_new

        if self.mesh is not None:
            dual_update = jax.shard_map(
                dual_update, mesh=self.mesh, check_vma=False,
                in_specs=(P(AXIS), P(AXIS), P(), P(), P()),
                out_specs=P())
        return _jit(dual_update, 2)

    def _bucket(self, n: int) -> int:
        """Pad target for an n-request window.

        ``linear``: the next multiple of ``pad_quantum`` (historical -
        tight padding, but a noisy size distribution visits many
        buckets).  ``pow2``: the next power-of-two MULTIPLE of the
        quantum, so arbitrary 10x-1000x traffic swings land on
        O(log(max/min)) compiled shapes - the zero-steady-state-
        recompile guarantee bench_scale gates on.
        """
        q = self.pad_quantum
        b = max(q, ((n + q - 1) // q) * q)
        if self.bucketing == "pow2":
            b = q * (1 << max(0, (b + q - 1) // q - 1).bit_length())
        return b

    def window_bucket(self, n: int) -> int:
        """Padded size of an n-request window - the GLOBAL bucket every
        host of a multi-process mesh derives identically from n alone
        (tenant windows bucket per block; see ``window_layout``)."""
        if self.tenant_budgets is not None:
            t_n = len(self.tenant_budgets)
            if n % t_n:
                raise ValueError(f"window size {n} not divisible by "
                                 f"{t_n} tenants")
            return self._bucket(n // t_n) * t_n
        return self._bucket(n)

    # -- multi-process array assembly ----------------------------------------

    def _repl(self, x):
        """Host value -> fully-replicated global array on the mesh
        (every process passes the same bytes - pure (seed, t) windows
        and the replicated dual chain guarantee it)."""
        from jax.experimental import multihost_utils
        return multihost_utils.host_local_array_to_global_array(
            np.asarray(x), self.mesh, P())

    def _shard_rows(self, x):
        """This host's rows of a request-sharded (b, ...) array -> the
        global array (rows stay on the host that produced them)."""
        from jax.experimental import multihost_utils
        return multihost_utils.host_local_array_to_global_array(
            np.asarray(x), self.mesh, P(AXIS))

    def _shard_tables(self, x):
        """This host's (G, rows, cap) chunk-table slice -> the global
        (G, b, cap) array sharded along the row axis."""
        from jax.experimental import multihost_utils
        return multihost_utils.host_local_array_to_global_array(
            np.asarray(x), self.mesh, P(None, AXIS, None))

    def compile_count(self) -> int:
        """Total jit cache entries (XLA traces) across every window fn
        this pipeline ever built - the delta per window lands in
        ``WindowResult.compiles``; steady-state traffic on warm buckets
        must hold it at zero."""
        total = 0
        for f in self._built:
            try:
                total += int(f._cache_size())
            except AttributeError:  # older jax: count builds, not traces
                total += 1
        return total

    # -- public API -----------------------------------------------------------

    def _named_vector(self, value, names: tuple, what: str):
        """A named per-axis dict -> the canonical vector (scalar when
        the axis is the single global one); non-dicts pass through."""
        if not isinstance(value, dict):
            return value
        missing = [k for k in names if k not in value]
        extra = [k for k in value if k not in names]
        if missing or extra:
            raise ValueError(
                f"named {what} keys must be exactly {list(names)} "
                f"(missing {missing}, unknown {extra})")
        vec = np.asarray([float(value[k]) for k in names], np.float32)
        return float(vec[0]) if names == ("global",) else vec

    def _pad_chunk_tables(self, tables: dict, n: int, b: int) -> dict:
        """A WindowChunk's (G, n, cap) tables -> the (G, b, cap) traced
        tables of this window.  Padded REQUESTS gather chunk row 0 and
        are valid-masked (exactly like the materialized path's padding
        rows), so the sentinel fill rows here are never read - they only
        keep the traced shape bucket-stable."""
        if "p" not in self._tables:
            raise ValueError("per-window chunk tables need the compact "
                             "(k3) layout; this pipeline runs the "
                             "generic scan kernel")
        p, ck = tables["p"], tables["ck"]
        if p.shape[1] != n:
            raise ValueError(f"chunk tables carry {p.shape[1]} rows for "
                             f"a {n}-request window")
        if isinstance(p, jax.Array):  # device-resident chunk: pad there
            if p.dtype != jnp.int32:
                p = p.astype(jnp.int32)
            if ck.dtype != jnp.float32:
                ck = ck.astype(jnp.float32)
            if b != n:
                p = jnp.pad(p, ((0, 0), (0, b - n), (0, 0)),
                            constant_values=self._cap)
                ck = jnp.pad(ck, ((0, 0), (0, b - n), (0, 0)))
            return {"p": p, "ck": ck, "g_of": self._tables["g_of"],
                    "n3_of": self._tables["n3_of"]}
        p = np.asarray(p, np.int32)
        ck = np.asarray(ck, np.float32)
        if b != n:
            g_n, _, cap = p.shape
            p = np.concatenate(
                [p, np.full((g_n, b - n, cap), self._cap, np.int32)],
                axis=1)
            ck = np.concatenate(
                [ck, np.zeros((g_n, b - n, cap), np.float32)], axis=1)
        self._h2d_window += int(p.nbytes + ck.nbytes)
        return {"p": jnp.asarray(p), "ck": jnp.asarray(ck),
                "g_of": self._tables["g_of"],
                "n3_of": self._tables["n3_of"]}

    def _mh_tables(self, tables: dict) -> dict:
        """A host-local (already padded + sentineled) chunk-table slice
        -> the global row-sharded tables of the multi-process pass.
        The (G,)/(J,) layout vectors are replicated once and cached."""
        if "p" not in tables:
            raise ValueError("multihost serving needs the compact (k3) "
                             "chunk-table layout")
        p = np.asarray(tables["p"], np.int32)
        ck = np.asarray(tables["ck"], np.float32)
        self._h2d_window += int(p.nbytes + ck.nbytes)
        if self._layout_mh is None:
            self._layout_mh = {
                "g_of": self._repl(self._tables["g_of"]),
                "n3_of": self._repl(self._tables["n3_of"]),
            }
        return {"p": self._shard_tables(p),
                "ck": self._shard_tables(ck), **self._layout_mh}

    def serve_window(self, ctx: np.ndarray, rows: np.ndarray, *,
                     lam=None, update_lam: bool = True, budget=None,
                     cost_scale=None, dual_budget=None,
                     dual_cost_scale=None,
                     tables: dict | None = None,
                     shard=None) -> WindowResult:
        """Serve one traffic window.

        ctx (n, d_context) raw contexts, rows (n,) user indices into the
        server's score tables - or, with ``tables`` (a ``WindowChunk``'s
        per-window (G, n, cap) compact tables), LOCAL chunk indices
        0..n-1: the fused pass then gathers within the chunk instead of
        a materialized user axis, which is how a streaming
        ``RequestSource`` serves unbounded universes (REQUIRED when the
        pipeline was built over a ``StreamUniverse``).  Decisions use
        ``lam`` (default: the pipeline's nearline price(s), i.e.
        lambda_{t-1}); the pass then publishes lambda_t unless
        ``update_lam=False``.

        ``budget`` overrides this window's budget (scalar; (T,) with
        tenant blocks; (R,) in geo mode and (T + R,) - tenant grams
        first, region grams after - in the combined mode, REQUIRED
        there together with an (R,) ``cost_scale``).  Both accept the
        NAMED form: a dict keyed by ``spec.compile().budget_names`` /
        ``.scale_names`` (the ``k_names`` constraint order) instead of
        a positional vector - the vector form stays bit-identical.
        ``cost_scale`` re-denominates the window's costs as
        ``costs * cost_scale`` - carbon pricing passes kappa*CI(t)
        [gCO2e/FLOP] here together with a gCO2e ``budget``, making the
        dual price reward-per-gram.  All are traced, so time-varying
        values never recompile; ``WindowResult.compiles`` reports this
        window's jit cache misses (nonzero only on a cold bucket).

        ``dual_budget``/``dual_cost_scale`` aim the NEARLINE update at a
        different (budget, scale) than the online pass - pass the NEXT
        window's values to warm-start the price where the grid is about
        to be (the CI-forecast warm-start; defaults: the online values).

        ``shard`` (a ``repro.distributed.multihost.HostWindowSlice``,
        normally carried by a ``MultihostSource`` chunk) switches the
        call to the MULTI-PROCESS window protocol: ``ctx``/``rows``/
        ``tables`` are this host's ALREADY-PADDED slice of the global
        window (``shard`` names the global n/bucket and the local
        valid/k_of), the pass runs over global arrays assembled from
        every host's slice, and the stitched collectives make lambda,
        spends and counters replicated - bitwise equal on every host.
        """
        if shard is not None and not self.multihost:
            raise ValueError("serve_window(shard=...) needs a pipeline "
                             "built over the multi-process mesh "
                             "(multihost=True)")
        if self.multihost and shard is None:
            raise ValueError("a multihost pipeline serves host slices: "
                             "pass shard= (use a MultihostSource)")
        n = len(rows) if shard is None else int(shard.n)
        ctx = np.asarray(ctx, np.float32)
        rows = np.asarray(rows, np.int32)
        if self._stream_only and tables is None and n:
            raise ValueError(
                "this pipeline serves a streaming universe: every "
                "window must carry its RequestSource chunk tables "
                "(serve_window(..., tables=chunk.tables))")
        bn = self._cs.budget_names
        budget = self._named_vector(budget, bn, "budget")
        dual_budget = self._named_vector(dual_budget, bn, "dual_budget")
        sn = self._cs.scale_names
        cost_scale = self._named_vector(cost_scale, sn, "cost_scale")
        dual_cost_scale = self._named_vector(dual_cost_scale, sn,
                                             "dual_cost_scale")
        cs = self._cs
        mode = cs.mode
        geo = mode == "geo"
        combined = mode == "geotenants"
        tb = self.tenant_budgets

        if combined:
            t_n, r_n = len(tb), self.n_regions
            if budget is None or cost_scale is None:
                raise ValueError(
                    "the combined tenant x region mode serves against "
                    "per-tenant AND per-region budgets: pass a "
                    f"({t_n} + {r_n},) budget (tenant grams first) and "
                    f"an ({r_n},) cost_scale every window")
            bud_vec = np.asarray(budget, np.float32).reshape(-1)
            sc_vec = np.asarray(cost_scale, np.float32).reshape(-1)
            if len(bud_vec) != t_n + r_n or len(sc_vec) != r_n:
                raise ValueError(
                    f"combined budget/cost_scale must have {t_n + r_n} "
                    f"and {r_n} entries, got {len(bud_vec)} and "
                    f"{len(sc_vec)}")
            # the tightest aggregate cap the chained walks enforce
            bud = float(min(bud_vec[:t_n].sum(), bud_vec[t_n:].sum()))
            sc = float(sc_vec.mean())
        elif geo:
            if budget is None or cost_scale is None:
                raise ValueError("geo mode serves against per-region "
                                 "budgets: pass (R,) budget and (R,) "
                                 "cost_scale every window")
            bud_vec = np.asarray(budget, np.float32).reshape(-1)
            sc_vec = np.asarray(cost_scale, np.float32).reshape(-1)
            if len(bud_vec) != self.n_regions \
                    or len(sc_vec) != self.n_regions:
                raise ValueError(f"geo budget/cost_scale must have "
                                 f"{self.n_regions} entries")
            bud, sc = float(bud_vec.sum()), float(sc_vec.mean())
        elif tb is not None:
            if budget is None:
                bud_vec = tb
            else:
                bud_vec = np.asarray(budget, np.float32).reshape(-1)
                if len(bud_vec) != len(tb):
                    raise ValueError(f"tenant budget override must have "
                                     f"{len(tb)} entries")
            sc = 1.0 if cost_scale is None else float(cost_scale)
            bud = float(bud_vec.sum())
        else:
            bud = self.budget if budget is None else float(budget)
            sc = 1.0 if cost_scale is None else float(cost_scale)
            bud_vec = None

        if n == 0:  # zero-arrival window: nothing to serve or learn from
            r_n = self.n_regions
            res = WindowResult(
                n_valid=0, budget=bud, lam_before=self._lam_rec,
                lam_after=self._lam_rec, decisions=jnp.zeros(0, jnp.int32),
                revenue=jnp.zeros(0, jnp.float32),
                spend=jnp.float32(0.0), downgraded=jnp.int32(0),
                valid=np.zeros(0, np.float32), flops=jnp.float32(0.0),
                cost_scale=sc,
                regions=(jnp.zeros(0, jnp.int32) if r_n is not None
                         else None),
                region_spend=(jnp.zeros(r_n, jnp.float32)
                              if r_n is not None else None),
                tr_spend=(jnp.zeros((len(tb), r_n), jnp.float32)
                          if combined else None),
                tenant_spend=(jnp.zeros(len(tb), jnp.float32)
                              if combined else None),
                k_budget=None if bud_vec is None else np.array(bud_vec))
            self.stats.append(res)
            if self.ledger is not None:
                self.ledger.record_result(res)
            return res

        chunked = tables is not None
        if shard is not None:
            # multi-process window: the source already laid out this
            # host's padded slice (window_layout positions lo..hi); the
            # global (n, b) pair keys the SAME bucket on every host
            if not chunked:
                raise ValueError("multihost serving streams chunk "
                                 "tables; materialized (U, J) serving "
                                 "is single-process only")
            b = int(shard.b)
            valid = np.asarray(shard.valid, np.float32)
            k_of = (None if shard.k_of is None
                    else np.asarray(shard.k_of, np.int32))
            perm = None
        else:
            # tenant windows carry T equal blocks, padded at the end of
            # EACH block so per-tenant guard walks and prices see blocks
            # aligned with their budgets; plain windows pad at the end
            b = self.window_bucket(n)
            perm, valid, k_of = window_layout(
                n, b, None if tb is None else len(tb))
            if b != n:
                m = valid > 0
                ctx_p = np.zeros((b, ctx.shape[1]), np.float32)
                rows_p = np.zeros(b, np.int32)
                ctx_p[m] = ctx[perm[m]]
                rows_p[m] = rows[perm[m]]
                ctx, rows = ctx_p, rows_p
        self._h2d_window = int(ctx.nbytes + rows.nbytes + valid.nbytes
                               + (k_of.nbytes if k_of is not None else 0))
        with self.obs.span("h2d", n=n, b=b):
            if shard is not None:
                run_tables = self._mh_tables(tables)
                ctx_j = self._shard_rows(ctx)
                rows_j = self._shard_rows(rows.astype(np.int32))
            elif chunked:
                run_tables = self._pad_chunk_tables(tables, n, b)
                rows = perm.astype(np.int32)  # gather within padded chunk
                ctx_j = jnp.asarray(ctx)
                rows_j = jnp.asarray(rows, jnp.int32)
            else:
                run_tables = self._tables
                ctx_j = jnp.asarray(ctx)
                rows_j = jnp.asarray(rows, jnp.int32)
        key = (b, b != n, chunked)
        if key not in self._fns:
            self._fns[key] = (self._build_main_fn(b, b != n),
                              self._build_dual_fn(b, b != n))
            self._built.extend(self._fns[key])
        main_fn, dual_fn = self._fns[key]
        c0 = self.compile_count()
        params = self.reward_params
        if shard is not None:
            # global twins of host-resident state, built once: params
            # replicate to every host's devices; the lambda chain is
            # converted in place and stays global from then on (dual-fn
            # outputs over the process-spanning mesh are global already)
            if self._params_mh is None:
                self._params_mh = jax.tree_util.tree_map(
                    self._repl, self.reward_params)
            params = self._params_mh
            if not self._mh_lam:
                self.lam = self._repl(self.lam)
                self._lam_rec = self._repl(self._lam_rec)
                self._mh_lam = True
            _c = self._repl  # replicated scalars / (K,) vectors
            _k = self._shard_rows  # request-sharded per-position maps
        else:
            _c = _k = jnp.asarray
        if lam is None:
            lam_in = self.lam
            lam_before_rec = self._lam_rec
        else:
            lam_in = _c(np.broadcast_to(np.asarray(lam, np.float32),
                                        np.shape(self.lam)))
            lam_before_rec = lam_in
        # the dual fn DONATES its lambda argument: hand it the chain
        # buffer only when this call advances the chain; otherwise (a
        # pinned price, or update_lam=False keeping the old chain) a
        # bitwise device copy is consumed so live buffers survive
        if not self.donate_dual:
            lam_dual = lam_in
        elif lam is None and update_lam:
            lam_dual = lam_in
        else:
            lam_dual = jnp.copy(lam_in)
        valid_j = _k(valid) if shard is not None else jnp.asarray(valid)
        k_of_j = None if k_of is None else _k(k_of)

        if combined:
            bud_j = _c(np.asarray(bud_vec, np.float32))
            sc_j = _c(np.asarray(sc_vec, np.float32))
            args = (k_of_j, lam_in, bud_j, sc_j)
        elif geo:
            bud_j = _c(np.asarray(bud_vec, np.float32))
            sc_j = _c(np.asarray(sc_vec, np.float32))
            args = (lam_in, bud_j, sc_j)
        elif tb is not None:
            bud_j = _c(np.asarray(bud_vec, np.float32))
            sc_j = _c(np.float32(sc))
            args = (k_of_j, lam_in, bud_j, sc_j)
        else:
            bud_j, sc_j = _c(np.float32(bud)), _c(np.float32(sc))
            args = (lam_in, bud_j, sc_j)
        with self.obs.span("dispatch", n=n, b=b):
            out = main_fn(params, run_tables, ctx_j, rows_j, valid_j,
                          *args)
        (rewards, dec, rev, spend, flops, dg, t_spend, regions,
         r_spend) = out[:9]
        tr_spend = out[9] if len(out) > 9 else None

        # nearline: the price update never blocks the response - it is a
        # second dispatch reusing the on-device reward matrix, and the
        # NEXT window's decisions depend on its (device-side) output.
        # dual_budget/dual_cost_scale retarget it at the next window's
        # constraint (CI-forecast warm-start); defaults keep this
        # window's, bit-identical to the non-forecast behavior.
        with self.obs.span("dual_update", n=n, b=b):
            if combined:
                d_bud = bud_j if dual_budget is None \
                    else _c(np.asarray(dual_budget,
                                       np.float32).reshape(-1))
                d_sc = sc_j if dual_cost_scale is None \
                    else _c(np.asarray(dual_cost_scale, np.float32))
                lam_new = dual_fn(rewards, valid_j, k_of_j,
                                  lam_dual, d_bud, d_sc)
            elif geo:
                d_bud = bud_j if dual_budget is None \
                    else _c(np.asarray(dual_budget, np.float32))
                d_sc = sc_j if dual_cost_scale is None \
                    else _c(np.asarray(dual_cost_scale, np.float32))
                lam_new = dual_fn(rewards, valid_j, lam_dual, d_bud, d_sc)
            elif tb is not None:
                d_bud = bud_j if dual_budget is None \
                    else _c(np.asarray(dual_budget,
                                       np.float32).reshape(-1))
                d_sc = sc_j if dual_cost_scale is None \
                    else _c(np.float32(dual_cost_scale))
                if cs.tenant_priced:
                    lam_new = dual_fn(rewards, valid_j, k_of_j,
                                      lam_dual, d_bud, d_sc)
                else:  # shared price descends on the TOTAL budget
                    lam_new = dual_fn(rewards, valid_j, lam_dual,
                                      jnp.sum(d_bud), d_sc)
            else:
                d_bud = bud_j if dual_budget is None else _c(
                    np.float32(dual_budget))
                d_sc = sc_j if dual_cost_scale is None else _c(
                    np.float32(dual_cost_scale))
                lam_new = dual_fn(rewards, valid_j, lam_dual, d_bud, d_sc)
        if update_lam:
            self.lam = lam_new
            # the chain buffer will be donated next window; records keep
            # a bitwise device copy that stays readable forever
            self._lam_rec = jnp.copy(lam_new) if self.donate_dual \
                else lam_new
            lam_after_rec = self._lam_rec
        else:  # orphan price: never enters the chain, never donated
            lam_after_rec = lam_new
        res = WindowResult(
            n_valid=n, budget=bud, lam_before=lam_before_rec,
            lam_after=lam_after_rec, decisions=dec, revenue=rev,
            spend=spend,
            downgraded=dg, valid=valid, tenant_spend=t_spend, flops=flops,
            cost_scale=sc, regions=regions, region_spend=r_spend,
            k_budget=None if bud_vec is None else np.array(bud_vec),
            tr_spend=tr_spend, compiles=self.compile_count() - c0,
            bucket=key, h2d_bytes=self._h2d_window)
        self.stats.append(res)
        if self.ledger is not None:
            self.ledger.record_result(res)
        return res

    def spend_trace(self) -> np.ndarray:
        return np.array([float(np.sum(np.asarray(r.spend)))
                         for r in self.stats])
