"""The vectorized, device-resident scenario-sweep engine.

One compiled program runs thousands of closed-loop simulations: a
scenario's demand traces are compiled to a dense ``(N, T)`` array, the
full control loop (saturated store, Eq. 1 update, clamp) runs as a
single jitted :func:`jax.lax.scan` over time, and that scan is
``vmap``'d over a :class:`GainSet` -- a whole gain grid advances in
lockstep, one XLA dispatch per gain chunk.

Closed-loop histories never leave the device.  Every
:class:`~repro.lab.score.FleetStats` metric streams through the scan
carry as per-node accumulators (Kahan-compensated float32 sums -- see
:func:`~repro.lab.score.kahan_add`), and the p99 comes from the
streaming fixed-bin quantile (:mod:`~repro.lab.score`): utilization is
quantized to ``uint16`` codes on a 65536-bin grid and the quantile is
bisected out of the implicit histogram with 16 count reductions.  Each
chunk therefore transfers O(G) scalars to the host; chunks are
dispatched asynchronously and collected once at the end.

The gain axis also shards across devices: ``sweep_demand(...,
devices=...)`` (auto-detected by default) runs each device's slice of
the chunk under ``shard_map`` over a 1-D ``("gains",)`` mesh; demand is
replicated, gains are split, and no collectives are needed.  With a
single device the plain jitted path is taken and results are
bit-identical to the sharded one (each gain's program is unchanged).

Gain chunks bound peak *device* memory (the uint16 code history is
``chunk x T x N x 2`` bytes); ``chunk=None`` picks the largest chunk
within :data:`CODES_BUDGET_BYTES`.

**CacheLoop** (a :class:`~repro.lab.scenarios.CacheSpec`) adds per-node
cache state to the scan carry and scores ``hit_ratio`` /
``evicted_bytes`` / ``app_runtime``; **AppGraph** (an
:class:`~repro.lab.appgraph.AppGraphSpec`) co-simulates the scenario's
stage DAG in the same scan and scores ``makespan``.  The closed-loop
step itself -- law, cache, queue, accumulators -- lives once in
:mod:`.step`; this module is its XLA lowering.  A mixed
paper/beyond-paper gain set is partitioned by law class
(:func:`paper_law_mask`) so only the points with active beyond-paper
knobs pay for the fallback executable.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import time
from typing import (Callable, List, NamedTuple, Optional, Sequence, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..analysis.runtime import (count, dispatch_guard, record_trace,
                                sanitizers_enabled, span)
from . import step
from ._compat import warn_once
from ..core.control import ControllerParams
from ..core.traces import GiB
from .appgraph import AppGraphSpec, compile_graph
from .scenarios import CacheSpec, ScenarioSpec, get_scenario
from .score import FleetStats, default_score

# Upper bound on gains per compiled chunk; the auto-chunk logic lowers
# it when the per-gain uint16 code history would blow the budget.
# (Named for the engine it belongs to since PR 9 -- the pallas engine
# tiles lanes by pallas_sweep.TILE_GAINS instead.  The old spelling
# ``DEFAULT_CHUNK`` still resolves through a module __getattr__ shim.)
XLA_DEFAULT_CHUNK = 32
CODES_BUDGET_BYTES = 256 << 20

ENGINES = ("xla", "pallas")


def _resolve_engine(engine: str, who: str) -> str:
    if engine not in ENGINES:
        raise ValueError(f"{who}: unknown engine {engine!r}; "
                         f"expected one of {ENGINES}")
    return engine


# ---------------------------------------------------------------------------
# Gain sets
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GainSet:
    """``G`` candidate control-law gain points, packed as arrays.

    Every law knob the sweep engine simulates is here -- a
    :class:`ControllerParams` round-trips losslessly through
    :meth:`from_params` / :meth:`params_at`, so the loop a tune run
    scores is the loop the tuned params deploy.  ``lam_grant`` equals
    ``lam`` where the gains are symmetric (the paper-faithful case);
    capacities are bytes.  Scalar / length-1 fields broadcast to the
    set's length.
    """

    r0: np.ndarray
    lam: np.ndarray
    lam_grant: np.ndarray
    u_min: np.ndarray
    u_max: np.ndarray
    deadband: np.ndarray = 0.0
    feedforward: np.ndarray = 0.0

    def __post_init__(self) -> None:
        arrays = {f.name: np.atleast_1d(np.asarray(getattr(self, f.name),
                                                   dtype=np.float64))
                  for f in dataclasses.fields(self)}
        g = max(a.shape[0] for a in arrays.values())
        sizes = {a.shape[0] for a in arrays.values()} - {1, g}
        if sizes:
            raise ValueError(f"gain arrays must share a length or be "
                             f"scalar; got lengths {sizes | {g}}")
        for name, arr in arrays.items():
            object.__setattr__(self, name,
                               np.broadcast_to(arr, (g,)).copy()
                               if arr.shape[0] != g else arr)

    def __len__(self) -> int:
        return self.r0.shape[0]

    @classmethod
    def from_params(cls, params: ControllerParams,
                    *more: ControllerParams) -> "GainSet":
        ps = (params,) + more
        return cls(
            r0=np.array([p.r0 for p in ps]),
            lam=np.array([p.lam for p in ps]),
            lam_grant=np.array([p.lam_grant if p.lam_grant is not None
                                else p.lam for p in ps]),
            u_min=np.array([p.u_min for p in ps]),
            u_max=np.array([p.u_max for p in ps]),
            deadband=np.array([p.deadband for p in ps]),
            feedforward=np.array([p.feedforward for p in ps]),
        )

    def params_at(self, i: int, base: ControllerParams) -> ControllerParams:
        """Materialize gain point ``i`` as a :class:`ControllerParams`."""
        lam = float(self.lam[i])
        lam_grant = float(self.lam_grant[i])
        return base.replace(
            r0=float(self.r0[i]), lam=lam,
            lam_grant=None if lam_grant == lam else lam_grant,
            u_min=float(self.u_min[i]), u_max=float(self.u_max[i]),
            deadband=float(self.deadband[i]),
            feedforward=float(self.feedforward[i]))

    def concat(self, other: "GainSet") -> "GainSet":
        return GainSet(*(np.concatenate([getattr(self, f.name),
                                         getattr(other, f.name)])
                         for f in dataclasses.fields(self)))

    def slice(self, lo: int, hi: int) -> "GainSet":
        return GainSet(*(getattr(self, f.name)[lo:hi]
                         for f in dataclasses.fields(self)))

    def take(self, idx: Sequence[int]) -> "GainSet":
        """Gather gain points by index (survivor promotion in halving)."""
        idx = np.asarray(idx, dtype=np.int64)
        return GainSet(*(getattr(self, f.name)[idx]
                         for f in dataclasses.fields(self)))


# ---------------------------------------------------------------------------
# The compiled chunk: streaming closed loop, one gain
# ---------------------------------------------------------------------------

def _one_gain_stream(demand_tn, m, inv_m, r0_g, lam_g, lam_grant_g, u_min_g,
                     u_max_g, db_g, ff_g, interval_s, occupancy, *,
                     paper_law: bool, unit_occupancy: bool,
                     static_bounds: Optional[Tuple[float, float]],
                     cache: Optional[CacheSpec],
                     app_graph: Optional[AppGraphSpec] = None,
                     work_sn=None,
                     axis_name: Optional[str] = None,
                     node_shards: int = 1):
    """Closed loop for one gain point: the XLA lowering of :mod:`.step`
    over ``(N,)`` node lanes.

    The scan carry is the step's groups in order, then the clock ``t``
    (O(N) state); the sole scan output is the uint16 utilization code
    history the in-program quantile bisects.  ``static_bounds`` clamps
    against compile-time constants when every gain point shares its
    capacity bounds.  Under the 2-D gains x nodes mesh (``axis_name``
    set) the lanes are one node shard: only the stat folds, the
    quantile counts and the AppGraph barrier folds are collectives,
    over the *global* fleet (``n_nodes * node_shards``); the ``(S+1,
    N)`` AppGraph work matrix arrives as the traced ``work_sn``.
    """
    n_steps, n_nodes = demand_tn.shape
    if static_bounds is not None:
        u_min_g, u_max_g = static_bounds
    u0 = jnp.full((n_nodes,), u_max_g, jnp.float32)
    zeros = jnp.zeros((n_nodes,), jnp.float32)
    izeros = jnp.zeros((n_nodes,), step.count_dtype(n_steps))
    g = step.gain_rows(r0_g, lam_g, lam_grant_g, u_min_g, u_max_g, db_g,
                       ff_g)
    nodes = step.Rows((m, inv_m, None, None), step.NODE_ROWS)
    wf0 = cc = q = None
    if cache is not None:
        w = step.working_set(cache, m, jnp.float32)
        nodes = step.Rows((m, inv_m, w, 1.0 / w), step.NODE_ROWS)
        cc = step.cache_consts(cache, interval_s, jnp.float32)
        res0, wf0 = step.warm_seed(cc, u0, nodes)
    if app_graph is not None:
        q = step.queue_consts(app_graph, interval_s, work_sn, axis_name)
    loop = step.Loop(paper_law, unit_occupancy, occupancy, interval_s, cc, q,
                     inv_gib=jnp.float32(1.0 / GiB))
    names = step.carry_names(paper_law, cache is not None,
                             app_graph is not None)

    def body(carry, d):
        s = dict(zip(names, carry))
        out, codes = step.step(s, d, s["t"], g, nodes, loop, wf0)
        return [out[n] for n in names], codes

    s0 = step.acc_init(zeros, izeros, carried_clock=True)
    if cache is not None:
        s0.update(dict.fromkeys(step.CACHE, zeros), resident=res0)
    if app_graph is not None:
        s0.update(step.queue_init(q, zeros))
    s0["u"] = u0
    if not paper_law:
        # Seed v_prev with the first interval's usage so the slope term
        # is exactly zero before there is a previous observation
        # (matching the scalar loop's v_prev=None first step).
        d0 = demand_tn[0]
        if app_graph is not None:
            d0 = step.queue_observe(q, d0, 0)
        s0["v_prev"] = step.observe(s0, d0, loop)
    carry, codes = jax.lax.scan(body, [s0[n] for n in names], demand_tn,
                                unroll=2)
    return step.finalize(dict(zip(names, carry)), codes, r0_g, n_steps,
                         n_nodes * node_shards, interval_s, cache=cc,
                         queue=q, axis_name=axis_name)


def _chunk_stats(demand_tn, m, r0, lam, lam_grant, u_min, u_max, deadband,
                 feedforward, interval_s, occupancy, *, paper_law: bool,
                 unit_occupancy: bool,
                 static_bounds: Optional[Tuple[float, float]],
                 cache: Optional[CacheSpec],
                 app_graph: Optional[AppGraphSpec] = None,
                 work_sn=None, spec: str = "",
                 axis_name: Optional[str] = None, node_shards: int = 1):
    """One gain chunk: scan over T, vmap over gains -> (G,)-field stats.

    ``demand_tn`` is ``(T, N)`` bytes, ``m`` ``(N,)`` bytes, gain arrays
    ``(G,)``; ``interval_s`` and ``occupancy`` ride along as traced
    scalars.  Under the 2-D mesh ``demand_tn``/``m`` are one node shard
    and ``axis_name``/``node_shards`` make the stat folds collective.
    """
    # Trace-time only: the recompile counter the sanitizer fixtures
    # assert on.  Its key must be one-to-one with the executable cache
    # key -- shapes from the operands, the rest of the key (devices,
    # plan, full CacheSpec, mesh shape) in ``spec``, its digest.
    record_trace("lab.sweep.chunk", chunk=int(r0.shape[0]),
                 horizon=int(demand_tn.shape[0]),
                 nodes=int(demand_tn.shape[1]),
                 paper_law=bool(paper_law), spec=spec)
    demand_tn = jnp.asarray(demand_tn, jnp.float32)
    m = jnp.asarray(m, jnp.float32)
    inv_m = 1.0 / m
    if work_sn is not None:
        work_sn = jnp.asarray(work_sn, jnp.float32)

    def one_gain(r0_g, lam_g, lam_grant_g, u_min_g, u_max_g, db_g, ff_g):
        return _one_gain_stream(demand_tn, m, inv_m, r0_g, lam_g,
                                lam_grant_g, u_min_g, u_max_g, db_g, ff_g,
                                interval_s, occupancy, paper_law=paper_law,
                                unit_occupancy=unit_occupancy,
                                static_bounds=static_bounds, cache=cache,
                                app_graph=app_graph, work_sn=work_sn,
                                axis_name=axis_name,
                                node_shards=node_shards)

    return jax.vmap(one_gain)(*(
        jnp.asarray(c, jnp.float32)
        for c in (r0, lam, lam_grant, u_min, u_max, deadband, feedforward)))


def _spec_digest(*parts) -> str:
    """Short stable digest of one compiled-program cache key.

    Folded into the recompile-counter dims (``lab.sweep.chunk``,
    ``lab.sweep.pallas``) so the counter key is one-to-one with the
    executables that legitimately exist: two :class:`CacheSpec`\\ s (or
    device tuples, mesh shapes, or bound specializations) at the same
    shape compile separately and must count separately.  ``repr`` of a
    frozen dataclass / device string is deterministic, so the digest is
    stable across processes too.
    """
    return hashlib.sha1(repr(parts).encode()).hexdigest()[:12]


@functools.lru_cache(maxsize=None)
def _compiled_sweep(devices: Tuple, paper_law: bool, unit_occupancy: bool,
                    static_bounds: Optional[Tuple[float, float]],
                    cache: Optional[CacheSpec], node_shards: int = 1,
                    app_graph: Optional[AppGraphSpec] = None):
    """Jitted chunk program for a device tuple (sharded when > 1).

    ``node_shards == 1``: the gain axis splits over a 1-D ``("gains",)``
    mesh; demand and node memory replicate, and per-gain programs are
    the single-device ones, so sharding changes placement, not results.
    ``node_shards > 1``: a 2-D ``("gains", "nodes")`` mesh also splits
    the node axis, so fleets too large for one device's code budget
    shard too; the stat folds run ``psum``/``pmax`` over ``"nodes"``,
    which reassociates float adds (reduction tolerance, not bitwise --
    one device stays the bit-exact reference).
    """
    spec = _spec_digest(tuple(str(d) for d in devices), paper_law,
                        unit_occupancy, static_bounds, cache, node_shards,
                        app_graph)
    fn = functools.partial(_chunk_stats, paper_law=paper_law,
                           unit_occupancy=unit_occupancy,
                           static_bounds=static_bounds, cache=cache,
                           app_graph=app_graph, spec=spec,
                           axis_name="nodes" if node_shards > 1 else None,
                           node_shards=node_shards)
    if app_graph is not None:
        # The work matrix rides as a third leading positional operand
        # (node-sharded like demand); routed through a wrapper so the
        # app_graph=None program keeps its exact historical signature
        # and jaxpr.
        base = fn

        def fn(demand_tn, m, work_sn, *rest):
            return base(demand_tn, m, *rest, work_sn=work_sn)

    def lab_sweep_chunk(*args):
        # A named function, so the executable is ``jit_lab_sweep_chunk``
        # in a profiler trace.
        return fn(*args)

    if len(devices) <= 1:
        return jax.jit(lab_sweep_chunk)
    mapped = jax.shard_map(
        lab_sweep_chunk, mesh=sweep_mesh(devices, node_shards),
        in_specs=(_lead_specs(node_shards, app_graph is not None)
                  + (P("gains"),) * 7 + (P(), P())),
        out_specs=P("gains"),
        check_vma=False)
    return jax.jit(mapped)


def sweep_mesh(devices: Tuple, node_shards: int = 1) -> Mesh:
    """The 1-D ``("gains",)`` or 2-D ``("gains", "nodes")`` sweep mesh."""
    if node_shards == 1:
        return Mesh(np.asarray(devices), ("gains",))
    grid = np.asarray(devices).reshape(len(devices) // node_shards,
                                       node_shards)
    return Mesh(grid, ("gains", "nodes"))


def _lead_specs(node_shards: int, with_work: bool) -> Tuple:
    """Partition specs of demand, node memory and (AppGraph) work."""
    node_p = P(None) if node_shards == 1 else P("nodes")
    demand_p = P(None, None) if node_shards == 1 else P(None, "nodes")
    lead = (demand_p, node_p)
    return lead + (demand_p,) if with_work else lead   # work_sn (S+1, N)


def _stager(devices: Tuple, node_shards: int):
    """``stage(array, spec)``: put an operand where the program reads it.

    On a mesh each operand goes to its own ``NamedSharding`` once, so no
    chunk call reshards it; one device keeps the default placement.
    """
    if len(devices) <= 1:
        return lambda x, spec: jnp.asarray(x)
    mesh = sweep_mesh(devices, node_shards)
    return lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))


@functools.lru_cache(maxsize=None)
def _transposer(dtype: np.dtype, sharding: Optional[NamedSharding]):
    """The jitted ``(N, T) f32 -> (T, N) dtype`` program, per placement."""
    placement = "default" if sharding is None else repr(sharding)

    def lab_stage_demand(demand_nt):
        # A named function, so the executable is ``jit_lab_stage_demand``
        # in a profiler trace.
        record_trace("lab.sweep.stage_demand", nodes=demand_nt.shape[0],
                     steps=demand_nt.shape[1], dtype=dtype.name,
                     placement=placement)
        return demand_nt.T.astype(dtype)

    return jax.jit(lab_stage_demand, out_shardings=sharding)


def stage_demand(demand: np.ndarray, dtype=np.float32,
                 sharding: Optional[NamedSharding] = None) -> jax.Array:
    """Put an ``(N, T)`` host demand on the device as the ``(T, N)``
    operand the sweep programs read, once per entry-point call.

    The host only casts, contiguously (no copy for C-ordered float32);
    the device transposes and, for ``dtype`` bfloat16, rounds.  Either
    step is exact or elementwise, so the operand equals
    ``np.ascontiguousarray(demand.T, np.float32)`` cast to ``dtype``
    bit for bit.  ``sharding`` places the ``(T, N)`` operand on a mesh:
    the block goes up under the transposed spec, so each device
    transposes its own rows; ``None`` takes the default device.  Counts
    ``lab.demand.staged`` and ``lab.demand.staged_bytes``.
    """
    host = np.ascontiguousarray(demand, np.float32)
    count("lab.demand.staged")
    count("lab.demand.staged_bytes", host.nbytes)
    if sharding is None:
        block = jax.device_put(host)
    else:
        block = jax.device_put(host, NamedSharding(
            sharding.mesh, P(*reversed(sharding.spec))))
    return _transposer(np.dtype(dtype), sharding)(block)


def resolve_devices(devices: Union[None, int, Sequence] = None) -> Tuple:
    """Normalize the ``devices`` knob to a tuple of jax devices.

    ``None`` auto-detects every local device; an int takes the first
    ``n``; an explicit sequence is used as given.
    """
    if devices is None:
        return tuple(jax.local_devices())
    if isinstance(devices, int):
        local = jax.local_devices()
        if not 1 <= devices <= len(local):
            raise ValueError(f"devices={devices} but only {len(local)} "
                             "local devices exist")
        return tuple(local[:devices])
    return tuple(devices)


def _check_args(demand, horizon: Optional[int], *,
                cache: Optional[CacheSpec] = None, occupancy: float = 1.0,
                node_shards: int = 1, precision: str = "f32") -> np.ndarray:
    """The checks every sweep entry point makes; returns ``demand`` cut
    to its first ``horizon`` intervals (the last axis)."""
    demand = np.asarray(demand)
    if cache is not None and float(occupancy) != 1.0:
        raise ValueError("cache modeling replaces the occupancy "
                         "abstraction; need occupancy == 1.0")
    if node_shards < 1:
        raise ValueError("node_shards must be >= 1")
    if precision not in ("f32", "bf16"):
        raise ValueError("precision must be f32|bf16")
    if horizon is not None:
        if not 1 <= horizon <= demand.shape[-1]:
            raise ValueError(f"horizon must be in [1, {demand.shape[-1]}]")
        demand = demand[..., :horizon]
    return demand


def _mesh_devices(devices, node_shards: int, n_nodes: int):
    """``(devices, node_shards)`` of a sweep.  One device always runs
    the plain jitted program, whatever ``node_shards`` was requested:
    the bit-exact fallback."""
    devs = resolve_devices(devices)
    if len(devs) <= 1:
        return devs, 1
    if len(devs) % node_shards:
        raise ValueError(f"devices ({len(devs)}) must divide evenly into "
                         f"node_shards={node_shards}")
    if n_nodes % node_shards:
        raise ValueError(f"n_nodes ({n_nodes}) must be divisible by "
                         f"node_shards={node_shards}")
    return devs, node_shards


def _resolve_chunk(chunk: Optional[int], n_gains: int, n_steps: int,
                   n_nodes: int, n_dev: int) -> int:
    """Gains per compiled call: memory-capped, device-divisible.

    The auto chunk never exceeds the code budget -- a huge (T, N)
    shape degrades to one gain per call rather than overshooting
    device memory.
    """
    if chunk is None:
        per_gain = max(n_steps * n_nodes * 2, 1)       # uint16 codes
        chunk = min(max(int(CODES_BUDGET_BYTES // per_gain), 1),
                    XLA_DEFAULT_CHUNK)
    chunk = max(int(chunk), 1)
    chunk = min(chunk, max(n_gains, 1))
    # round up so every device holds the same number of gain points
    return -(-chunk // n_dev) * n_dev


# ---------------------------------------------------------------------------
# The sweep driver
# ---------------------------------------------------------------------------

class SweepPlan(NamedTuple):
    """Trace-time specializations one gain set compiles under."""

    paper_law: bool
    unit_occupancy: bool
    static_bounds: Optional[Tuple[float, float]]


def paper_law_mask(gains: GainSet) -> np.ndarray:
    """Per gain point: does the specialized paper-faithful law apply?

    A point leaves the fast path only when a beyond-paper knob is
    actually active -- asymmetric grant gain, nonzero deadband, or
    slope feedforward.
    """
    return ((gains.feedforward == 0.0) & (gains.deadband == 0.0)
            & (gains.lam_grant == gains.lam))


def plan_specialization(gains: GainSet,
                        occupancy: float = 1.0) -> SweepPlan:
    """The specializations :func:`sweep_demand` compiles ``gains`` under.

    With a fully paper-faithful gain set (symmetric gains, zero
    deadband, zero feedforward) the hot loop sheds the slope state and
    both law branches -- the common case (default grids, every registry
    preset) runs ~2x faster.  Uniform capacity bounds clamp against
    compile-time constants.  Mixed gain sets are partitioned by
    :func:`paper_law_mask` first, so this expects one law class.
    """
    static_bounds = None
    if np.unique(gains.u_min).size == 1 and np.unique(gains.u_max).size == 1:
        static_bounds = (float(gains.u_min[0]), float(gains.u_max[0]))
    return SweepPlan(paper_law=bool(paper_law_mask(gains).all()),
                     unit_occupancy=float(occupancy) == 1.0,
                     static_bounds=static_bounds)


def sweep_demand(
    demand: np.ndarray,
    gains: GainSet,
    *,
    node_memory: Union[float, np.ndarray],
    interval_s: float = 0.1,
    occupancy: float = 1.0,
    chunk: Optional[int] = None,
    devices: Union[None, int, Sequence] = None,
    cache: Optional[CacheSpec] = None,
    app_graph: Optional[AppGraphSpec] = None,
    node_shards: int = 1,
    horizon: Optional[int] = None,
    engine: str = "xla",
) -> FleetStats:
    """Sweep a raw ``(N, T)`` demand matrix over every gain point.

    The low-level entry: :func:`run_sweep` compiles a scenario down to
    this, and ``cluster_sim.simulate_fleet`` feeds it the historical
    fleet workload directly.  Returns ``(G,)``-field stats as numpy.

    ``engine`` selects the backend: ``"xla"`` (this module) or
    ``"pallas"`` (:func:`~repro.lab.pallas_sweep.pallas_sweep_demand`,
    which takes pallas-only knobs like ``precision=``).  ``horizon``
    truncates the loop to the first ``horizon`` intervals.  ``cache``
    enables CacheLoop, ``app_graph`` the stage-DAG co-simulation scored
    through ``FleetStats.makespan`` (see :mod:`.step`).

    Every chunk is dispatched before any result is collected, so on an
    asynchronous backend chunk k+1 computes while chunk k's (G,)-scalar
    stats drain.  The host phases run under the spans
    ``lab.sweep.stage`` / ``dispatch`` / ``drain`` / ``merge`` and
    count ``lab.sweep.chunks`` and ``lab.sweep.lane_steps.{live,run}``
    (:mod:`repro.analysis.runtime`); the demand goes up once a call
    (:func:`stage_demand`).  ``devices`` shards the gain axis;
    ``node_shards > 1`` also splits the node axis over a 2-D
    ``(gains x nodes)`` mesh -- ``len(devices)`` must be divisible by
    ``node_shards`` and ``N`` by the shard count.  Stats do not depend
    on chunking or sharding (node-sharded float sums to reduction
    tolerance; one device always runs the bit-exact plain jit).  A gain
    set mixing paper-faithful and beyond-paper points runs each law
    class on its own specialized executable over the same staged
    operands.
    """
    if _resolve_engine(engine, "sweep_demand") == "pallas":
        from .pallas_sweep import pallas_sweep_demand
        return pallas_sweep_demand(
            demand, gains, node_memory=node_memory, interval_s=interval_s,
            occupancy=occupancy, chunk=chunk, devices=devices, cache=cache,
            app_graph=app_graph, node_shards=node_shards, horizon=horizon)
    demand = _check_args(demand, horizon, cache=cache, occupancy=occupancy,
                         node_shards=node_shards)
    n_nodes, n_steps = demand.shape
    with span("lab.sweep.stage"):
        devs, node_shards = _mesh_devices(devices, node_shards, n_nodes)
        # Stage every operand device-side (f32) exactly once, before the
        # guarded dispatch loops, so the loop bodies are transfer-free
        # (which dispatch_guard() enforces under PLANECHECK_SANITIZERS=1).
        # Law classes share all but their gain columns.
        stage = _stager(devs, node_shards)
        lead_p = _lead_specs(node_shards, app_graph is not None)
        placement = None if len(devs) <= 1 else NamedSharding(
            sweep_mesh(devs, node_shards), lead_p[0])
        m = np.broadcast_to(np.asarray(node_memory, np.float64),
                            (n_nodes,)).astype(np.float32)
        lead = (stage_demand(demand, sharding=placement),
                stage(m, lead_p[1]))
        if app_graph is not None:
            # The (S+1, N) work matrix compiles against the *global*
            # fleet (task round-robin and slow-node skew need true node
            # indices) and is staged once like demand; node sharding
            # splits its column axis the same way.
            work = compile_graph(app_graph, n_nodes).work_gib
            lead = lead + (stage(work, lead_p[2]),)
        tail = (stage(np.float32(interval_s), P()),
                stage(np.float32(occupancy), P()))

        def pack(gains: GainSet) -> _Chunks:
            width = _resolve_chunk(chunk, len(gains), n_steps, n_nodes,
                                   len(devs) // node_shards)
            # Pad the ragged tail up to the chunk width so every call
            # hits the same shape-specialized executable; the padded
            # rows' stats are sliced off after the drain.
            n_real = len(gains)
            gains = _pad_gains(gains, width)
            plan = plan_specialization(gains, occupancy)
            fn = _compiled_sweep(devs, plan.paper_law, plan.unit_occupancy,
                                 plan.static_bounds, cache, node_shards,
                                 app_graph)
            gain_cols = [np.asarray(getattr(gains, f.name), np.float32)
                         for f in dataclasses.fields(GainSet)]
            calls = [(*lead, *(stage(a[lo:lo + width], P("gains"))
                               for a in gain_cols), *tail)
                     for lo in range(0, len(gains), width)]
            return _Chunks(fn, calls, n_real, len(gains), n_steps)

        classes = _law_classes(gains)
        job = pack(gains) if classes is None else None
    if classes is None:
        return _run_chunks(job)
    return _run_law_classes(gains, classes, pack)


class _Chunks(NamedTuple):
    """One law class, packed: its program and each chunk's operands."""

    fn: Callable
    calls: List[tuple]
    n_real: int          # gain points, before padding
    n_run: int           # lanes dispatched, padding included
    n_steps: int


def _pad_gains(gains: GainSet, multiple: int) -> GainSet:
    """Repeat the last gain point up to a multiple of ``multiple``."""
    short = (-len(gains)) % multiple
    if not short:
        return gains
    pad = GainSet(*(np.repeat(getattr(gains, f.name)[-1:], short)
                    for f in dataclasses.fields(GainSet)))
    return gains.concat(pad)


def _law_classes(gains: GainSet) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Indices of the paper-law and the beyond-paper points, or None
    where every point shares one law class."""
    mask = paper_law_mask(gains)
    if mask.all() or not mask.any():
        return None
    return np.flatnonzero(mask), np.flatnonzero(~mask)


def _run_law_classes(gains: GainSet, classes, pack) -> FleetStats:
    """Run each law class at its own specialization on the operands
    staged once, and stitch stats back in gain order, so the
    beyond-paper points never drag the whole grid off the fast path.
    Each class packs its gains under its own ``lab.sweep.stage`` span,
    after the entry point's, so no stage span nests."""
    parts = []
    for idx in classes:
        with span("lab.sweep.stage"):
            job = pack(gains.take(idx))
        parts.append((idx, _run_chunks(job)))
    with span("lab.sweep.merge"):
        return _stitch(len(gains), *parts)


def _run_chunks(job: _Chunks) -> FleetStats:
    """Dispatch every chunk, then drain them all: chunk k+1 computes
    while chunk k's (G,)-scalar stats come back."""
    count("lab.sweep.chunks", len(job.calls))
    count("lab.sweep.lane_steps.live", job.n_real * job.n_steps)
    count("lab.sweep.lane_steps.run", job.n_run * job.n_steps)
    if sanitizers_enabled():
        # Compile (and its constant transfers) happen outside the guard;
        # the guarded loop below then replays only cached executables.
        jax.block_until_ready(job.fn(*job.calls[0]))
    with span("lab.sweep.dispatch"):
        pending = []
        with dispatch_guard():
            for args in job.calls:
                pending.append(job.fn(*args))
    with span("lab.sweep.drain"):
        chunks = [jax.tree_util.tree_map(np.asarray, st) for st in pending]
    with span("lab.sweep.merge"):
        return FleetStats(*(np.concatenate([getattr(c, f)
                                            for c in chunks])[:job.n_real]
                            for f in FleetStats._fields))


def _stitch(n: int, *parts) -> FleetStats:
    """Stats of law-class subsets, back in gain order."""
    merged = []
    for f in FleetStats._fields:
        out = None
        for idx, stats in parts:
            a = getattr(stats, f)
            if out is None:
                out = np.empty(n, dtype=a.dtype)
            out[idx] = a
        merged.append(out)
    return FleetStats(*merged)


def oracle_history(demand: np.ndarray, m, params: ControllerParams,
                   occupancy: float = 1.0) -> Tuple[np.ndarray, np.ndarray]:
    """Float64 numpy replay of Eq. 1 on a saturated store: the oracle.

    Independent of the engines (scalar law, no precomputed reciprocals,
    no streaming): returns the dense ``(T, N)`` utilization history
    ``v / M`` and the granted capacity after each interval (bytes) for
    one gain point, which tests and the chip smoke reduce to the stats
    :func:`sweep_demand` streams.
    """
    demand = np.asarray(demand, np.float64)
    m = np.broadcast_to(np.asarray(m, np.float64), (demand.shape[0],))
    n, t = demand.shape
    u = np.full(n, params.u_max, np.float64)
    v_prev = None
    utils = np.empty((t, n))
    caps = np.empty((t, n))
    for i in range(t):
        v = demand[:, i] + occupancy * u
        v_eff = v.copy()
        if params.feedforward > 0.0 and v_prev is not None:
            v_eff = v + params.feedforward * (v - v_prev)
        r = v_eff / m
        err = r - params.r0
        lam = np.where(
            err < 0,
            params.lam if params.lam_grant is None else params.lam_grant,
            params.lam)
        u_next = u - lam * v_eff * err / params.r0
        if params.deadband > 0.0:
            u_next = np.where(np.abs(err) <= params.deadband, u, u_next)
        u = np.clip(u_next, params.u_min, params.u_max)
        utils[i] = v / m
        caps[i] = u
        v_prev = v
    return utils, caps


@dataclasses.dataclass
class SweepResult:
    """Everything one sweep produced, gain-point-aligned."""

    scenario: ScenarioSpec
    gains: GainSet
    stats: FleetStats                 # (G,) numpy fields
    seed: int
    elapsed_s: float
    objective: Optional[object] = None  # score fn the sweep was run under

    @property
    def n_configs(self) -> int:
        return len(self.gains)

    def _score_fn(self, score_fn):
        if score_fn is not None:
            return score_fn
        return self.objective if self.objective is not None \
            else default_score

    def scores(self, score_fn=None) -> np.ndarray:
        """Score every gain point; defaults to the stored objective."""
        return np.asarray(self._score_fn(score_fn)(self.stats))

    def best(self, score_fn=None) -> int:
        return int(np.argmax(self.scores(score_fn)))

    def top(self, k: int = 5, score_fn=None) -> Sequence[int]:
        s = self.scores(score_fn)
        return list(np.argsort(-s)[:k])


def run_sweep(
    scenario: Union[str, ScenarioSpec],
    gains: GainSet,
    *,
    seed: int = 0,
    chunk: Optional[int] = None,
    node_memory: Optional[Union[float, np.ndarray]] = None,
    devices: Union[None, int, Sequence] = None,
    horizon: Optional[int] = None,
    node_shards: int = 1,
    engine: str = "xla",
    objective=None,
) -> SweepResult:
    """Compile ``scenario`` and run its closed loop over every gain.

    ``node_memory`` overrides the scenario's per-node budget (bytes);
    by default the spec's (possibly jittered) fleet memory is used.
    ``horizon`` truncates the closed loop to the scenario's first
    ``horizon`` intervals -- the successive-halving tuner scores cheap
    prefix rounds with it while reusing the same demand compilation.
    ``node_shards`` splits the node axis across devices (2-D mesh; see
    :func:`sweep_demand`).  ``engine`` selects the sweep backend
    (``"xla"`` | ``"pallas"``); ``objective`` (a registry name or
    ``FleetStats -> scores`` callable) is stored on the result so
    ``result.scores()`` / ``result.best()`` default to it.
    """
    _resolve_engine(engine, "run_sweep")
    if objective is not None:
        from .tune import resolve_objective
        objective = resolve_objective(objective)
    spec = get_scenario(scenario)
    demand = spec.build_demand(seed=seed)
    if horizon is not None:
        if not 1 <= horizon <= spec.n_intervals:
            raise ValueError(f"horizon must be in [1, {spec.n_intervals}]")
        demand = demand[:, :horizon]
        spec = spec.replace(n_intervals=horizon)
    m = spec.build_node_memory(seed=seed) if node_memory is None \
        else node_memory
    t0 = time.perf_counter()
    stats = sweep_demand(
        demand, gains, node_memory=m, interval_s=spec.interval_s,
        occupancy=spec.occupancy, chunk=chunk, devices=devices,
        cache=spec.cache, app_graph=spec.app_graph,
        node_shards=node_shards, engine=engine)
    elapsed = time.perf_counter() - t0
    return SweepResult(scenario=spec, gains=gains, stats=stats, seed=seed,
                       elapsed_s=elapsed, objective=objective)


def __getattr__(name: str):
    if name == "DEFAULT_CHUNK":
        warn_once("sweep:DEFAULT_CHUNK",
                  "repro.lab.sweep.DEFAULT_CHUNK was renamed to "
                  "XLA_DEFAULT_CHUNK in the PR-9 engine unification; "
                  "the old name will go away")
        return XLA_DEFAULT_CHUNK
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
