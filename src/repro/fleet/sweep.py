"""The fused (tenants x nodes) fleet sweep: two-level control, batched.

The fleet analogue of :mod:`repro.lab.sweep`: one compiled program
rolls the *composed* two-level system forward -- every tenant's Eq. 1
loop every interval, the global arbiter every ``epoch_intervals``
intervals -- as a nested ``lax.scan`` (epochs outer, intervals inner),
``vmap``'d over a :class:`~repro.lab.sweep.GainSet`, sharded over the
same 1-D ``("gains",)`` or 2-D ``("gains", "nodes")`` device mesh the
lab engine uses.  The arbitration policy compiles in as a trace-time
constant through :func:`~repro.fleet.arbiter.arbitrate` -- pure one-hot
array math, no host syncs, so the whole epoch loop fuses.

Stats are the lab's :class:`~repro.lab.score.FleetStats` computed on
the *fleet-level* closed loop -- utilization is all tenants' usage over
physical node memory, capacity is the summed storage grant -- so fleet
sweeps score with the same objectives single-plane sweeps do.  On top
of those, :class:`FleetExtras` streams the arbitration invariants
(conservation slack, floor slack, per-tenant budget statistics) out of
the scan so tests assert them over *every* epoch of every gain point
without materializing a history.

Parity: :func:`fleet_reference` is the float64 numpy oracle -- scalar
per-node loops, the exact runtime arbitration semantics
(:func:`~repro.fleet.arbiter.arbitrate_reference` each epoch) -- and
the test suite pins the fused path against it, mirroring the
``ArrayController`` / ``DynIMSController`` contract one level up.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..analysis.runtime import (dispatch_guard, record_trace,
                                sanitizers_enabled)
from ..core.control import vectorized_step
from ..core.traces import GiB
from ..lab.score import (FleetStats, OVER_R0_EPS, SETTLE_TOL,
                         compute_fleet_stats, finalize_fleet_stats,
                         kahan_add, quantile_from_codes, utilization_codes)
from ..lab._compat import warn_once
from ..lab.sweep import (GainSet, _resolve_engine, _stager, resolve_devices,
                         sweep_mesh)
from .arbiter import MIN_TENANT_BUDGET, arbitrate, arbitrate_reference
from .specs import FleetSpec

Array = Union[np.ndarray, "jnp.ndarray"]

# Gains per compiled fleet chunk: the code history is the same
# chunk x T x N uint16 budget as the lab engine's, but the carry is K
# times wider, so default to a smaller chunk.
FLEET_CHUNK = 8


class FleetExtras(NamedTuple):
    """Arbitration invariants streamed out of the fleet scan.

    Each field is per gain point; slacks are worst-case over every
    (epoch, node) -- non-negative iff the invariant held at every
    arbitration the sweep performed.
    """

    conservation_slack_gib: Array    # (G,) min of M - sum_k B[k]
    floor_slack_gib: Array           # (G,) min of B[k] - effective floor
    tenant_budget_mean_gib: Array    # (G, K) mean budget per tenant
    tenant_budget_min_gib: Array     # (G, K) min budget per tenant


def _effective_floors(floors, m, xp):
    """Floors as granted: raised to the minimum budget, admissible."""
    f = xp.maximum(floors[:, None], MIN_TENANT_BUDGET)
    scale = xp.minimum(1.0, m / xp.maximum(f.sum(0), 1.0))
    return f * scale                                   # (K, N)


def _initial_budgets(weights, floors, m, xp):
    """Pre-telemetry budgets: floors + weight share of the remainder.

    Matches :meth:`~repro.fleet.arbiter.FleetArbiter.initial_budgets`
    broadcast over nodes.
    """
    f_eff = _effective_floors(floors, m, xp)
    rem = xp.maximum(m - f_eff.sum(0), 0.0)
    share = (weights / weights.sum())[:, None]
    return f_eff + share * rem                         # (K, N)


def _one_fleet_gain(demand, m, inv_m, w, fl, r0_g, lam_g, lam_grant_g,
                    u_min_g, u_max_g, db_g, ff_g, interval_s, *,
                    policy: str, priority_order: Tuple[int, ...],
                    axis_name: Optional[str] = None,
                    node_shards: int = 1):
    """The composed closed loop for one gain point, fully streamed.

    ``demand`` is ``(n_epochs, E, K, N)`` bytes (tenant compute demand,
    epoch-major); ``m`` the ``(N,)`` physical node memory; ``w``/``fl``
    the ``(K,)`` tenant weights and floors.  The carry holds per-tenant
    capacities and budgets plus the same O(N) stat accumulators the lab
    engine streams; the only scan output is the fleet-utilization code
    history for the quantile bisection.

    Epoch semantics mirror the live :class:`~repro.fleet.plane.FleetPlane`:
    epoch 0 runs under the weight-share initial budgets; at the top of
    epoch ``e >= 1`` the arbiter folds epoch ``e-1``'s mean usage into
    new budgets (``desired = usage / r0``, hit ratio 1 -- the saturated
    store misses nothing), shrunk tenants evict down to their grant
    immediately (``u = min(u, B)``), and every tenant then runs Eq. 1
    inside its grant for the epoch's ``E`` intervals.
    """
    n_epochs, ep_len, k, n_nodes = demand.shape
    f_eff = _effective_floors(fl, m, jnp)
    b0 = _initial_budgets(w, fl, m, jnp)
    inv_r0_g = 1.0 / r0_g
    thr_over = r0_g + OVER_R0_EPS
    thr_settle = r0_g + SETTLE_TOL
    inv_gib = jnp.float32(1.0 / GiB)
    inv_ep = jnp.float32(1.0 / ep_len)
    zeros = jnp.zeros((n_nodes,), jnp.float32)
    cnt_dtype = jnp.int16 if n_epochs * ep_len < 2**15 else jnp.int32
    izeros = jnp.zeros((n_nodes,), cnt_dtype)
    u0 = jnp.minimum(u_max_g, b0)

    def interval_step(carry, d):
        u, b, v_prev, usage, acc = carry
        (us, us_c, cs, cs_c, c2, mx, n_r0, n_viol, last_bad, t) = acc
        v = d + u                                      # saturated store
        # Feedforward applied to v up front (identical to the law's own
        # branch, which trace-time-resolves from a Python float a
        # vmapped gain axis cannot feed).
        v_eff = v + ff_g * (v - v_prev)
        u_max_eff = jnp.minimum(u_max_g, b)
        u_next = vectorized_step(
            u, v_eff, total_memory=b, r0=r0_g, lam=lam_g,
            u_min=jnp.minimum(u_min_g, u_max_eff), u_max=u_max_eff,
            lam_grant=lam_grant_g, deadband=db_g, inv_r0=inv_r0_g)
        r = v.sum(0) * inv_m                           # fleet-level (N,)
        us, us_c = kahan_add(us, us_c, r)
        cap_gib = u_next.sum(0) * inv_gib
        cs, cs_c = kahan_add(cs, cs_c, cap_gib)
        c2 = c2 + cap_gib * cap_gib
        mx = jnp.maximum(mx, r)
        n_r0 = n_r0 + (r > thr_over)
        n_viol = n_viol + (r > 1.0)
        last_bad = jnp.where(r > thr_settle, t, last_bad)
        acc = (us, us_c, cs, cs_c, c2, mx, n_r0, n_viol, last_bad, t + 1)
        return (u_next, b, v, usage + v, acc), utilization_codes(r)

    def epoch_step(carry, xs):
        e, d_ep = xs
        u, b, v_prev, usage, acc, ext = carry
        desired = usage * (inv_ep * inv_r0_g)
        b_new = arbitrate(desired, m, weights=w, floors=fl,
                          priority_order=priority_order, policy=policy,
                          rr_offset=e - 1)
        b = jnp.where(e > 0, b_new, b)
        # Shrunk tenants evict down to the new grant at the boundary --
        # the plane's apply_capacity semantics; grown tenants let the
        # law climb.
        u = jnp.minimum(u, b)
        (u, b, v_prev, usage, acc), codes = jax.lax.scan(
            interval_step, (u, b, v_prev, jnp.zeros_like(usage), acc),
            d_ep, unroll=2)
        cons_min, floor_min, b_sum, b_min = ext
        ext = (jnp.minimum(cons_min, (m - b.sum(0)).min()),
               jnp.minimum(floor_min, (b - f_eff).min()),
               b_sum + b.sum(1),
               jnp.minimum(b_min, b.min(1)))
        return (u, b, v_prev, usage, acc, ext), codes

    acc0 = (zeros, zeros, zeros, zeros, zeros, zeros, izeros, izeros,
            jnp.full((n_nodes,), -1, jnp.int32), jnp.int32(0))
    ext0 = (jnp.float32(jnp.inf), jnp.float32(jnp.inf),
            jnp.zeros((k,), jnp.float32), jnp.full((k,), jnp.inf,
                                                   jnp.float32))
    # Seed v_prev with the first interval's usage so the slope term is
    # exactly zero before there is a previous observation.
    v_prev0 = demand[0, 0] + u0
    usage0 = jnp.zeros((k, n_nodes), jnp.float32)
    carry, codes = jax.lax.scan(
        epoch_step, (u0, b0, v_prev0, usage0, acc0, ext0),
        (jnp.arange(n_epochs, dtype=jnp.int32), demand))
    _, _, _, _, acc, ext = carry
    (us, _, cs, _, c2, mx, n_r0, n_viol, last_bad, _) = acc
    n_global = n_nodes * node_shards
    n_steps = n_epochs * ep_len
    p99 = quantile_from_codes(codes, 0.99, n_steps * n_global,
                              axis_name=axis_name)
    stats = finalize_fleet_stats(
        util_sum=us, util_max=mx, caps_sum_gib=cs, caps_sumsq_gib=c2,
        over_r0_count=n_r0, violation_count=n_viol, last_bad=last_bad,
        p99_utilization=p99, r0=r0_g, n_intervals=n_steps,
        interval_s=interval_s, axis_name=axis_name, n_nodes=n_global)
    cons_min, floor_min, b_sum, b_min = ext
    if axis_name is not None:
        cons_min = jax.lax.pmin(cons_min, axis_name)
        floor_min = jax.lax.pmin(floor_min, axis_name)
        b_sum = jax.lax.psum(b_sum, axis_name)
        b_min = jax.lax.pmin(b_min, axis_name)
    extras = FleetExtras(
        conservation_slack_gib=cons_min * inv_gib,
        floor_slack_gib=floor_min * inv_gib,
        tenant_budget_mean_gib=b_sum * inv_gib / (n_epochs * n_global),
        tenant_budget_min_gib=b_min * inv_gib)
    return stats, extras


def _fleet_chunk_stats(demand, m, w, fl, r0, lam, lam_grant, u_min, u_max,
                       deadband, feedforward, interval_s, *, policy: str,
                       priority_order: Tuple[int, ...], spec: str = "",
                       axis_name: Optional[str] = None,
                       node_shards: int = 1):
    """One gain chunk of the fleet sweep: vmap over the gain arrays."""
    record_trace("fleet.sweep.chunk", chunk=int(r0.shape[0]),
                 epochs=int(demand.shape[0]),
                 ep_len=int(demand.shape[1]),
                 tenants=int(demand.shape[2]),
                 nodes=int(demand.shape[3]), policy=policy, spec=spec)
    demand = jnp.asarray(demand, jnp.float32)
    m = jnp.asarray(m, jnp.float32)
    inv_m = 1.0 / m
    w = jnp.asarray(w, jnp.float32)
    fl = jnp.asarray(fl, jnp.float32)

    def one_gain(r0_g, lam_g, lam_grant_g, u_min_g, u_max_g, db_g, ff_g):
        return _one_fleet_gain(demand, m, inv_m, w, fl, r0_g, lam_g,
                               lam_grant_g, u_min_g, u_max_g, db_g, ff_g,
                               interval_s, policy=policy,
                               priority_order=priority_order,
                               axis_name=axis_name, node_shards=node_shards)

    return jax.vmap(one_gain)(
        jnp.asarray(r0, jnp.float32), jnp.asarray(lam, jnp.float32),
        jnp.asarray(lam_grant, jnp.float32),
        jnp.asarray(u_min, jnp.float32), jnp.asarray(u_max, jnp.float32),
        jnp.asarray(deadband, jnp.float32),
        jnp.asarray(feedforward, jnp.float32))


@functools.lru_cache(maxsize=None)
def _compiled_fleet_sweep(devices: Tuple, policy: str,
                          priority_order: Tuple[int, ...],
                          node_shards: int = 1):
    """Jitted fleet-chunk program for a device tuple (see lab engine).

    Same mesh layouts as ``repro.lab.sweep._compiled_sweep``: one
    device -> plain jit (the bit-exact reference placement);
    ``node_shards == 1`` -> 1-D ``("gains",)`` mesh with demand and
    node memory replicated; otherwise the 2-D ``("gains", "nodes")``
    mesh with the node axis of demand / memory split and the stat folds
    running collectives.
    """
    spec = repr((tuple(str(d) for d in devices), policy, priority_order,
                 node_shards))
    fn = functools.partial(_fleet_chunk_stats, policy=policy,
                           priority_order=priority_order, spec=spec,
                           axis_name="nodes" if node_shards > 1 else None,
                           node_shards=node_shards)
    if len(devices) <= 1:
        return jax.jit(fn)
    mapped = jax.shard_map(fn, mesh=sweep_mesh(devices, node_shards),
                           in_specs=(_fleet_specs(node_shards)
                                     + (P("gains"),) * 7 + (P(),)),
                           out_specs=P("gains"), check_vma=False)
    return jax.jit(mapped)


def _fleet_specs(node_shards: int) -> Tuple:
    """Partition specs of demand, node memory, weights and floors."""
    if node_shards == 1:
        return (P(None, None, None, None), P(None), P(None), P(None))
    return (P(None, None, None, "nodes"), P("nodes"), P(None), P(None))


def fleet_sweep_demand(
    demand: np.ndarray,
    gains: GainSet,
    *,
    node_memory: Union[float, np.ndarray],
    weights: np.ndarray,
    floors: np.ndarray,
    policy: str = "proportional",
    priority_order: Optional[Tuple[int, ...]] = None,
    epoch_intervals: int = 50,
    interval_s: float = 0.1,
    chunk: Optional[int] = None,
    devices: Union[None, int, Sequence] = None,
    node_shards: int = 1,
    horizon: Optional[int] = None,
    engine: str = "xla",
) -> Tuple[FleetStats, FleetExtras]:
    """Sweep a ``(K, N, T)`` per-tenant demand tensor over every gain.

    The fleet analogue of :func:`repro.lab.sweep.sweep_demand`:
    ``demand[k, n, t]`` is tenant ``k``'s compute demand on node ``n``
    at interval ``t`` (bytes), ``T`` must divide into
    ``epoch_intervals``-sized arbitration epochs, and every gain point
    runs the full two-level loop.  Returns ``(G,)``-field
    :class:`~repro.lab.score.FleetStats` over the *fleet-level* closed
    loop plus :class:`FleetExtras` with the arbitration invariants.

    The unified sweep kwargs apply here too: ``horizon`` truncates to
    the first ``horizon`` intervals (still a whole number of epochs),
    and ``engine`` is accepted for API uniformity -- the fleet carry is
    not kernelized yet, so ``engine="pallas"`` falls back to the XLA
    path with a one-time warning.

    Sharding matches the lab engine: gains across devices, optionally
    nodes too (``node_shards``), single device bit-exact.
    """
    if _resolve_engine(engine, "fleet_sweep_demand") == "pallas":
        warn_once("fleet_sweep_demand:pallas",
                  "fleet_sweep_demand(engine='pallas'): the two-level "
                  "fleet carry is not kernelized yet; falling back to "
                  "the XLA engine", RuntimeWarning)
    demand = np.asarray(demand)
    if demand.ndim != 3:
        raise ValueError("demand must be (tenants, nodes, intervals)")
    if horizon is not None:
        if not 1 <= horizon <= demand.shape[2]:
            raise ValueError(f"horizon must be in [1, {demand.shape[2]}]")
        demand = demand[:, :, :horizon]
    k, n_nodes, n_steps = demand.shape
    if epoch_intervals < 1 or n_steps % epoch_intervals:
        raise ValueError(
            f"n_intervals ({n_steps}) must divide into whole epochs of "
            f"{epoch_intervals}")
    weights = np.asarray(weights, np.float64)
    floors = np.asarray(floors, np.float64)
    if weights.shape != (k,) or floors.shape != (k,):
        raise ValueError("weights and floors must be (tenants,)")
    if priority_order is None:
        priority_order = tuple(range(k))
    if sorted(priority_order) != list(range(k)):
        raise ValueError("priority_order must be a permutation of tenants")
    if node_shards < 1:
        raise ValueError("node_shards must be >= 1")
    n_epochs = n_steps // epoch_intervals
    # epoch-major (n_epochs, E, K, N): the outer scan's xs
    demand_e = np.ascontiguousarray(
        demand.transpose(2, 0, 1).reshape(n_epochs, epoch_intervals, k,
                                          n_nodes), dtype=np.float32)
    m = np.broadcast_to(np.asarray(node_memory, np.float64),
                        (n_nodes,)).astype(np.float32)
    devs = resolve_devices(devices)
    if len(devs) <= 1:
        node_shards = 1
    else:
        if len(devs) % node_shards:
            raise ValueError(f"devices ({len(devs)}) must divide evenly "
                             f"into node_shards={node_shards}")
        if n_nodes % node_shards:
            raise ValueError(f"n_nodes ({n_nodes}) must be divisible by "
                             f"node_shards={node_shards}")
    gain_shards = len(devs) // node_shards
    chunk = min(FLEET_CHUNK if chunk is None else max(int(chunk), 1),
                max(len(gains), 1))
    chunk = -(-chunk // gain_shards) * gain_shards
    n_real = len(gains)
    if n_real % chunk:
        pad = GainSet(*(np.repeat(getattr(gains, f.name)[-1:],
                                  chunk - n_real % chunk)
                        for f in dataclasses.fields(GainSet)))
        gains = gains.concat(pad)
    fn = _compiled_fleet_sweep(devs, policy, tuple(priority_order),
                               node_shards)
    stage = _stager(devs, node_shards)
    lead_p = _fleet_specs(node_shards)
    demand_dev = stage(demand_e, lead_p[0])
    m_dev = stage(m, lead_p[1])
    w_dev = stage(weights.astype(np.float32), lead_p[2])
    fl_dev = stage(floors.astype(np.float32), lead_p[3])
    gain_cols = [np.asarray(getattr(gains, f.name), np.float32)
                 for f in dataclasses.fields(GainSet)]
    iv = stage(np.float32(interval_s), P())
    cols_per_chunk = [[stage(a[lo:lo + chunk], P("gains")) for a in gain_cols]
                      for lo in range(0, len(gains), chunk)]
    if sanitizers_enabled():
        jax.block_until_ready(fn(
            demand_dev, m_dev, w_dev, fl_dev, *cols_per_chunk[0], iv))
    pending = []
    with dispatch_guard():
        for cols in cols_per_chunk:
            pending.append(fn(demand_dev, m_dev, w_dev, fl_dev, *cols, iv))
    chunks = [jax.tree_util.tree_map(np.asarray, pair) for pair in pending]
    stats = FleetStats(*(
        np.concatenate([getattr(st, f) for st, _ in chunks])[:n_real]
        for f in FleetStats._fields))
    extras = FleetExtras(*(
        np.concatenate([getattr(ex, f) for _, ex in chunks])[:n_real]
        for f in FleetExtras._fields))
    return stats, extras


# ---------------------------------------------------------------------------
# The float64 reference (parity oracle)
# ---------------------------------------------------------------------------

def fleet_reference(
    demand: np.ndarray,
    gains: GainSet,
    *,
    node_memory: Union[float, np.ndarray],
    weights: np.ndarray,
    floors: np.ndarray,
    policy: str = "proportional",
    priority_order: Optional[Tuple[int, ...]] = None,
    epoch_intervals: int = 50,
    interval_s: float = 0.1,
) -> Tuple[FleetStats, FleetExtras]:
    """Scalar float64 oracle for :func:`fleet_sweep_demand`.

    Dense numpy per-gain loops, arbitration via
    :func:`~repro.fleet.arbiter.arbitrate_reference` -- readable,
    exact, slow.  Stats come from
    :func:`~repro.lab.score.compute_fleet_stats` on the materialized
    fleet history, so the only expected divergence from the fused path
    is float32 accumulation and the streaming quantile's quantization.
    """
    demand = np.asarray(demand, np.float64)
    k, n_nodes, n_steps = demand.shape
    if priority_order is None:
        priority_order = tuple(range(k))
    weights = np.asarray(weights, np.float64)
    floors = np.asarray(floors, np.float64)
    m = np.broadcast_to(np.asarray(node_memory, np.float64), (n_nodes,))
    n_epochs = n_steps // epoch_intervals
    f_eff = _effective_floors(floors, m, np)
    stats_rows = []
    extras_rows = []
    for g in range(len(gains)):
        r0 = float(gains.r0[g])
        lam = float(gains.lam[g])
        lam_grant = float(gains.lam_grant[g])
        u_min = float(gains.u_min[g])
        u_max = float(gains.u_max[g])
        db = float(gains.deadband[g])
        ff = float(gains.feedforward[g])
        b = _initial_budgets(weights, floors, m, np)
        u = np.minimum(u_max, b)
        v_prev = demand[:, :, 0] + u
        utils = np.empty((n_steps, n_nodes))
        caps = np.empty((n_steps, n_nodes))
        cons_min = np.inf
        floor_min = np.inf
        b_sum = np.zeros(k)
        b_min = np.full(k, np.inf)
        for e in range(n_epochs):
            if e > 0:
                lo = (e - 1) * epoch_intervals
                usage = (demand[:, :, lo:lo + epoch_intervals]
                         + u_hist[..., :]).mean(-1)
                b = arbitrate_reference(
                    usage / r0, m, weights=weights, floors=floors,
                    priority_order=priority_order, policy=policy,
                    rr_offset=(e - 1) % k)
                u = np.minimum(u, b)
            cons_min = min(cons_min, float((m - b.sum(0)).min()))
            floor_min = min(floor_min, float((b - f_eff).min()))
            b_sum += b.sum(1)
            b_min = np.minimum(b_min, b.min(1))
            u_hist = np.empty((k, n_nodes, epoch_intervals))
            for j in range(epoch_intervals):
                t = e * epoch_intervals + j
                d = demand[:, :, t]
                v = d + u
                v_eff = v + ff * (v - v_prev)
                r_t = v_eff / b
                err = r_t - r0
                lam_eff = np.where(err < 0, lam_grant, lam)
                u_max_eff = np.minimum(u_max, b)
                u_min_eff = np.minimum(u_min, u_max_eff)
                u_next = np.where(np.abs(err) <= db, u,
                                  u - lam_eff * v_eff * err / r0)
                u_next = np.clip(u_next, u_min_eff, u_max_eff)
                u_hist[:, :, j] = u
                utils[t] = v.sum(0) / m
                caps[t] = u_next.sum(0)
                v_prev = v
                u = u_next
        stats_rows.append(jax.tree_util.tree_map(
            np.asarray, compute_fleet_stats(utils, caps, r0=r0,
                                            interval_s=interval_s)))
        extras_rows.append(FleetExtras(
            conservation_slack_gib=cons_min / GiB,
            floor_slack_gib=floor_min / GiB,
            tenant_budget_mean_gib=b_sum / GiB / (n_epochs * n_nodes),
            tenant_budget_min_gib=b_min / GiB))
    stats = FleetStats(*(np.stack([getattr(s, f) for s in stats_rows])
                         for f in FleetStats._fields))
    extras = FleetExtras(*(np.stack([np.asarray(getattr(x, f))
                                     for x in extras_rows])
                           for f in FleetExtras._fields))
    return stats, extras


def run_fleet_sweep(scenario, gains: GainSet, *, seed: int = 0,
                    chunk: Optional[int] = None,
                    devices: Union[None, int, Sequence] = None,
                    node_shards: int = 1, horizon: Optional[int] = None,
                    engine: str = "xla") -> Tuple[FleetStats, FleetExtras]:
    """Sweep a registered (or inline) :class:`FleetScenario`.

    Resolves the scenario's per-tenant demand tensor and arbitration
    shape and hands them to :func:`fleet_sweep_demand`; ``horizon`` /
    ``engine`` pass through (the unified sweep kwarg set).
    """
    from .scenario import get_fleet_scenario
    fs = get_fleet_scenario(scenario)
    demand = fs.build_demand(seed=seed)
    return fleet_sweep_demand(
        demand, gains, node_memory=fs.node_memory_gib * GiB,
        weights=fs.weights(), floors=fs.floors_bytes(),
        policy=fs.policy, priority_order=fs.priority_order(),
        epoch_intervals=fs.epoch_intervals, interval_s=fs.interval_s,
        chunk=chunk, devices=devices, node_shards=node_shards,
        horizon=horizon, engine=engine)
