"""The closed-loop step, written once: law, cache, queue and accumulator.

Every sweep engine advances the same closed loop one control interval
at a time.  This module holds it as the carry groups
``docs/ARCHITECTURE.md`` draws, each with its init, its update inside
the step and its finalize:

* **law** (``u``, ``v_prev``): the usage the controller observes, the
  slope feedforward, and :func:`~repro.core.control.vectorized_step`;
* **cache** (``resident``, the ``hs``/``es``/``ts`` Kahan pairs):
  CacheLoop, :func:`cache_update`;
* **queue** (``sidx``, ``wleft``, ``wd``, ``wd_c``, ``t_done``): the
  AppGraph stage queues, :func:`queue_update`;
* **acc** (``us`` ... ``last_bad``): the streamed
  :class:`~repro.lab.score.FleetStats` accumulators, and
  :func:`finalize`, which folds them with the cache sums and the
  queue's makespan into stats.

The engines are lowerings of :func:`step`, each in its own layout: the
XLA engine (:mod:`.sweep`) over one gain point's ``(N,)`` node lanes,
``vmap``'d over gains; the Mosaic kernel (:mod:`.pallas_sweep`) over
``(L, N)`` planes of a stacked ``(S, L, N)`` block; the fleet sweep
(:mod:`repro.fleet.sweep`) runs its own tenant law, then the acc group.
Gain parameters are scalars (XLA, inside ``vmap``) or ``(L, 1)`` columns
(the kernel), node rows ``(N,)``; both arrive as :class:`Rows`, read
where the step reads them, so every lowering emits the same ops in the
same order.  The XLA engine traces ``interval_s`` and ``occupancy``;
the kernel bakes them in (:meth:`Loop.bake`).  Counters keep their
carry's dtype: int lanes and a carried clock ``t`` (XLA, fleet), f32
planes stamped with the loop index (the kernel).

``paper_law``, ``unit_occupancy``, ``cache`` and ``queue`` are
trace-time specializations: with every gain point paper-faithful
(symmetric gains, no deadband, no feedforward) the slope state, the
gain select and the hold branch drop out of the step, and
``cache=None`` / ``queue=None`` compile the loop without them.  Every
path gives identical results for the parameters it admits.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..core.control import vectorized_step
from ..core.eviction import policy_model
from ..core.traces import GiB
from .appgraph import AppGraphSpec, compile_graph
from .scenarios import CacheSpec
from .score import (FleetStats, OVER_R0_EPS, SETTLE_TOL, _axis_min,
                    _axis_sum, finalize_fleet_stats, hpl_slowdown_curve,
                    kahan_add, quantile_from_codes, utilization_codes)

INV_GIB = float(np.float32(1.0 / GiB))

# Carry groups, by state name.  The paper law carries only ``u``.
LAW = ("u", "v_prev")
CACHE = ("resident", "hs", "hs_c", "es", "es_c", "ts", "ts_c")
QUEUE = ("sidx", "wleft", "wd", "wd_c", "t_done")
ACC = ("us", "us_c", "cs", "cs_c", "c2", "mx", "n_r0", "n_viol",
       "last_bad")

# Per-gain parameters (the law's knobs, then the derived reciprocal
# and the two count thresholds), and per-node rows.
GAIN_ROWS = ("r0", "lam", "lam_grant", "u_min", "u_max", "deadband",
             "feedforward", "inv_r0", "thr_over", "thr_settle")
NODE_ROWS = ("m", "inv_m", "w", "inv_w")


class Rows:
    """Named rows of a tuple (no op to read) or a packed array (one
    gather per read, at its point of use): ``rows.name`` is
    ``rows["name"]``."""

    __slots__ = ("_block", "_names")

    def __init__(self, block, names: Sequence[str]):
        self._block = block
        self._names = tuple(names)

    def __getitem__(self, name: str):
        return self._block[self._names.index(name)]

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        return self[name]


def thresholds(r0):
    """``r0 + OVER_R0_EPS`` and ``r0 + SETTLE_TOL``, the two count
    thresholds, in f32 (host f32 arrays or traced scalars alike)."""
    return r0 + OVER_R0_EPS, r0 + SETTLE_TOL


def gain_rows(r0, lam, lam_grant, u_min, u_max, deadband,
              feedforward) -> Rows:
    """One gain point's :data:`GAIN_ROWS`, the derived rows traced."""
    return Rows((r0, lam, lam_grant, u_min, u_max, deadband, feedforward,
                 1.0 / r0, *thresholds(r0)), GAIN_ROWS)


def count_dtype(n_steps: int):
    """Int lanes for the event counters: int16 (twice the SIMD width)
    whenever the horizon cannot overflow them."""
    return jnp.int16 if n_steps < 2**15 else jnp.int32


def law_names(paper_law: bool) -> tuple:
    return LAW[:1] if paper_law else LAW


def carry_names(paper_law: bool, has_cache: bool, has_queue: bool) -> tuple:
    """State order of the XLA engine's scan carry: the groups, then the
    clock ``t``."""
    return (law_names(paper_law) + (CACHE if has_cache else ())
            + (QUEUE if has_queue else ()) + ACC + ("t",))


def plane_names(paper_law: bool, has_cache: bool) -> tuple:
    """Plane order of the kernel's stacked ``(S, L, N)`` state block:
    the law, the resident set, the accumulators, the cache's sums."""
    if not has_cache:
        return law_names(paper_law) + ACC
    return law_names(paper_law) + CACHE[:1] + ACC + CACHE[1:]


# ---------------------------------------------------------------------------
# Trace-time constants
# ---------------------------------------------------------------------------

class CacheConsts(NamedTuple):
    """The cache model's scalars, in f32: see :func:`cache_consts`."""

    conc: float          # hit-curve concentration of the eviction policy
    hit_exp: float       # 1 - reuse_skew
    miss_pen: float      # s of app time per GiB missed
    evict_pen: float     # s of app time per GiB evicted
    access_g: float      # GiB read per interval
    refill_b: float      # bytes refillable per interval
    access_b: float      # bytes read per interval
    cold_mix: float      # reuse_skew: cold-scan to steady-state blend
    warm_frac: float     # resident share of the first grant


def cache_consts(cache: CacheSpec, interval_s, f32) -> CacheConsts:
    """A :class:`CacheSpec` and the interval, as the step's constants.

    The XLA engine passes its traced interval and ``f32=jnp.float32``
    (device scalars, which its scan closes over); the kernel a host
    ``np.float32`` interval and ``f32=np.float32`` (see
    :meth:`Loop.bake`).  Either way the products are the same f32 ops.
    """
    access_g = f32(cache.access_gibps) * interval_s
    return CacheConsts(
        conc=float(policy_model(cache.policy).concentration),
        hit_exp=1.0 - float(cache.reuse_skew),
        miss_pen=f32(cache.miss_penalty_s_per_gib),
        evict_pen=f32(cache.evict_penalty_s_per_gib),
        access_g=access_g,
        refill_b=f32(cache.refill_gibps * GiB) * interval_s,
        access_b=access_g * f32(GiB),
        cold_mix=f32(cache.reuse_skew),
        warm_frac=f32(cache.warm_frac))


def working_set(cache: CacheSpec, m, f32):
    """Per-node working-set bytes: the ``w`` node row."""
    return f32(cache.working_set_frac) * m


class QueueConsts(NamedTuple):
    """The AppGraph queue's constants: see :func:`queue_consts`."""

    n_rows: int          # stage rows; sidx == n_rows means drained
    demand_b: object     # (S+1,) bytes an active stage holds
    barrier: object      # (S+1,) barrier flags
    comp_itv: object     # GiB of nominal work per interval
    work_sn: object      # (S+1, N) per-node work, GiB (traced)
    axis_name: Optional[str]


def queue_consts(app_graph: AppGraphSpec, interval_s, work_sn,
                 axis_name: Optional[str]) -> QueueConsts:
    """Node-independent graph constants bake in from the frozen spec
    (stage-held demand, barrier flags); only the per-node work matrix
    is traced, because it depends on *global* node indices, which a
    node shard cannot rebuild.  ``slow_nodes`` is a work-matrix concern,
    stripped so the 1-node compile passes range validation."""
    cg = compile_graph(app_graph.replace(slow_nodes=()), 1)
    return QueueConsts(
        n_rows=cg.n_rows, demand_b=jnp.asarray(cg.demand_bytes),
        barrier=jnp.asarray(cg.barrier),
        comp_itv=jnp.float32(app_graph.compute_gibps) * interval_s,
        work_sn=work_sn, axis_name=axis_name)


class Loop(NamedTuple):
    """What one executable specializes the step on.

    ``inv_gib`` is the bytes-to-GiB factor as the lowering holds its
    constants: a device scalar in the XLA engine (its scan closes over
    it), a Python float folded into the kernel's body.
    """

    paper_law: bool
    unit_occupancy: bool
    occupancy: object            # traced (XLA engine) or float (kernel)
    interval_s: object           # traced (XLA engine) or float (kernel)
    cache: Optional[CacheConsts] = None
    queue: Optional[QueueConsts] = None
    inv_gib: object = INV_GIB

    @classmethod
    def bake(cls, plan, cache: Optional[CacheSpec], interval_s: float,
             occupancy: float) -> "Loop":
        """The kernel's loop: interval and occupancy compiled in, every
        constant a Python float (hashable: it keys the compiled-program
        caches, and repr-stable: it feeds the recompile counter)."""
        iv = np.float32(interval_s)
        cc = None if cache is None else CacheConsts(
            *(float(c) for c in cache_consts(cache, iv, np.float32)))
        return cls(plan.paper_law, plan.unit_occupancy, float(occupancy),
                   float(iv), cc)


# ---------------------------------------------------------------------------
# law
# ---------------------------------------------------------------------------

def observe(s, d, loop: Loop):
    """The usage the controller observes: with a cache, what the store
    holds (a freshly granted GiB is empty until refilled); without, the
    saturated store's whole grant, scaled by occupancy."""
    if loop.cache is not None:
        return d + s["resident"]
    if loop.unit_occupancy:
        return d + s["u"]
    return d + loop.occupancy * s["u"]


def law_update(s, v, g: Rows, nodes: Rows, loop: Loop):
    """Eq. 1 on the observed usage ``v``: the next grant.  The slope
    feedforward applies to ``v`` up front (``vectorized_step``'s own
    branch needs a Python float, which a gain axis cannot feed)."""
    if loop.paper_law:
        v_eff = v
    else:
        v_eff = v + g.feedforward * (v - s["v_prev"])
    return vectorized_step(
        s["u"], v_eff, total_memory=nodes.m, r0=g.r0, lam=g.lam,
        u_min=g.u_min, u_max=g.u_max,
        lam_grant=None if loop.paper_law else g.lam_grant,
        deadband=0.0 if loop.paper_law else g.deadband,
        inv_total_memory=nodes.inv_m, inv_r0=g.inv_r0)


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def warm_seed(c: CacheConsts, u_top, nodes: Rows):
    """The warm-seeded resident set and its working-set fraction ``wf0``
    (the only blocks a strictly cyclic first pass can hit): the
    resident set starts at ``warm_frac`` of the first grant ``u_top``."""
    res0 = c.warm_frac * jnp.minimum(u_top, nodes.w)
    return res0, res0 * nodes.inv_w


def cache_update(s, r, u_next, stamp, nodes: Rows, loop: Loop, wf0):
    """CacheLoop: evict, hit curve, cold scan, read-through refill, stall.

    Actuation evicts down to the shrunk grant within the interval (the
    paper's "free space" RPC semantics); min/max forms keep the
    arithmetic exact when nothing changes.  The analytic hit curve
    ``h(f) = c * f**(1-alpha) + (1-c) * f`` (see
    :class:`~repro.core.eviction.PolicyModel`) turns the resident
    fraction of the working set into a hit ratio.  The first pass over
    the working set is warmup-aware: until a node has scanned its
    working set once (the compulsory-miss window), blocks refilled
    *within* the pass are not re-referenced by a cyclic scan, so at
    ``reuse_skew`` 0 only the warm-seeded prefix can hit; as the skew
    grows, intra-pass re-reference of hot blocks revives the
    steady-state curve.  ``reuse_skew`` interpolates between the two
    regimes, and the warm prefix is clamped by the live resident
    fraction (eviction shrinks it too) -- parity-pinned against the
    discrete-event simulator's cold start.  Only missed bytes refill
    the grant, capped by admission bandwidth, the grant itself and the
    working set.  Misses, eviction churn and the Fig.-2 pressure curve
    accumulate into the interval's app time ``dt_app``, returned beside
    the updated group for the queue to read.  The hit curve stays
    ``**``: on a TPU v5e an ``exp2(e * log2(f))`` spelling, or even a
    5e-7-accurate polynomial, drifts the 4096-node closed loop by up to
    2e-2 in the violation rate, while ``**`` lowers to the same pow in
    Mosaic and XLA.
    """
    c = loop.cache
    resident = s["resident"]
    res_ev = jnp.minimum(resident, u_next)
    ev_g = (resident - res_ev) * loop.inv_gib
    f = jnp.minimum(res_ev * nodes.inv_w, 1.0)
    hit = c.conc * f ** c.hit_exp + (1.0 - c.conc) * f
    scanned = stamp.astype(jnp.float32) * c.access_b
    wf = jnp.minimum(wf0, f)
    hit = jnp.where(scanned < nodes.w, wf + c.cold_mix * (hit - wf), hit)
    miss_g = (1.0 - hit) * c.access_g
    target = jnp.minimum(u_next, nodes.w)
    resident = jnp.minimum(
        target, res_ev + jnp.minimum(miss_g * float(GiB), c.refill_b))
    dt_app = (loop.interval_s * hpl_slowdown_curve(r)
              + miss_g * c.miss_pen + ev_g * c.evict_pen)
    hs, hs_c = kahan_add(s["hs"], s["hs_c"], hit * c.access_g)
    es, es_c = kahan_add(s["es"], s["es_c"], ev_g)
    ts, ts_c = kahan_add(s["ts"], s["ts_c"], dt_app)
    return dict(resident=resident, hs=hs, hs_c=hs_c, es=es, es_c=es_c,
                ts=ts, ts_c=ts_c), dt_app


# ---------------------------------------------------------------------------
# queue
# ---------------------------------------------------------------------------

def queue_init(q: QueueConsts, zeros) -> Dict[str, object]:
    """Every node at stage row 0 with its row-0 work; not finished."""
    return dict(sidx=jnp.zeros(zeros.shape, jnp.int32), wleft=q.work_sn[0],
                wd=zeros, wd_c=zeros, t_done=jnp.float32(-1.0))


def queue_observe(q: QueueConsts, d, sidx):
    """An active stage holds its declared shuffle/scratch bytes: the
    controller observes demand *including* them, so stage entry and
    exit feed back into the pressure the law reacts to."""
    return d + q.demand_b[sidx]


def queue_update(s, r, dt_app, t, loop: Loop) -> Dict[str, object]:
    """Advance the stage queues one interval.

    The interval's wall clock stretches to ``dt_eff`` under pressure
    (and cache stalls), so the app makes ``interval_s / dt_eff`` of its
    nominal progress.  A two-level progress code (``2*row``, ``+1`` once
    the row's work is drained) gates barrier rows: one promotes only
    when the *fleet* min of the code says every node finished it
    (limplock: one slow node holds every node's code down).  Under the
    2-D mesh the barrier and completion folds are ``pmin`` collectives
    over ``axis_name`` -- two scalar reductions per step.
    """
    q = loop.queue
    sidx, wleft, wd, wd_c, t_done = (s[n] for n in QUEUE)
    dt_eff = dt_app if loop.cache is not None \
        else loop.interval_s * hpl_slowdown_curve(r)
    active = sidx < q.n_rows
    adv = jnp.where(active, q.comp_itv * (loop.interval_s / dt_eff), 0.0)
    wd, wd_c = kahan_add(wd, wd_c, jnp.minimum(adv, wleft))
    wleft = jnp.maximum(wleft - adv, 0.0)
    fin = active & (wleft <= 0.0)
    lvl = sidx * 2 + fin.astype(jnp.int32)
    fleet_lvl = _axis_min(jnp.min(lvl), q.axis_name)
    can = fin & ((q.barrier[sidx] == 0.0) | (fleet_lvl >= sidx * 2 + 1))
    sidx = sidx + can.astype(jnp.int32)
    wleft = jnp.where(
        can, jnp.take_along_axis(q.work_sn, sidx[None, :], axis=0)[0],
        wleft)
    done_all = _axis_min(jnp.min(sidx), q.axis_name) >= q.n_rows
    t_done = jnp.where((t_done < 0.0) & done_all,
                       (t + 1).astype(jnp.float32), t_done)
    return dict(sidx=sidx, wleft=wleft, wd=wd, wd_c=wd_c, t_done=t_done)


def queue_makespan(s, n_steps: int, interval_s, q: QueueConsts):
    """Finished: the recorded interval count.  Unfinished: the
    work-linear extrapolation (clamped to at least the horizon), so
    truncated runs still order by real progress."""
    total_w = _axis_sum(jnp.sum(q.work_sn), q.axis_name)
    done_w = _axis_sum(s["wd"], q.axis_name)
    horizon_s = jnp.float32(n_steps) * interval_s
    return jnp.where(
        s["t_done"] >= 0.0, s["t_done"] * interval_s,
        jnp.maximum(horizon_s * total_w / jnp.maximum(done_w, 1e-6),
                    horizon_s))


# ---------------------------------------------------------------------------
# acc
# ---------------------------------------------------------------------------

def acc_init(zeros, counts, *, carried_clock: bool) -> Dict[str, object]:
    """Zeroed accumulators; ``last_bad`` starts at -1.

    ``carried_clock``: the XLA engine and the fleet sweep carry the
    interval clock ``t`` and stamp ``last_bad`` in int32; the kernel
    stamps its f32 plane with the loop index.
    """
    s = dict.fromkeys(ACC[:6], zeros)
    s.update(n_r0=counts, n_viol=counts)
    if carried_clock:
        s.update(last_bad=jnp.full(zeros.shape, -1, jnp.int32),
                 t=jnp.int32(0))
    else:
        s["last_bad"] = zeros - 1.0
    return s


def acc_update(s, r, grant, stamp, g: Rows, inv_gib, *,
               tenant_axis: Optional[int] = None) -> Dict[str, object]:
    """Fold one interval's utilization ``r`` and ``grant`` (bytes) into
    the accumulators; ``stamp`` is the interval index in ``last_bad``'s
    dtype, ``inv_gib`` as in :class:`Loop`.  The fleet sweep's grant is
    ``(K, N)``: ``tenant_axis`` sums it over tenants first."""
    us, us_c = kahan_add(s["us"], s["us_c"], r)
    cap = grant if tenant_axis is None else grant.sum(tenant_axis)
    cap_gib = cap * inv_gib
    cs, cs_c = kahan_add(s["cs"], s["cs_c"], cap_gib)
    out = dict(us=us, us_c=us_c, cs=cs, cs_c=cs_c,
               c2=s["c2"] + cap_gib * cap_gib,
               mx=jnp.maximum(s["mx"], r),
               n_r0=s["n_r0"] + (r > g.thr_over),
               n_viol=s["n_viol"] + (r > 1.0),
               last_bad=jnp.where(r > g.thr_settle, stamp, s["last_bad"]))
    if "t" in s:
        out["t"] = s["t"] + 1
    return out


def finalize(s, codes, r0, n_steps: int, n_nodes: int, interval_s, *,
             cache: Optional[CacheConsts] = None,
             queue: Optional[QueueConsts] = None,
             axis_name: Optional[str] = None) -> FleetStats:
    """:class:`FleetStats` from the accumulators, the cache sums and
    the queue: the p99 bisected out of the ``codes`` history.
    ``n_nodes`` is the global fleet (``axis_name``: the node axis is
    sharded and the folds are collectives)."""
    p99 = quantile_from_codes(codes, 0.99, n_steps * n_nodes,
                              axis_name=axis_name)
    kw = {}
    if cache is not None:
        kw = dict(hits_gib=s["hs"], evicted_gib=s["es"],
                  app_time_s=s["ts"], accesses_gib=cache.access_g * n_steps)
    if queue is not None:
        kw["makespan_s"] = queue_makespan(s, n_steps, interval_s, queue)
    return finalize_fleet_stats(
        util_sum=s["us"], util_max=s["mx"], caps_sum_gib=s["cs"],
        caps_sumsq_gib=s["c2"], over_r0_count=s["n_r0"],
        violation_count=s["n_viol"], last_bad=s["last_bad"],
        p99_utilization=p99, r0=r0, n_intervals=n_steps,
        interval_s=interval_s, axis_name=axis_name, n_nodes=n_nodes, **kw)


# ---------------------------------------------------------------------------
# The step
# ---------------------------------------------------------------------------

def step(s, d, t, g: Rows, nodes: Rows, loop: Loop, wf0=None):
    """One closed-loop interval: ``s`` (state by name) -> (next state,
    the interval's uint16 utilization codes).

    Order is the contract the f64 oracles replay: the queue's held
    demand joins the trace, the controller observes the *pre-update*
    store, the law grants, the accumulators fold the observation, the
    cache evicts to the *new* grant within the same interval, and the
    queue advances at the rate that observation implies.  ``t`` is the
    interval index (int); ``wf0`` the cache's warm fraction
    (:func:`warm_seed`).
    """
    q = loop.queue
    if q is not None:
        d = queue_observe(q, d, s["sidx"])
    v = observe(s, d, loop)
    u_next = law_update(s, v, g, nodes, loop)
    r = v * nodes.inv_m
    stamp = t.astype(s["last_bad"].dtype)
    out = acc_update(s, r, u_next, stamp, g, loop.inv_gib)
    dt_app = None
    if loop.cache is not None:
        res, dt_app = cache_update(s, r, u_next, stamp, nodes, loop, wf0)
        out.update(res)
    if q is not None:
        out.update(queue_update(s, r, dt_app, t, loop))
    out["u"] = u_next
    if not loop.paper_law:
        out["v_prev"] = v
    return out, utilization_codes(r)
