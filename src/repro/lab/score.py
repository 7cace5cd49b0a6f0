"""Scoring: fleet-stability metrics and gain objectives (Figs. 5-8 analogues).

Pure functions of sweep output.  :func:`compute_fleet_stats` reduces a
closed-loop utilization/capacity history to the paper-evaluation
metrics -- pressure-violation rate, time over ``r0``, mean/p99
utilization, granted-capacity volume, settle time -- and is written in
``jax.numpy`` so the sweep engine can fuse it into the jitted scan
(it accepts plain numpy arrays equally, which is how the legacy
Python-loop fleet sim and the tests call it).

The device-resident sweep (``lab.sweep``) never materializes a history:
it streams per-node accumulators through the scan (Kahan-compensated
float32 sums -- the f32-clean reduction path) and estimates the p99
with the **streaming fixed-bin quantile** primitives here: utilization
is quantized to :data:`QUANT_BINS` fixed bins (``uint16`` codes over
:data:`QUANT_RANGE`), and :func:`quantile_from_codes` extracts any
quantile of the implicit histogram by bisecting the code space with
count reductions -- O(1) state per bin boundary probed, O(gains)
transfers, no sort and no scatter (both pathologically slow on XLA
CPU; see ROADMAP).  Worst-case quantization error is
``(hi - lo) / QUANT_BINS`` ~= 3e-5 utilization.
:func:`finalize_fleet_stats` assembles a :class:`FleetStats` from the
streamed accumulators so the metric *definitions* stay in this module.

:func:`default_score` folds a :class:`FleetStats` into one scalar per
gain point -- higher is better -- trading granted storage against
pressure.  Tuning (``lab.tune``) maximizes it; swap in any callable
with the same signature for a different objective.

CacheLoop additions: :class:`FleetStats` carries ``hit_ratio`` /
``evicted_bytes`` / ``app_runtime`` / ``app_slowdown`` (neutral when
cache modeling is off), :func:`hpl_slowdown_curve` is the vectorized
Fig.-2 pressure multiplier the scanned cache model applies, and
:func:`runtime_score` is the pure modeled-app-runtime objective.

AppGraph additions: ``FleetStats.makespan`` is the DAG co-simulation's
end-to-end wall clock (neutral when no graph is attached) and
:func:`makespan_score` the objective that makes the paper's headline
speedup emergent -- no penalty weight involved.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core.traces import GiB

Array = Union[np.ndarray, jnp.ndarray]

# A few thousandths over r0 is measurement noise, not pressure (matches
# the historical simulate_fleet threshold).
OVER_R0_EPS = 1e-3
# Settle band: the fleet has settled once its max utilization stays
# within this margin above r0.
SETTLE_TOL = 0.02

# Streaming-quantile fixed-bin grid: utilization codes are uint16 over
# [0, 2) -- ratios beyond 2x total memory saturate into the top bin
# (far past the swap cliff; every scenario in the registry peaks well
# below it).  65536 bins -> 3.05e-5 quantization granularity.
QUANT_BINS = 65536
QUANT_RANGE: Tuple[float, float] = (0.0, 2.0)
_QUANT_SCALE = QUANT_BINS / (QUANT_RANGE[1] - QUANT_RANGE[0])


class FleetStats(NamedTuple):
    """Per-gain stability metrics; each field is scalar or ``(G,)``.

    Fields 11-14 are the CacheLoop (cache-dynamics) metrics.  With
    cache modeling off (``ScenarioSpec.cache is None``) they hold
    their neutral values -- ``hit_ratio=1``, ``evicted_bytes=0``,
    ``app_runtime`` equal to the ideal horizon wall-clock,
    ``app_slowdown=1`` -- so every objective built on them is a no-op
    for pure stability sweeps.

    ``makespan`` is the AppGraph (DAG co-simulation) metric: wall-clock
    seconds until the last node drained the last stage of the
    scenario's :class:`~repro.lab.appgraph.AppGraphSpec`.  Neutral
    (ideal horizon seconds) when no graph is attached.  A graph that
    does *not* finish within the horizon reports the work-linear
    extrapolation ``horizon * total_work / done_work`` (clamped to at
    least the horizon) so unfinished runs still order correctly.
    """

    mean_utilization: Array
    p99_utilization: Array
    max_utilization: Array
    frac_intervals_over_r0: Array    # share of (t, n) samples with r > r0
    max_over_r0: Array               # worst excursion above r0
    pressure_violation_rate: Array   # share of (t, n) samples with r > 1
    mean_capacity_gib: Array
    capacity_std_gib: Array
    granted_volume_gib_s: Array      # integral of the storage grant
    settle_intervals: Array          # first t after which max util <= r0+tol
    hit_ratio: Array                 # fleet cache hits / accesses (bytes)
    evicted_bytes: Array             # controller-forced eviction flux
    app_runtime: Array               # modeled app runtime, s (fleet barrier)
    app_slowdown: Array              # app_runtime / ideal horizon wall-clock
    makespan: Array                  # AppGraph end-to-end makespan, s


def compute_fleet_stats(
    utils: Array,
    caps: Array,
    *,
    r0: Union[float, Array],
    interval_s: float,
    p99_utilization: Optional[Array] = None,
    hit_ratio: Optional[Array] = None,
    evicted_bytes: Optional[Array] = None,
    app_runtime: Optional[Array] = None,
    makespan: Optional[Array] = None,
) -> FleetStats:
    """Reduce a ``(T, N)`` closed-loop history to :class:`FleetStats`.

    ``utils`` is the observed utilization ratio ``v / M`` per interval
    and node; ``caps`` the granted storage capacity in bytes.  ``r0``
    may be traced (the sweep engine vmaps this function over gains).

    Every statistic except p99 is a streaming reduction XLA fuses into
    the producing scan.  The quantile needs the full distribution and
    XLA's CPU sort is ~40x slower than numpy's selection, so the sweep
    engine computes it host-side on the materialized history and passes
    it in via ``p99_utilization``; left as None it is computed here.

    The CacheLoop fields (``hit_ratio`` / ``evicted_bytes`` /
    ``app_runtime``) come from a cache-dynamics simulation this dense
    path does not run; callers with cache state pass them in, everyone
    else gets the neutral values.
    """
    utils = jnp.asarray(utils)
    caps = jnp.asarray(caps)
    t = utils.shape[0]
    over = jnp.clip(utils - r0, 0.0, None)
    fleet_max = utils.max(axis=1)                          # (T,)
    bad = fleet_max > r0 + SETTLE_TOL
    last_bad = jnp.where(bad.any(), t - 1 - jnp.argmax(bad[::-1]), -1)
    if p99_utilization is None:
        p99_utilization = jnp.quantile(utils, 0.99)
    ideal_s = t * interval_s
    if app_runtime is None:
        app_runtime = jnp.float32(ideal_s)
    return FleetStats(
        mean_utilization=utils.mean(),
        p99_utilization=p99_utilization,
        max_utilization=utils.max(),
        frac_intervals_over_r0=(utils > r0 + OVER_R0_EPS).mean(),
        max_over_r0=over.max(),
        pressure_violation_rate=(utils > 1.0).mean(),
        mean_capacity_gib=caps.mean() / GiB,
        capacity_std_gib=caps.std() / GiB,
        granted_volume_gib_s=caps.mean(axis=1).sum() * interval_s / GiB,
        settle_intervals=(last_bad + 1).astype(jnp.int32),
        hit_ratio=jnp.float32(1.0) if hit_ratio is None else hit_ratio,
        evicted_bytes=(jnp.float32(0.0) if evicted_bytes is None
                       else evicted_bytes),
        app_runtime=app_runtime,
        app_slowdown=jnp.asarray(app_runtime, jnp.float32) / ideal_s,
        makespan=(jnp.float32(ideal_s) if makespan is None
                  else jnp.asarray(makespan, jnp.float32)),
    )


# ---------------------------------------------------------------------------
# Streaming (device-resident) reductions
# ---------------------------------------------------------------------------

def kahan_add(total: Array, comp: Array, x: Array) -> Tuple[Array, Array]:
    """One compensated-summation step: ``total + x`` carrying ``comp``.

    Keeps long float32 accumulations (T x N closed-loop sums) at
    O(eps) relative error instead of O(T * eps) -- the sweep engine's
    f32-clean reduction path.  Elementwise, so XLA fuses it into the
    scan body.
    """
    y = x - comp
    t = total + y
    return t, (t - total) - y


def hpl_slowdown_curve(utilization: Array) -> Array:
    """Fig.-2 execution-time multiplier, vectorized for the scan.

    The elementwise jax form of
    :func:`repro.core.traces.hpl_slowdown` (``swap_frac=0``): flat to
    92% utilization, ~1.35x at 98%, 4x at 100%, then the deep-swap
    cliff.  The CacheLoop carry applies it per node per interval to
    price un-relieved pressure into the modeled app runtime; a parity
    test pins it to the scalar reference.
    """
    u = jnp.clip(jnp.asarray(utilization, jnp.float32), 0.0, 1.5)
    return jnp.where(
        u <= 0.92, 1.0,
        jnp.where(u <= 0.98, 1.0 + (u - 0.92) / 0.06 * 0.35,
                  jnp.where(u <= 1.0, 1.35 + (u - 0.98) / 0.02 * 2.65,
                            4.0 + (u - 1.0) * 300.0)))


def utilization_codes(utils: Array) -> Array:
    """Quantize utilization ratios onto the fixed streaming-bin grid."""
    lo, _ = QUANT_RANGE
    idx = (jnp.asarray(utils, jnp.float32) - lo) * _QUANT_SCALE
    # Through int32: Mosaic has no direct float -> uint16 conversion,
    # and the in-range truncation is the same either way.
    return jnp.clip(idx, 0, QUANT_BINS - 1).astype(jnp.int32).astype(
        jnp.uint16)


# Bisection depth of the streaming quantile: 12 levels resolve the
# 2^16-bin code space to a 16-bin bracket, i.e. 2^-11 of QUANT_RANGE
# (~5e-4 utilization worst case, ~2.4e-4 expected).  Each level is one
# dense count reduction over the codes, so depth trades accuracy
# against sweep throughput linearly; 16 recovers the exact (quantized)
# order statistic.
QUANT_LEVELS = 12


def quantile_from_codes(codes: Array, q: float, n_total: int,
                        levels: int = QUANT_LEVELS,
                        axis_name: Optional[str] = None) -> Array:
    """Quantile of the implicit fixed-bin histogram behind ``codes``.

    ``codes`` is any-shape ``uint16`` (one code per closed-loop sample,
    produced by :func:`utilization_codes`); the quantile is recovered
    by bisecting the 2^16 code space -- ``levels`` count reductions,
    each a dense compare-and-sum XLA fuses well (a scatter histogram or
    an on-device sort is 10-40x slower on CPU backends).  Returns the
    dequantized midpoint of the final bracket around the order
    statistic at ``floor(q * (n_total - 1))`` (``np.quantile``'s lower
    neighbour): error <= ``QUANT_RANGE`` span * 2^-(levels+1), plus
    half a bin once ``levels`` hits 16.

    Under ``shard_map`` with the node axis sharded, pass ``axis_name``
    (and the *global* ``n_total``): each bisection level's count is
    ``psum``'d across the axis, so every shard walks the identical
    bracket sequence over the global histogram -- integer counts make
    the collective exact, and the result is replicated by construction.
    """
    target = jnp.int32(int(np.floor(q * (n_total - 1))))

    # two-stage integer reduction: narrow partials along the last axis
    # (int16 when < 32768 lanes) then one int32 fold
    part_dtype = jnp.int16 if codes.shape[-1] < 2**15 else jnp.int32

    def body(_, lo_hi):
        lo, hi = lo_hi
        mid = (lo + hi) >> 1
        below = codes <= mid.astype(jnp.uint16)
        count = below.sum(axis=-1, dtype=part_dtype).astype(jnp.int32).sum()
        if axis_name is not None:
            count = jax.lax.psum(count, axis_name)
        go_left = count > target
        return (jnp.where(go_left, lo, mid + 1),
                jnp.where(go_left, mid, hi))

    lo, hi = jax.lax.fori_loop(0, min(levels, 16), body,
                               (jnp.int32(0), jnp.int32(QUANT_BINS - 1)))
    lo0, _hi0 = QUANT_RANGE
    mid_code = (lo.astype(jnp.float32) + hi.astype(jnp.float32) + 1.0) * 0.5
    return lo0 + mid_code / _QUANT_SCALE


def _axis_sum(x: Array, axis_name: Optional[str]) -> Array:
    """Fold per-node lanes, then (under shard_map) across the axis.

    ``axis_name=None`` is the exact historical expression, so unsharded
    callers stay bitwise identical.
    """
    if axis_name is None:
        return x.sum()
    return jax.lax.psum(x.sum(), axis_name)


def _axis_max(x: Array, axis_name: Optional[str]) -> Array:
    if axis_name is None:
        return x.max()
    return jax.lax.pmax(x.max(), axis_name)


def _axis_min(x: Array, axis_name: Optional[str]) -> Array:
    """Fleet-wide min (the AppGraph barrier/completion fold).

    The DAG carry asks "has *every* node reached level L?" -- a min
    over the global fleet, so under the 2-D mesh it is the one
    collective the queue/barrier state machine needs per step.
    """
    if axis_name is None:
        return x.min()
    return jax.lax.pmin(x.min(), axis_name)


def finalize_fleet_stats(
    *,
    util_sum: Array,             # (N,) Kahan-compensated sum of r over T
    util_max: Array,             # (N,) running max of r
    caps_sum_gib: Array,         # (N,) Kahan-compensated sum of u / GiB
    caps_sumsq_gib: Array,       # (N,) sum of (u / GiB)^2
    over_r0_count: Array,        # (N,) int count of r > r0 + OVER_R0_EPS
    violation_count: Array,      # (N,) int count of r > 1
    last_bad: Array,             # (N,) int last t with r > r0 + SETTLE_TOL
    p99_utilization: Array,      # scalar (from quantile_from_codes)
    r0: Array,
    n_intervals: int,
    interval_s: float,
    hits_gib: Optional[Array] = None,        # (N,) sum of hit bytes / GiB
    evicted_gib: Optional[Array] = None,     # (N,) sum of evicted bytes / GiB
    app_time_s: Optional[Array] = None,      # (N,) modeled per-node app time
    accesses_gib: Optional[Array] = None,    # scalar per-node access total
    makespan_s: Optional[Array] = None,      # scalar AppGraph makespan, s
    axis_name: Optional[str] = None,         # shard_map node axis, if sharded
    n_nodes: Optional[int] = None,           # global N when lanes are a shard
) -> FleetStats:
    """Assemble :class:`FleetStats` from streamed per-node accumulators.

    The metric definitions (thresholds, units, settle semantics) match
    :func:`compute_fleet_stats` on the dense history exactly; only the
    reduction order differs (per-node lanes folded once at the end).

    The four trailing cache arguments are the CacheLoop accumulators;
    all-None (cache modeling off) yields the neutral field values.
    ``app_runtime`` is the slowest node's modeled time -- iterative
    apps synchronize on a barrier, so the straggler sets the fleet's
    runtime (``cluster_sim``'s iteration semantics).  ``makespan_s``
    is the AppGraph co-simulation's end-to-end result, already a
    fleet-global scalar (its barrier folds run inside the scan);
    ``None`` (no graph attached) pins the neutral ideal horizon.

    When the node axis is sharded under ``shard_map`` (the 2-D
    gains x nodes mesh), the accumulators here are one shard's lanes:
    pass ``axis_name`` so the final folds become ``psum``/``pmax``
    collectives, and ``n_nodes`` as the *global* fleet size.  Every
    returned field is then replicated across the node axis.
    """
    t = n_intervals
    n = util_sum.shape[-1] if n_nodes is None else n_nodes
    samples = t * n
    caps_total = _axis_sum(caps_sum_gib, axis_name)
    caps_mean = caps_total / samples
    caps_var = jnp.maximum(_axis_sum(caps_sumsq_gib, axis_name) / samples
                           - caps_mean * caps_mean, 0.0)
    max_util = _axis_max(util_max, axis_name)
    ideal_s = t * interval_s
    if app_time_s is None:
        hit_ratio = jnp.float32(1.0)
        evicted_bytes = jnp.float32(0.0)
        app_runtime = jnp.asarray(ideal_s, jnp.float32)
    else:
        hit_ratio = _axis_sum(hits_gib, axis_name) / (n * accesses_gib)
        evicted_bytes = _axis_sum(evicted_gib, axis_name) * jnp.float32(GiB)
        app_runtime = _axis_max(app_time_s, axis_name)
    return FleetStats(
        mean_utilization=_axis_sum(util_sum, axis_name) / samples,
        p99_utilization=p99_utilization,
        max_utilization=max_util,
        frac_intervals_over_r0=_axis_sum(over_r0_count, axis_name) / samples,
        max_over_r0=jnp.clip(max_util - r0, 0.0, None),
        pressure_violation_rate=_axis_sum(violation_count,
                                          axis_name) / samples,
        mean_capacity_gib=caps_mean,
        capacity_std_gib=jnp.sqrt(caps_var),
        granted_volume_gib_s=caps_total / n * interval_s,
        settle_intervals=(_axis_max(last_bad, axis_name) + 1)
        .astype(jnp.int32),
        hit_ratio=hit_ratio,
        evicted_bytes=evicted_bytes,
        app_runtime=app_runtime,
        app_slowdown=app_runtime / ideal_s,
        makespan=(jnp.asarray(ideal_s, jnp.float32) if makespan_s is None
                  else jnp.asarray(makespan_s, jnp.float32)),
    )


# GiB-equivalents one full unit of modeled app slowdown costs in
# default_score: the paper's 5X-runtime headline is an app-level
# metric, so once a scenario models cache dynamics the objective must
# price it on par with the stability terms.
RUNTIME_WEIGHT = 50.0


def default_score(stats: FleetStats) -> Array:
    """Storage yield minus pressure penalties; higher is better.

    Units are GiB of mean granted capacity.  The weights price the
    paper's asymmetry: a swapping node (utilization > 1) collapses HPL
    by ~10x (Fig. 2), so violations dominate; sustained time above
    ``r0`` costs throughput; slow settling delays every burst response.
    The app-runtime term is zero whenever cache modeling is off
    (``app_slowdown`` is pinned at 1), so pure stability sweeps score
    exactly as before CacheLoop.
    """
    return (
        jnp.asarray(stats.mean_capacity_gib)
        - 200.0 * jnp.asarray(stats.frac_intervals_over_r0)
        - 2000.0 * jnp.asarray(stats.pressure_violation_rate)
        - 100.0 * jnp.asarray(stats.max_over_r0)
        - 0.01 * jnp.asarray(stats.settle_intervals)
        - RUNTIME_WEIGHT * (jnp.asarray(stats.app_slowdown) - 1.0)
    )


def runtime_score(stats: FleetStats) -> Array:
    """Pure modeled-app-runtime objective; higher is better.

    The negated slowdown of the fleet's straggler node: the metric the
    paper's headline result (up to 5X Spark runtime) optimizes.  Memory
    pressure needs no separate guard -- the Fig.-2 curve inside the
    CacheLoop already stretches ``app_runtime`` catastrophically once a
    node swaps.  Only meaningful on cache-enabled scenarios; with cache
    modeling off every gain scores the constant -1.
    """
    return -jnp.asarray(stats.app_slowdown)


def makespan_score(stats: FleetStats) -> Array:
    """Negated AppGraph end-to-end makespan; higher is better.

    The *emergent* runtime objective: no penalty weights, no modeled
    slowdown term -- just how fast the declared stage DAG actually
    drained under the candidate gains, with memory pressure and cache
    misses acting through the queue-advance rate inside the
    co-simulation.  A controller wins here only by keeping caches warm
    and nodes off the swap cliff *while the job runs*, which is the
    paper's headline claim stated as a measurement instead of a
    weighted objective.  Only meaningful on scenarios with an
    ``app_graph``; otherwise every gain scores the constant negated
    horizon.
    """
    return -jnp.asarray(stats.makespan)


def stats_to_dict(stats: FleetStats,
                  index: Optional[int] = None) -> Dict[str, float]:
    """One gain point's stats as a plain-float dict (JSON-friendly)."""
    out = {}
    for name, value in stats._asdict().items():
        arr = np.asarray(value)
        out[name] = float(arr if arr.ndim == 0 else arr[index])
    return out
