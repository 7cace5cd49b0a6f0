"""Parameter schema: one declaration -> init + sharding + shape stand-ins.

Every module declares its parameters as a nested dict of :class:`ParamDef`
(shape, initializer, PartitionSpec).  From that single schema we derive:

* ``init_params``   -- materialized arrays (for real runs / smoke tests),
* ``param_specs``   -- the PartitionSpec pytree (for pjit in_shardings),
* ``param_shapes``  -- ShapeDtypeStruct stand-ins (for the dry-run; no
  allocation ever happens for the full-size configs),
* ``stack_schema``  -- prepend a layer axis L to every leaf (scan-over-
  layers stacking; the new axis is never sharded).

Keeping all four views in one schema is what makes the 40-cell dry-run
tractable: a sharding change is one edit, provably consistent everywhere.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Schema = Dict[str, Union["ParamDef", "Schema"]]


@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    spec: P
    init: str = "fan_in"          # fan_in|normal|zeros|ones|small
    fan_in_axes: Tuple[int, ...] = (0,)   # axes whose product is fan-in
    scale: float = 1.0
    dtype: Optional[str] = None   # None -> caller-supplied default

    def with_layer_axis(self, n_layers: int) -> "ParamDef":
        return replace(
            self,
            shape=(n_layers,) + self.shape,
            spec=P(*((None,) + tuple(self.spec))),
            fan_in_axes=tuple(a + 1 for a in self.fan_in_axes),
        )

    def resolve_dtype(self, default):
        return jnp.dtype(self.dtype) if self.dtype else default


def _init_leaf(key: jax.Array, d: ParamDef, dtype) -> jax.Array:
    dtype = d.resolve_dtype(dtype)
    if d.init == "zeros":
        return jnp.zeros(d.shape, dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, dtype)
    if d.init == "const":
        return jnp.full(d.shape, d.scale, dtype)
    if d.init == "normal":
        return (jax.random.normal(key, d.shape) * d.scale).astype(dtype)
    if d.init == "small":
        return (jax.random.normal(key, d.shape) * 0.02 * d.scale).astype(dtype)
    if d.init == "fan_in":
        fan = 1
        for a in d.fan_in_axes:
            fan *= d.shape[a]
        std = d.scale / max(fan, 1) ** 0.5
        return (jax.random.normal(key, d.shape) * std).astype(dtype)
    raise ValueError(f"unknown init {d.init!r}")


def is_leaf(x) -> bool:
    return isinstance(x, ParamDef)


def init_params(schema: Schema, key: jax.Array, dtype=jnp.float32):
    leaves, treedef = jax.tree.flatten(schema, is_leaf=is_leaf)
    keys = jax.random.split(key, len(leaves))
    arrays = [_init_leaf(k, d, dtype) for k, d in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, arrays)


def param_specs(schema: Schema):
    return jax.tree.map(lambda d: d.spec, schema, is_leaf=is_leaf)


def param_shapes(schema: Schema, dtype=jnp.bfloat16):
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.resolve_dtype(dtype)),
        schema, is_leaf=is_leaf)


def stack_schema(schema: Schema, n_layers: int) -> Schema:
    return jax.tree.map(
        lambda d: d.with_layer_axis(n_layers), schema, is_leaf=is_leaf)


def count_params(schema: Schema) -> int:
    total = 0
    for d in jax.tree.leaves(schema, is_leaf=is_leaf):
        n = 1
        for s in d.shape:
            n *= s
        total += n
    return total


def bytes_of(schema: Schema, bytes_per_el: int = 2) -> int:
    return count_params(schema) * bytes_per_el


@dataclass(frozen=True)
class Axes:
    """Logical -> mesh axis mapping (DESIGN.md §4).

    ``fsdp`` shards parameters/optimizer state (the "data" mesh axis);
    ``tp`` shards heads / d_ff / vocab / experts (the "model" axis);
    ``batch`` is what activations' leading dim shards over -- ("pod",
    "data") on the multi-pod mesh, ("data",) on one pod.
    """

    fsdp: Optional[str] = "data"
    tp: Optional[str] = "model"
    batch: Tuple[str, ...] = ("data",)

    def batch_spec(self, *rest) -> P:
        return P(self.batch if len(self.batch) > 1 else self.batch[0], *rest)


SINGLE_POD_AXES = Axes(batch=("data",))
MULTI_POD_AXES = Axes(batch=("pod", "data"))
UNSHARDED_AXES = Axes(fsdp=None, tp=None, batch=(None,))


def shard_act(x: jax.Array, spec: P) -> jax.Array:
    """Constrain an activation's sharding (no-op without an active mesh).

    GSPMD resolves the FSDP conflict -- activations batch-sharded and
    weights contracting-dim-sharded on the SAME axis -- by whichever
    re-shard its cost model likes, and on the 16x16 mesh it picks
    replicating the activations (measured: full-batch f32 tensors
    all-reduced over ``data``, +100 GB/chip).  Pinning activations to
    batch sharding forces the correct choice: per-layer weight
    all-gather, the canonical FSDP schedule.
    """
    try:
        names = _ambient_axis_names()
        if not names:
            return x
        needed = {a for part in spec if part for a in
                  ((part,) if isinstance(part, str) else part)}
        if not needed.issubset(set(names)):
            return x
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


def _ambient_axis_names() -> tuple:
    """Axis names of the ambient mesh set by ``jax.set_mesh``, if any."""
    return tuple(jax.sharding.get_abstract_mesh().axis_names)


def axes_for(mesh) -> Axes:
    names = tuple(mesh.axis_names)
    if "pod" in names:
        return MULTI_POD_AXES
    if "data" in names and "model" in names:
        return SINGLE_POD_AXES
    return UNSHARDED_AXES
