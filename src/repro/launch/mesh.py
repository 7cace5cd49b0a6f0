"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (device count locks on first jax init, and the
dry-run must set XLA_FLAGS before that happens).

Mesh semantics (DESIGN.md §4):

* ``pod``   -- pure data parallelism across pods; gradient all-reduce is
  the only collective crossing it (optionally int8-compressed).
* ``data``  -- FSDP + batch sharding within a pod.
* ``model`` -- tensor/expert parallelism (heads, d_ff, vocab, experts).
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def activate_mesh(mesh):
    """Context manager making ``mesh`` the ambient mesh.

    ``jax.set_mesh`` sets the mesh and returns a context manager that
    restores the previous one, so the mesh is only ambient inside the
    ``with``.
    """
    return jax.set_mesh(mesh)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Arbitrary mesh (elastic re-mesh path, tests).

    Axes are ``Auto``: the model stack places arrays through sharding
    constraints and jit shardings that the partitioner propagates, not
    through sharding-typed arrays.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def single_device_mesh():
    return make_mesh((1, 1), ("data", "model"))


def describe(mesh) -> dict:
    return {
        "shape": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "n_devices": int(mesh.devices.size),
        "axis_names": list(mesh.axis_names),
    }
