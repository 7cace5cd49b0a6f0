"""Roofline machinery: the HLO cost model against known programs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline import chip_peaks, parse_collectives, roofline_terms
from repro.roofline.analysis import model_flops
from repro.roofline.hlo_cost import hlo_cost

UNIT = 2 * 1024 ** 3          # one 1024^3 matmul


def _chain(nl, remat):
    def body(x, w):
        return jnp.tanh(jnp.dot(x, w)), None

    def f(x, ws):
        g = jax.checkpoint(body) if remat else body
        x, _ = jax.lax.scan(g, x, ws)
        return x.sum()
    return f


@pytest.mark.parametrize("nl,remat,expect", [
    (4, False, 12), (4, True, 16), (8, False, 24), (8, True, 32)])
def test_hlo_cost_counts_loop_trips(nl, remat, expect):
    """fwd (N) + bwd (2N) [+ remat recompute (N)] matmuls, with the scan
    trip count applied -- the thing backend cost_analysis gets wrong."""
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    ws = jax.ShapeDtypeStruct((nl, 1024, 1024), jnp.float32)
    c = jax.jit(jax.value_and_grad(_chain(nl, remat),
                                   argnums=(0, 1))).lower(x, ws).compile()
    r = hlo_cost(c.as_text())
    assert r["flops"] == pytest.approx(expect * UNIT, rel=1e-6)


def test_backend_cost_analysis_is_wrong_on_loops():
    """Documents WHY hlo_cost exists: the backend reports loop-invariant
    flops (if this ever starts passing trip counts, simplify!)."""
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 1024, 1024), jnp.float32)
    c = jax.jit(jax.value_and_grad(_chain(8, False),
                                   argnums=(0, 1))).lower(x, ws).compile()
    backend = c.cost_analysis()["flops"]
    ours = hlo_cost(c.as_text())["flops"]
    assert ours >= 3 * backend


def test_remat_reduces_bytes():
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 1024, 1024), jnp.float32)
    plain = hlo_cost(jax.jit(jax.value_and_grad(
        _chain(8, False), argnums=(0, 1))).lower(x, ws).compile().as_text())
    remat = hlo_cost(jax.jit(jax.value_and_grad(
        _chain(8, True), argnums=(0, 1))).lower(x, ws).compile().as_text())
    assert remat["bytes"] < plain["bytes"]


def test_roofline_terms_and_dominance():
    t = roofline_terms(hlo_flops_per_chip=197e12,       # exactly 1 s
                       hlo_bytes_per_chip=819e9 / 2,    # 0.5 s
                       collective_bytes_per_chip=50e9 / 4,
                       chip=chip_peaks("TPU v5 lite"))
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(0.5)
    assert t["collective_s"] == pytest.approx(0.25)
    assert t["dominant"] == "compute"
    assert t["bound_s"] == pytest.approx(1.0)


def test_chip_peaks_keyed_by_device_kind():
    assert chip_peaks("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks("cpu")


def test_model_flops_conventions():
    assert model_flops(10, 0, 100, "train") == 6 * 10 * 100
    assert model_flops(10, 0, 100, "prefill") == 2 * 10 * 100
    assert model_flops(100, 25, 10, "train") == 6 * 25 * 10   # MoE active


def test_parse_collectives_finds_psum():
    mesh = jax.make_mesh((1,), ("x",))

    def f(a):
        return jax.lax.psum(a, "x")

    shard_map = jax.shard_map
    from jax.sharding import PartitionSpec as P
    fn = jax.jit(shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P()))
    c = fn.lower(jax.ShapeDtypeStruct((16, 64), jnp.float32)).compile()
    out = parse_collectives(c.as_text())
    # single-device meshes may elide the collective; accept either but
    # the parser must not crash and must return the schema
    assert set(out) >= {"total_bytes", "per_kind_bytes", "n_ops"}
