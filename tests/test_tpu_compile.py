"""Ahead-of-time compiles of the sweep programs for a described TPU v5e.

No chip is needed: the TPU compiler builds for ``v5e:2x2`` described by
``jax.experimental.topologies``, which catches what interpret mode
cannot -- block shapes Mosaic refuses, scoped-VMEM overruns, programs
that do not fit HBM, meshes that do not partition.  Sweep sizes are
the chip smoke's phase 1 (4096 nodes x 3000 intervals); the attention
kernels compile at llama3.2-1b's head shapes.

The topology is described inside a module fixture (never at import),
and every test here skips where it cannot be described.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.configs.dynims import PAPER_TABLE_I
from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.ssm_scan.kernel import ssm_scan
from repro.lab import get_scenario, grid_gains
from repro.lab import pallas_sweep as ps
from repro.lab import step
from repro.lab import sweep as sw

N_NODES, N_STEPS = 4096, 3000
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Compiles for a described chip cannot be read back from the
    # persistent cache, so keep them out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _gains(n):
    lam = np.linspace(0.1, 1.8, n)
    return grid_gains(PAPER_TABLE_I, lam=lam, r0=(0.95,))


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes)


@pytest.mark.parametrize("cache", [False, True], ids=["cache_off",
                                                      "cache_on"])
def test_mosaic_sweep_compiles_at_fleet_size(one_chip, cache):
    lanes = 2 * ps.TILE_GAINS
    spec = get_scenario("spark-iterative-cache").cache if cache else None
    fn = ps.sweep_program(_gains(lanes), backend="mosaic", cache=spec)
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
              for s in ((N_STEPS, N_NODES), (ps._N_NODE_ROWS, N_NODES),
                        (ps._N_PARAM_ROWS, lanes), (1, lanes))]
    compiled = fn.lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def test_halving_program_names_its_kernel_and_rungs(one_chip):
    """The names a profiler trace shows: the program, the Mosaic kernel
    and each rung's named scope."""
    n_cand, nodes, steps = 12, 512, 480
    gains = _gains(n_cand)
    base = ps.GainSet.from_params(PAPER_TABLE_I)
    plan = ps.plan_specialization(gains.concat(base))
    loop = step.Loop.bake(plan, None, 0.1, 1.0)
    horizons, keeps = ps.halving_schedule(steps, n_cand, (0.125, 0.5, 1.0),
                                          0.25, 4)
    fn = ps._compiled_halving("mosaic", loop, "f32", tuple(horizons),
                              tuple(keeps), n_cand, 1, ps.default_score)
    lanes = 2 * ps.TILE_GAINS
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
              for s in ((steps, nodes), (ps._N_NODE_ROWS, nodes),
                        (ps._N_PARAM_ROWS, lanes), (1, lanes))]
    text = fn.lower(*shapes).compile().as_text()
    assert "HloModule jit_lab_halving" in text
    assert "lab_sweep_kernel" in text
    for i in range(len(horizons)):
        assert f"jit(lab_halving)/rung{i}/" in text


def _xla_chunk_shapes(chunk, sharding_of):
    lead = [((N_STEPS, N_NODES), "demand"), ((N_NODES,), "m")]
    cols = [((chunk,), "gain")] * 7
    scal = [((), "scalar")] * 2
    return [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding_of(k))
            for s, k in lead + cols + scal]


def test_xla_chunk_compiles_at_auto_chunk(topo, one_chip):
    chunk = sw._resolve_chunk(None, 64, N_STEPS, N_NODES, 1)
    plan = sw.plan_specialization(_gains(chunk))
    fn = sw._compiled_sweep((topo.devices[0],), plan.paper_law,
                            plan.unit_occupancy, plan.static_bounds, None)
    compiled = fn.lower(*_xla_chunk_shapes(chunk, lambda k: one_chip)
                        ).compile()
    assert _device_bytes(compiled) < HBM_BYTES
    assert "HloModule jit_lab_sweep_chunk" in compiled.as_text()


NODE_SHARDS = 2


@pytest.fixture(scope="module")
def gains_nodes_shardings(topo):
    """Operand kind -> its sharding on the 2x2 ("gains", "nodes") mesh."""
    mesh = sw.sweep_mesh(tuple(topo.devices), NODE_SHARDS)
    lead = sw._lead_specs(NODE_SHARDS, False)
    specs = {"demand": lead[0], "m": lead[1],
             "gain": jax.sharding.PartitionSpec("gains"),
             "scalar": jax.sharding.PartitionSpec()}
    return {k: NamedSharding(mesh, p) for k, p in specs.items()}


def test_gains_nodes_mesh_compiles_on_four_chips(topo,
                                                 gains_nodes_shardings):
    devices = tuple(topo.devices)
    chunk = sw._resolve_chunk(None, 64, N_STEPS, N_NODES,
                              len(devices) // NODE_SHARDS)
    plan = sw.plan_specialization(_gains(chunk))
    fn = sw._compiled_sweep(devices, plan.paper_law, plan.unit_occupancy,
                            plan.static_bounds, None, NODE_SHARDS)
    compiled = fn.lower(*_xla_chunk_shapes(
        chunk, gains_nodes_shardings.__getitem__)).compile()
    text = compiled.as_text()
    assert "all-reduce" in text           # the stat folds over "nodes"
    assert _device_bytes(compiled) < HBM_BYTES


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
# kernel, operand (shape, dtype)s: 32 query heads over 8 KV heads of 64
KERNELS = {
    "decode_attention": (
        lambda q, k, v, lens: decode_attention(q, k, v, lens, block_k=128),
        [((4, 32, 64), BF16), ((4, 512, 8, 64), BF16),
         ((4, 512, 8, 64), BF16), ((4,), I32)]),
    "flash_attention": (
        flash_attention,
        [((1, 256, 32, 64), BF16), ((1, 256, 8, 64), BF16),
         ((1, 256, 8, 64), BF16)]),
    "ssm_scan": (
        lambda a, d, h0: ssm_scan(a, d, h0, chunk=64, block_c=128),
        [((2, 256, 128, 16), F32), ((2, 256, 128, 16), F32),
         ((2, 128, 16), F32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_model_kernel_compiles_for_mosaic(one_chip, name):
    fn, operands = KERNELS[name]
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
              for s, dt in operands]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
