"""Sharding policy units + a small real-device dry run.

The full 512-device dry-run is `python -m repro.launch.dryrun --all`
(results under results/dryrun/); here we test the policy logic and,
in a subprocess with 8 forced host devices, one real lower+compile of
each cell kind on a small mesh to keep the machinery honest in CI.
"""

import functools
import json
import os
import subprocess
import sys

import pytest

from repro.configs import ARCH_IDS, get_config


def test_sharding_report_divisibility():
    r = get_config("mistral-large-123b").sharding_report(16, 16)
    assert r["attn_tp"] is True
    assert "expanded" in r["attn_note"]
    assert r["mlp_tp"] and r["vocab_tp"] and r["d_model_fsdp"]

    r = get_config("qwen2-1.5b").sharding_report(16, 16)
    assert r["attn_tp"] is False          # 12 heads % 16 != 0
    assert r["mlp_tp"] is True

    r = get_config("whisper-large-v3").sharding_report(16, 16)
    assert r["attn_tp"] is False          # 20 heads % 16 != 0

    r = get_config("qwen2-moe-a2.7b").sharding_report(16, 16)
    assert r["experts_padded"] == 4       # 60 -> 64
    assert r["attn_tp"] is True           # 16 heads, 16 kv


def test_every_arch_has_a_report():
    for a in ARCH_IDS:
        r = get_config(a).sharding_report(16, 16)
        assert r["mesh"] == {"data": 16, "model": 16}


SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax
from repro.launch.cells import CellSettings, build_cell
from repro.launch.mesh import activate_mesh, make_mesh
from repro.roofline import analyze_compiled, chip_peaks

mesh = make_mesh((4, 2), ("data", "model"))
out = {}
for arch, shape in [("llama3.2-1b-smoke", "train_4k"),
                    ("llama3.2-1b-smoke", "prefill_32k"),
                    ("llama3.2-1b-smoke", "decode_32k")]:
    import repro.configs.base as B
    import dataclasses
    # shrink the benchmark shapes to smoke scale but keep the kinds
    shp = B.SHAPES[shape]
    small = dataclasses.replace(shp, seq_len=64, global_batch=8)
    B_SHAPES = dict(B.SHAPES); B.SHAPES[shape] = small
    try:
        with activate_mesh(mesh):
            fn, inputs, desc = build_cell(arch, shape, mesh,
                                          settings=CellSettings(microbatches=2 if shp.kind == "train" else 1,
                                                                attn_impl="dense"))
            compiled = jax.jit(fn).lower(*inputs).compile()
        r = analyze_compiled(compiled, desc, 8, chip_peaks("TPU v5 lite"))
        out[shape] = {"flops": r["hlo_flops_per_chip"],
                      "dominant": r["roofline"]["dominant"]}
    finally:
        B.SHAPES.update(B_SHAPES)
print(json.dumps(out))
"""


@pytest.mark.slow
def test_small_mesh_dryrun_all_kinds():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", SUBPROCESS_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(out) == {"train_4k", "prefill_32k", "decode_32k"}
    assert all(v["flops"] > 0 for v in out.values())


STAGING_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from repro.analysis import runtime
from repro.lab import GainSet, sweep_demand
from repro.lab.sweep import _lead_specs, resolve_devices, stage_demand, sweep_mesh

target = NamedSharding(sweep_mesh(resolve_devices(8), 2),
                       _lead_specs(2, False)[0])
demand = np.random.default_rng(0).uniform(20, 90, (16, 50)) * 2**30
host = np.ascontiguousarray(demand.T, np.float32)
out = {}
for dtype in ("float32", "bfloat16"):
    got = stage_demand(demand, jnp.dtype(dtype), sharding=target)
    want = np.asarray(jnp.asarray(host).astype(dtype))
    out[dtype] = {
        "bits": (np.asarray(got).dtype == want.dtype
                 and np.asarray(got).tobytes() == want.tobytes()),
        "placed": got.sharding.is_equivalent_to(target, 2),
        "shards": sorted({tuple(s.data.shape) for s in got.addressable_shards})}
runtime.reset_counters()
lam = np.linspace(0.3, 1.5, 8)
gains = GainSet(r0=0.95, lam=lam, lam_grant=np.where(lam > 1, 0.25, lam),
                u_min=0.0, u_max=60 * 2**30)        # two law classes
sweep_demand(demand, gains, node_memory=125 * 2**30, devices=8,
             node_shards=2)
out["staged"] = runtime.counts("lab.demand.")
print(json.dumps(out))
"""


@functools.lru_cache(maxsize=None)
def _mesh_staging():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", STAGING_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_mesh_staged_demand_equals_host_transpose(dtype):
    """On the 8-device (gains x nodes) mesh with node_shards=2 the
    staged (T, N) operand is the host transpose bit for bit, split by
    node columns as the chunk program reads it."""
    got = _mesh_staging()[dtype]
    assert got["bits"] and got["placed"]
    assert got["shards"] == [[50, 8]]


def test_mesh_mixed_sweep_stages_demand_once():
    assert _mesh_staging()["staged"] == {
        "lab.demand.staged": 1, "lab.demand.staged_bytes": 16 * 50 * 4}


def test_dryrun_artifacts_if_present():
    """When the full sweep has run, sanity-check its artifacts."""
    d = "results/dryrun"
    if not os.path.isdir(d) or not os.listdir(d):
        pytest.skip("full dry-run not executed in this environment")
    files = [f for f in os.listdir(d) if f.endswith(".json")]
    assert len(files) >= 33
    for f in files[:10]:
        r = json.load(open(os.path.join(d, f)))
        assert r["hlo_flops_per_chip"] > 0
        assert r["roofline"]["dominant"] in ("compute", "memory",
                                             "collective")
