"""Serving engine: continuous batching, preemption, mixed progress."""

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Model
from repro.models import decode as D
from repro.serving import Request, ServingConfig, ServingEngine


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("llama3.2-1b-smoke")
    m = Model(cfg, remat="none", attn_impl="dense")
    return cfg, m, m.init(jax.random.key(0))


def make_engine(small_model, **kw):
    cfg, m, params = small_model
    sc = ServingConfig(max_batch=kw.pop("max_batch", 3),
                       max_len=kw.pop("max_len", 64),
                       block_tokens=kw.pop("block_tokens", 8), **kw)
    return cfg, ServingEngine(m, params, sc)


def test_engine_drains_all_requests(small_model):
    cfg, eng = make_engine(small_model)
    rng = np.random.default_rng(0)
    rids = [eng.submit(rng.integers(0, cfg.vocab_size, 7), 5)
            for _ in range(7)]
    fin = eng.run_until_drained(max_steps=2000)
    assert sorted(fin) == sorted(rids)
    assert all(len(r.output) == 5 for r in fin.values())


def test_mixed_progress_equals_isolated(small_model):
    """A request served alongside others (staggered admission, different
    positions per slot) must produce the same tokens as served alone --
    the per-sequence position machinery end-to-end."""
    cfg, m, params = small_model
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (5, 9, 3)]

    def serve(prompt_list):
        eng = ServingEngine(m, params,
                            ServingConfig(max_batch=3, max_len=64,
                                          block_tokens=8,
                                          cache_dtype="float32"))
        rids = [eng.submit(p, 6) for p in prompt_list]
        fin = eng.run_until_drained(max_steps=2000)
        return [fin[r].output for r in rids]

    together = serve(prompts)
    alone = [serve([p])[0] for p in prompts]
    assert together == alone


def test_preemption_requeues_and_finishes(small_model):
    cfg, eng = make_engine(small_model)
    rng = np.random.default_rng(2)
    for _ in range(6):
        eng.submit(rng.integers(0, cfg.vocab_size, 12), 10)
    for _ in range(8):
        eng.step()
    eng.pool.set_capacity(eng.pool.block_bytes * 3)
    for _ in range(4):
        eng.step()
    eng.pool.set_capacity(eng.pool.block_bytes * eng.pool.total_blocks)
    fin = eng.run_until_drained(max_steps=5000)
    st = eng.stats()
    assert len(fin) == 6
    assert st["preemptions"] >= 1
    assert all(len(r.output) == 10 for r in fin.values())


def test_preempted_output_preserved(small_model):
    """Preemption keeps generated tokens: on re-admission the sequence
    continues, it does not restart generation."""
    cfg, m, params = small_model
    eng = ServingEngine(m, params,
                        ServingConfig(max_batch=1, max_len=64,
                                      block_tokens=4,
                                      cache_dtype="float32"))
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, 6)
    rid = eng.submit(prompt, 8)
    for _ in range(9):
        eng.step()
    req = eng.slots[0].request
    tokens_before = list(req.output)
    assert tokens_before
    eng.pool.set_capacity(0)                     # hard burst
    eng.step()
    assert eng.queue and eng.queue[0].rid == rid
    eng.pool.set_capacity(eng.pool.block_bytes * eng.pool.total_blocks)
    fin = eng.run_until_drained(max_steps=4000)
    assert fin[rid].output[:len(tokens_before)] == tokens_before
    assert len(fin[rid].output) == 8
    assert fin[rid].preemptions >= 1


def test_admission_respects_pool_budget(small_model):
    cfg, m, params = small_model
    eng = ServingEngine(m, params,
                        ServingConfig(max_batch=3, max_len=64,
                                      block_tokens=8))
    eng.pool.set_capacity(eng.pool.block_bytes * 2)   # room for 1 request
    rng = np.random.default_rng(4)
    for _ in range(3):
        eng.submit(rng.integers(0, cfg.vocab_size, 8), 4)
    eng.step()
    assert sum(not s.free for s in eng.slots) == 1
    assert len(eng.queue) == 2


class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform, self.id, self._stats = platform, 0, stats

    def memory_stats(self):
        return self._stats


def test_device_monitor_reads_accelerator_stats():
    from repro.core.monitor import DeviceMemoryMonitor
    dev = _FakeDevice("tpu", {"bytes_limit": 1000, "bytes_in_use": 250})
    s = DeviceMemoryMonitor(dev, assumed_total=1.0).sample()
    assert (s.total, s.used) == (1000.0, 250.0)


def test_device_monitor_assumes_totals_only_on_cpu():
    from repro.core.monitor import DeviceMemoryMonitor, MonitorFault
    s = DeviceMemoryMonitor(_FakeDevice("cpu", None),
                            assumed_total=64.0).sample()
    assert (s.total, s.used) == (64.0, 0.0)
    with pytest.raises(ValueError, match="bytes_limit"):
        DeviceMemoryMonitor(_FakeDevice("tpu", {"bytes_in_use": 1}))
    dev = _FakeDevice("tpu", {"bytes_limit": 10, "bytes_in_use": 1})
    mon = DeviceMemoryMonitor(dev)
    dev._stats = {}
    with pytest.raises(MonitorFault):
        mon.sample()
