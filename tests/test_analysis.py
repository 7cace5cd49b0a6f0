"""PlaneCheck: per-rule fires/doesn't-fire pairs, lock regressions,
mutation gates, and the end-to-end zero-new-findings invariant."""

import os
import textwrap

import numpy as np
import pytest

from repro.analysis import Baseline, RULES, analyze_locks, analyze_traced, run
from repro.analysis.__main__ import main as planecheck_main
from repro.analysis.runtime import (dispatch_guard, excess_traces,
                                    record_trace, reset_trace_counts,
                                    trace_counts)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
BASELINE = os.path.join(REPO, "PLANECHECK_BASELINE.json")


def traced_rules(tmp_path, code):
    p = tmp_path / "snippet.py"
    p.write_text(textwrap.dedent(code))
    return [f.rule for f in analyze_traced([str(p)], root=str(tmp_path))]


def lock_rules(tmp_path, code):
    p = tmp_path / "snippet.py"
    p.write_text(textwrap.dedent(code))
    return [f.rule for f in analyze_locks([str(p)], root=str(tmp_path))]


# ---------------------------------------------------------------------------
# TraceLint rule pairs
# ---------------------------------------------------------------------------

def test_t001_host_sync_fires(tmp_path):
    rules = traced_rules(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            return x.item()
        """)
    assert "PC-T001" in rules


def test_t001_untraced_function_does_not_fire(tmp_path):
    rules = traced_rules(tmp_path, """
        def host_helper(x):
            return x.item()
        """)
    assert rules == []


def test_t002_float_cast_fires(tmp_path):
    rules = traced_rules(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            return float(x) + 1.0
        """)
    assert "PC-T002" in rules


def test_t002_shape_metadata_does_not_fire(tmp_path):
    rules = traced_rules(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            return x * float(x.shape[0])
        """)
    assert rules == []


def test_t002_static_kwonly_arg_does_not_fire(tmp_path):
    # keyword-only args follow the repo convention: static under jit
    rules = traced_rules(tmp_path, """
        import jax
        import functools

        def f(x, *, scale):
            return x * float(scale)

        g = jax.jit(f, static_argnames=("scale",))
        """)
    assert rules == []


def test_t003_branch_on_traced_fires(tmp_path):
    rules = traced_rules(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
        """)
    assert "PC-T003" in rules


def test_t003_is_none_and_key_membership_do_not_fire(tmp_path):
    rules = traced_rules(tmp_path, """
        import jax

        @jax.jit
        def f(params, y):
            if y is None:
                y = params["a"]
            if "b" in params:
                y = y + params["b"]
            return y
        """)
    assert rules == []


def test_t004_numpy_on_traced_fires(tmp_path):
    rules = traced_rules(tmp_path, """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.sum(x)
        """)
    assert "PC-T004" in rules


def test_t004_numpy_on_constants_does_not_fire(tmp_path):
    rules = traced_rules(tmp_path, """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return x * np.float32(3.0)
        """)
    assert rules == []


def test_t005_f64_promotion_fires(tmp_path):
    rules = traced_rules(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return jnp.asarray(x, jnp.float64)
        """)
    assert "PC-T005" in rules


def test_t005_f32_does_not_fire(tmp_path):
    rules = traced_rules(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return jnp.asarray(x, jnp.float32)
        """)
    assert rules == []


def test_t006_sort_and_traced_scatter_fire(tmp_path):
    rules = traced_rules(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x, idx):
            y = jnp.sort(x)
            return y.at[idx].set(0.0)
        """)
    assert rules.count("PC-T006") == 2


def test_t006_static_index_scatter_does_not_fire(tmp_path):
    rules = traced_rules(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            return x.at[0].set(0.0)
        """)
    assert rules == []


def test_t007_jit_in_loop_fires(tmp_path):
    rules = traced_rules(tmp_path, """
        import jax

        def build(n):
            out = []
            for i in range(n):
                out.append(jax.jit(lambda x: x + i))
            return out
        """)
    assert "PC-T007" in rules


def test_t007_hoisted_jit_does_not_fire(tmp_path):
    rules = traced_rules(tmp_path, """
        import jax

        def build(n):
            step = jax.jit(lambda x: x + 1)
            return [step for _ in range(n)]
        """)
    assert rules == []


def test_taint_flows_through_scan_and_partial(tmp_path):
    # the lab/sweep idiom: partial-bound statics + lax.scan body
    rules = traced_rules(tmp_path, """
        import functools
        import jax

        def kernel(demand, gains, *, paper_law):
            def step(carry, d):
                bad = d.item()          # host sync on the scanned value
                return carry, bad
            return jax.lax.scan(step, gains, demand)

        fn = functools.partial(kernel, paper_law=True)
        compiled = jax.jit(fn)
        """)
    assert "PC-T001" in rules


def test_planecheck_ignore_pragma_suppresses(tmp_path):
    rules = traced_rules(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            return float(x)  # planecheck: ignore[PC-T002]
        """)
    assert rules == []


# ---------------------------------------------------------------------------
# LockLint rule pairs
# ---------------------------------------------------------------------------

INVERSION = """
    import threading

    class C:
        def __init__(self):
            self.a = threading.Lock()
            self.b = threading.Lock()

        def m1(self):
            with self.a:
                with self.b:
                    pass

        def m2(self):
            with self.b:
                with self.a:
                    pass
    """


def test_l001_inversion_fires(tmp_path):
    assert "PC-L001" in lock_rules(tmp_path, INVERSION)


def test_l001_consistent_order_does_not_fire(tmp_path):
    rules = lock_rules(tmp_path, """
        import threading

        class C:
            def __init__(self):
                self.a = threading.Lock()
                self.b = threading.Lock()

            def m1(self):
                with self.a:
                    with self.b:
                        pass

            def m2(self):
                with self.a:
                    with self.b:
                        pass
        """)
    assert rules == []


def test_l001_cross_method_inversion_through_call(tmp_path):
    # m2 holds b and calls m1, which acquires a; m3 orders a before b
    rules = lock_rules(tmp_path, """
        import threading

        class C:
            def __init__(self):
                self.a = threading.Lock()
                self.b = threading.Lock()

            def locked_a(self):
                with self.a:
                    pass

            def m2(self):
                with self.b:
                    self.locked_a()

            def m3(self):
                with self.a:
                    with self.b:
                        pass
        """)
    assert "PC-L001" in rules


GUARDED = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []   # guarded-by: _lock

        def {body}
    """


def test_l002_unlocked_mutation_fires(tmp_path):
    code = GUARDED.format(body="bad(self):\n            "
                               "self._items.append(1)")
    assert "PC-L002" in lock_rules(tmp_path, code)


def test_l002_locked_mutation_does_not_fire(tmp_path):
    code = GUARDED.format(body="good(self):\n            "
                               "with self._lock:\n                "
                               "self._items.append(1)")
    assert lock_rules(tmp_path, code) == []


def test_l002_holds_pragma_trusted(tmp_path):
    code = GUARDED.format(body="helper(self):  # locklint: holds _lock\n"
                               "            self._items.append(1)")
    assert lock_rules(tmp_path, code) == []


def test_l002_documentation_only_guard_not_enforced(tmp_path):
    # guard names that are not lock attrs (e.g. join(_thread)) document
    # a synchronization contract the analyzer cannot check
    rules = lock_rules(tmp_path, """
        import threading

        class H:
            def __init__(self, thread):
                self._thread = thread
                self._box = {}   # guarded-by: join(_thread)

            def late_write(self):
                self._box["k"] = 1
        """)
    assert rules == []


def test_l003_blocking_under_lock_fires(tmp_path):
    rules = lock_rules(tmp_path, """
        import threading
        import time

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def bad(self):
                with self._lock:
                    time.sleep(1.0)
        """)
    assert "PC-L003" in rules


def test_l003_blocking_outside_lock_does_not_fire(tmp_path):
    rules = lock_rules(tmp_path, """
        import threading
        import time

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def good(self):
                time.sleep(1.0)
                with self._lock:
                    pass
        """)
    assert rules == []


def test_l003_transitive_blocking_through_callee(tmp_path):
    rules = lock_rules(tmp_path, """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def slow_flush(self):
                with open("/tmp/x", "w") as fh:
                    fh.write("x")

            def bad(self):
                with self._lock:
                    self.slow_flush()
        """)
    assert "PC-L003" in rules


# ---------------------------------------------------------------------------
# Baseline semantics
# ---------------------------------------------------------------------------

def test_baseline_requires_justification():
    b = Baseline([{"rule": "PC-T001", "file": "f.py", "symbol": "g",
                   "justification": ""}])
    assert b.validate()


def test_baseline_matches_on_symbol_not_line(tmp_path):
    p = tmp_path / "snippet.py"
    p.write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            return x.item()
        """))
    findings = analyze_traced([str(p)], root=str(tmp_path))
    assert findings
    b = Baseline([{"rule": f.rule, "file": f.file, "symbol": f.symbol,
                   "justification": "test"} for f in findings])
    assert all(b.covers(f) for f in findings)
    assert b.stale() == []


def test_rule_catalog_covers_both_families():
    assert {r[:4] for r in RULES} == {"PC-T", "PC-L"}
    assert len(RULES) == 10


# ---------------------------------------------------------------------------
# Mutation gates (acceptance criteria)
# ---------------------------------------------------------------------------

def test_gate_fails_on_injected_item_in_scan(tmp_path):
    src = open(os.path.join(REPO, "src/repro/lab/sweep.py")).read()
    needle = "        law = (u_next,) if paper_law else (u_next, v)"
    assert needle in src
    mutated = tmp_path / "sweep_mut.py"
    mutated.write_text(src.replace(
        needle, "        _bad = r.item()\n" + needle, 1))
    findings = analyze_traced([str(mutated)], root=str(tmp_path))
    assert any(f.rule == "PC-T001" and "step" in f.symbol
               for f in findings)
    rc = planecheck_main([str(mutated), "--check", "--baseline", BASELINE])
    assert rc == 1


def test_gate_fails_on_injected_lock_inversion(tmp_path):
    src = open(os.path.join(REPO, "src/repro/core/plane.py")).read()
    needle = "    def record(self, capacity"
    assert needle in src
    inj = ("    def _inverted(self):\n"
           "        with self._lock:\n"
           "            with self._tick_lock:\n"
           "                pass\n\n")
    mutated = tmp_path / "plane_mut.py"
    mutated.write_text(src.replace(needle, inj + needle, 1))
    findings = analyze_locks([str(mutated)], root=str(tmp_path))
    assert any(f.rule == "PC-L001" and "_tick_lock" in f.symbol
               for f in findings)
    rc = planecheck_main([str(mutated), "--check", "--baseline", BASELINE])
    assert rc == 1


# ---------------------------------------------------------------------------
# End-to-end over src/
# ---------------------------------------------------------------------------

def test_src_tree_has_zero_nonbaselined_findings(monkeypatch):
    monkeypatch.chdir(REPO)
    baseline = Baseline.load(BASELINE)
    assert baseline.validate() == []
    assert len(baseline.entries) <= 10
    findings, new = run(["src"], baseline)
    assert new == [], "\n".join(f.format() for f in new)
    assert baseline.stale() == []


def test_src_tree_has_no_lock_inversions(monkeypatch):
    monkeypatch.chdir(REPO)
    assert [f for f in analyze_locks(["src"])
            if f.rule == "PC-L001"] == []


def test_cli_check_exits_zero_on_tree(monkeypatch):
    monkeypatch.chdir(REPO)
    assert planecheck_main(["src", "--check", "--baseline", BASELINE]) == 0


# ---------------------------------------------------------------------------
# Runtime sanitizers
# ---------------------------------------------------------------------------

def test_record_trace_counts_and_excess(planecheck_sanitizers):
    reset_trace_counts()
    record_trace("unit.test", shape=4)
    record_trace("unit.test", shape=4)
    record_trace("unit.test", shape=8)
    counts = trace_counts("unit.test")
    assert counts == {"unit.test{shape=4}": 2, "unit.test{shape=8}": 1}
    assert excess_traces("unit.test") == {"unit.test{shape=4}": 2}
    reset_trace_counts()
    assert trace_counts("unit.test") == {}


def test_record_trace_noop_when_disabled(monkeypatch):
    # the counter dict must not grow in a production process (one key
    # per fleet size from plane.fused_step would accumulate forever)
    monkeypatch.delenv("PLANECHECK_SANITIZERS", raising=False)
    reset_trace_counts()
    record_trace("unit.disabled", shape=4)
    assert trace_counts("unit.disabled") == {}


def test_dispatch_guard_noop_when_disabled(monkeypatch):
    jnp = pytest.importorskip("jax.numpy")
    monkeypatch.delenv("PLANECHECK_SANITIZERS", raising=False)
    with dispatch_guard():
        assert float(jnp.sum(jnp.asarray(np.ones(4, np.float32)))) == 4.0


def test_dispatch_guard_blocks_implicit_transfers(planecheck_sanitizers):
    jnp = pytest.importorskip("jax.numpy")
    host = np.ones(8, np.float32)
    with dispatch_guard():
        # implicit host->device conversion of a numpy operand is
        # exactly the per-chunk regression class the guard exists for
        with pytest.raises(Exception, match="[Tt]ransfer"):
            jnp.sum(host).block_until_ready()


def test_sweep_compiles_once_per_shape(planecheck_sanitizers):
    pytest.importorskip("jax")
    from repro.core.cluster_sim import paper_controller_params
    from repro.core.traces import fleet_demand_traces
    from repro.lab import GainSet, sweep_demand

    p = paper_controller_params()
    # a shape unique to this test so parallel-file runs cannot collide
    demand = fleet_demand_traces(3, 37, p.interval_s, seed=11)
    gains = GainSet.from_params(p)
    reset_trace_counts()
    for _ in range(2):
        sweep_demand(demand, gains, node_memory=p.total_memory,
                     interval_s=p.interval_s)
    key = [k for k in trace_counts("lab.sweep.chunk") if "horizon=37" in k]
    assert key and trace_counts("lab.sweep.chunk")[key[0]] == 1
    assert excess_traces("lab.sweep.chunk") == {}


@pytest.mark.parametrize("engine,steps", [("xla", 41), ("pallas", 43)])
def test_demand_staging_compiles_once_per_shape(planecheck_sanitizers,
                                                engine, steps):
    pytest.importorskip("jax")
    from repro.core.cluster_sim import paper_controller_params
    from repro.core.traces import fleet_demand_traces
    from repro.lab import GainSet, sweep_demand

    p = paper_controller_params()
    # shapes unique to this test so parallel-file runs cannot collide
    demand = fleet_demand_traces(3, steps, p.interval_s, seed=11)
    gains = GainSet(r0=0.95, lam=(0.4, 0.8, 1.2), lam_grant=(0.4, 0.25, 1.2),
                    u_min=0.0, u_max=p.u_max)          # two law classes
    reset_trace_counts()
    for _ in range(3):
        sweep_demand(demand, gains, node_memory=p.total_memory,
                     interval_s=p.interval_s, engine=engine)
    staged = trace_counts("lab.sweep.stage_demand")
    assert list(staged.values()) == [1]
    assert f"steps={steps}" in next(iter(staged))
    assert excess_traces("lab.sweep.") == {}


def test_fused_step_compiles_once_per_fleet_shape(planecheck_sanitizers):
    jnp = pytest.importorskip("jax.numpy")
    from repro.core.control import ControllerParams
    from repro.core.plane import make_fused_step

    params = ControllerParams(total_memory=64.0, r0=0.7, lam=0.4)
    fused = make_fused_step(params)
    n = 5
    args = (jnp.zeros(n), jnp.zeros(n), jnp.zeros(n),
            jnp.zeros(n, bool), jnp.ones(n, bool), jnp.full(n, 64.0),
            jnp.zeros(n), jnp.full(n, 64.0))
    reset_trace_counts()
    fused(*args)
    fused(*args)
    assert trace_counts("plane.fused_step") == \
        {"plane.fused_step{nodes=5}": 1}
