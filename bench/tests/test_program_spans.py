"""The program's own spans and counters, as the per-layer readers take them."""

import io
import json
import os
import time
import types

import pytest

from bench import harness, program_spans
from bench import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "sweep_small.xplane.pb")
SWEEP_SPANS = ("lab.sweep.stage", "lab.sweep.dispatch", "lab.sweep.drain",
               "lab.sweep.merge")
HOST_READERS = ("stage_ms.sweep", "stage_ms.tune", "lane_fill.sweep",
                "lane_fill.tune", "sample_ms.plane", "publish_ms.plane",
                "actuate_ms.plane")


def test_per_call_time_is_the_union_of_the_spans_inside_each_call():
    calls = [(0, 100), (100, 300)]
    spans = [(10, 30), (20, 40), (90, 120), (500, 600)]
    # call 1: [10, 40) and [90, 100); call 2: [100, 120)
    assert program_spans.per_call_ms(calls, spans) == \
        pytest.approx([40e-6, 20e-6])
    assert program_spans.per_call_ms(calls, [(500, 600)]) is None


@pytest.mark.parametrize("name", HOST_READERS)
def test_host_readers_give_nothing_without_the_programs_spans(name,
                                                              monkeypatch):
    """A program that predates its spans and counters: no metric, no
    error."""
    monkeypatch.setattr(program_spans, "_runtime", lambda: None)
    for label in ("sweep_demand", "halving_tune", "tick"):
        gen = types.SimpleNamespace(calls=[(0.0, 1.0)], label=label)
        assert harness.load_reader(name)({"gen": gen}) is None


def _program_spans_by_call(summary, label, names):
    calls = [(s, e) for n, s, e in summary.spans if n == label]
    inner = [(n, s, e) for n, s, e in summary.spans if n in names]
    return calls, inner


def test_traced_run_reads_the_program_spans_inside_its_calls(tmp_path):
    """A whole ``--trace 1`` run at toy size on the CPU: the program's
    spans sit inside the calls' spans in the kept trace, the host-span
    metrics are read, and the device readers, which find no device
    plane on the CPU, leave theirs out."""
    out, err = io.StringIO(), io.StringIO()
    harness.run_cell("sweep.hpcc", 2**31 + 11, 1.0, True,
                     t_start=time.perf_counter(),
                     config_override=dict(n_nodes=64, n_intervals=240),
                     traffic_override=dict(trace_seconds=0.3),
                     allow_cpu=True, out=out, err=err,
                     trace_dir=str(tmp_path))
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"]
    assert set(res["metrics"]) == {"stage_ms.sweep", "lane_fill.sweep"}
    # 48 paper-law gains in 64 lanes and 16 grant gains in 16 (chunk 32)
    assert res["metrics"]["lane_fill.sweep"]["value"] == pytest.approx(80.0)
    assert "idle_pct.sweep found nothing" in err.getvalue()
    assert "xla_upd_per_s found nothing" in err.getvalue()
    s = tr.summarize(tr.find_xplane(str(tmp_path)),
                     ("sweep_demand",) + SWEEP_SPANS)
    calls, inner = _program_spans_by_call(s, "sweep_demand", SWEEP_SPANS)
    assert calls and {n for n, _, _ in inner} == set(SWEEP_SPANS)
    for n, a, b in inner:
        assert any(lo <= a and b <= hi for lo, hi in calls), n
    stage = program_spans.per_call_ms(
        calls, [(a, b) for n, a, b in inner if n == "lab.sweep.stage"])
    for (lo, hi), ms in zip(calls, stage):
        assert 0 < ms < (hi - lo) * 1e-6
    value = res["metrics"]["stage_ms.sweep"]["value"]
    assert 0 < value < min(hi - lo for lo, hi in calls) * 1e-6


def _module_names(path):
    from jax.profiler import ProfileData
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == tr.MODULES_LINE:
                    names.update(ev.name for ev in line.events)
    return names


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="recorded trace not present")
def test_recorded_chip_trace_names_the_programs_phases_and_executable():
    s = tr.summarize(RECORDED, ("sweep_demand", "gain_draw") + SWEEP_SPANS)
    calls, inner = _program_spans_by_call(s, "sweep_demand", SWEEP_SPANS)
    assert calls and {n for n, _, _ in inner} == set(SWEEP_SPANS)
    assert any(name.startswith("jit_lab_sweep_chunk")
               for name in _module_names(RECORDED))
    stage = program_spans.per_call_ms(
        calls, [(a, b) for n, a, b in inner if n == "lab.sweep.stage"])
    for (lo, hi), ms in zip(calls, stage):
        assert 0 < ms < (hi - lo) * 1e-6
    # the idle time of the window is named by the program's phases
    idle = sum(ns for _, ns in s.gaps)
    named = sum(ns for name, ns in s.gaps if name in SWEEP_SPANS)
    assert named > 0.5 * idle
