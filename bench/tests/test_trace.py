"""The trace reduction, on hand-made intervals and on a recorded trace."""

import os

import pytest

from bench import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "sweep_small.xplane.pb")


def test_merge_unions_overlapping_intervals():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 9), (10, 11)]) == \
        [(0, 3), (5, 9), (10, 11)]


def test_clip_keeps_only_the_window():
    assert tr.clip([(0, 5), (6, 8), (9, 20)], 3, 10) == \
        [(3, 5), (6, 8), (9, 10)]


def _summary(spans, intervals):
    return tr.Summary(window=(0, 1000), busy_ns={}, ops_ns={}, ops_meta={},
                      gaps=[], spans=spans, op_intervals=intervals)


def test_span_busy_time_sums_device_work_inside_named_spans():
    s = _summary([("sweep_demand", 0, 100), ("gain_draw", 100, 120),
                  ("sweep_demand", 120, 300)],
                 {"/device:TPU:0": [(10, 50), (90, 130), (200, 400)],
                  "/device:TPU:1": [(0, 100)]})
    # first device: 40 + 10 in the first call, 10 + 100 in the second
    assert s.span_busy_s("sweep_demand") == pytest.approx(160e-9)
    assert s.span_busy_s("gain_draw") == pytest.approx(20e-9)
    both = ["/device:TPU:0", "/device:TPU:1"]
    assert s.span_busy_s("sweep_demand", both) == pytest.approx(130e-9)
    assert s.span_busy_s("tick") == 0.0


def test_innermost_span_names_a_gap():
    spans = [("sweep_demand", 0, 100), ("gain_draw", 10, 20)]
    assert tr._span_at(spans, 15) == "gain_draw"
    assert tr._span_at(spans, 50) == "sweep_demand"
    assert tr._span_at(spans, 500) == "host"


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="recorded trace not present")
def test_recorded_trace_reduces_to_busy_modules_and_gaps():
    s = tr.summarize(RECORDED, ["gain_draw", "sweep_demand"])
    assert list(s.busy_ns) == ["/device:TPU:0"]
    assert 0 < s.busy_s() < s.window_s
    # the engine's device time lies inside its calls' spans
    assert 0 < s.span_busy_s("sweep_demand") <= s.busy_s() * 1.0001
    assert any(name == "sweep_demand" for name, _, _ in s.spans)
    idle = s.window_s - s.busy_s()
    assert sum(ns for _, ns in s.gaps) * 1e-9 == pytest.approx(idle,
                                                               rel=1e-6)
    b = tr.breakdown(s)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_traced_run_keeps_its_trace_and_reports_the_window(tmp_path):
    """A whole ``--trace 1`` run at toy size on the CPU: the trace is kept
    where asked, the window span is found, and the device readers, which
    find no device plane on the CPU, leave their metrics out."""
    import io
    import json
    import time

    from bench import harness
    out, err = io.StringIO(), io.StringIO()
    harness.run_cell("sweep.hpcc", 2**31 + 9, 1.0, True,
                     t_start=time.perf_counter(),
                     config_override=dict(n_nodes=64, n_intervals=240),
                     traffic_override=dict(trace_seconds=0.3),
                     allow_cpu=True, out=out, err=err,
                     trace_dir=str(tmp_path))
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] and res["attempted"] > 0
    assert tr.find_xplane(str(tmp_path))
    assert 0.3 <= res["device"]["window_s"] < 5.0
    assert res["device"]["busy_s"] == 0.0 and res["metrics"] == {}
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "idle_pct.sweep found nothing" in err.getvalue()
