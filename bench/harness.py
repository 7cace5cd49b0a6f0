"""One run of one cell: set-up, a measured window, the check, one result line.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json`` (read by the code of its ``kind``,
``bench/kinds/<kind>.py``), and each per-layer metric's reader in
``bench/metrics/<metric>.py``, or, where that file is absent, the reader
shared by every metric of the same stem, ``bench/metrics/<stem>.py``
(``idle_pct.sweep`` -> ``idle_pct.py``).  Adding any of them is adding
files and entries.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
import warnings
from typing import Dict, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class Refused(RuntimeError):
    """The run cannot produce a result here (no accelerator, too few)."""


def configure_jax_env() -> None:
    """Persistent compile cache at a fixed path inside the checkout.

    Must run before JAX is imported.  Every program is cached, however
    quick its compile, so that set-up after the first run is steady.
    """
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def cell_spec(bench: dict, workload: str) -> dict:
    """The cell, its end-to-end metrics and its per-layer metrics."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    cell = cells[workload]

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}

    def reads_here(metric):
        if "workloads" in metric:
            return workload in metric["workloads"]
        return metric["moves"] in e2e_names

    layer = [m for m in bench["per_layer"] if reads_here(m)]
    return {"cell": cell, "end_to_end": e2e, "per_layer": layer}


def load_reader(name: str):
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    if not os.path.exists(path):
        path = os.path.join(BENCH_DIR, "metrics",
                            f"{name.split('.')[0]}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts programs compiled or loaded, and loads from the persistent
    cache, through JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.HIT:
            self.hits += 1


def device_info(devices) -> dict:
    d0 = devices[0]
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_window(gen, seconds: float) -> tuple:
    """Calls back to back (or paced) until ``seconds`` have passed.

    Returns ``(window seconds, calls)``: the window runs from its start
    to the end of the last call, so rates take all work and all time.
    """
    import jax
    gen.mark_window()
    pace = gen.pace_s
    t0 = time.perf_counter()
    k = 0
    with jax.profiler.TraceAnnotation("window"):
        while True:
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
            if pace:
                due = t0 + k * pace
                if now < due:
                    time.sleep(due - now)
                gen.lateness.append(max(time.perf_counter() - due, 0.0))
            s = time.perf_counter()
            work = gen.call()
            e = time.perf_counter()
            gen.calls.append((s, e))
            gen.updates.append(work)
            k += 1
    end = gen.calls[-1][1] if k else time.perf_counter()
    return end - t0, k


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, bench: Optional[dict] = None,
             config_override: Optional[dict] = None,
             traffic_override: Optional[dict] = None,
             allow_cpu: bool = False, out=None, err=None,
             program_hook=None, trace_dir: Optional[str] = None) -> dict:
    """Run one cell once; returns the result object (also printed).

    ``allow_cpu``, the overrides and ``program_hook`` exist for the
    benchmark's own tests, which drive a run at toy sizes on the CPU.
    """
    out = out or sys.stdout
    err = err or sys.stderr
    bench = bench or load_benchmark()
    spec = cell_spec(bench, workload)
    cell = spec["cell"]
    import jax
    from bench import fleet, generator
    devices = jax.devices()
    if devices[0].platform == "cpu" and not allow_cpu:
        raise Refused("JAX found no accelerator; a benchmark run needs one")
    chips = int(cell["chips"])
    if len(devices) < chips:
        raise Refused(f"cell {workload} needs {chips} chips, JAX sees "
                      f"{len(devices)}")
    devices = devices[:chips]
    cfg = dict(fleet.load_json("configs", cell["config"]))
    cfg.update(config_override or {})
    traffic = dict(fleet.load_json("traffic", cell["traffic"]))
    traffic.update(traffic_override or {})
    compiles = CompileCounter()
    gen = generator.make(cfg, traffic, seed, chips)
    if program_hook is not None:
        program_hook(gen)
    # A RuntimeWarning from the program during set-up or the window is a
    # fallback (another engine, a mesh ignored): the run is not the cell.
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        gen.setup()
        setup_s = time.perf_counter() - t_start
        compiles_setup = compiles.count
        print(f"setup: {setup_s:.3f} s, {compiles_setup} programs compiled "
              f"or loaded, {compiles.hits} of them from the persistent "
              "cache", file=err)
        result: Dict[str, object] = {}
        if not trace:
            window_s, n = run_window(gen, seconds)
            in_window = compiles.count - compiles_setup
            values = gen.e2e(window_s, n)
            values["setup_s"] = setup_s
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in spec["end_to_end"]}
            _report_window(gen, window_s, n, in_window, err)
        else:
            metrics, extra = _traced_window(gen, spec, traffic, devices,
                                            cfg, compiles, err, trace_dir)
            n = extra.pop("calls")
            result.update(extra)
    device = device_info(devices)
    if trace:
        device.update(result.pop("device_extra"))
    attempted, failed = n, gen.failed()
    gen.free()
    gc.collect()
    t_chk = time.perf_counter()
    checks = gen.check()
    print(f"check: reference took {time.perf_counter() - t_chk:.1f} s",
          file=err)
    correct = all(v <= lim for _, v, lim in checks) and attempted > 0
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device,
              **result,
              "checks": {name: {"value": v, "limit": lim}
                         for name, v, lim in checks}}
    for name, v, lim in checks:
        print(f"{name} = {v!r} (limit {lim!r}) "
              f"{'ok' if v <= lim else 'FAIL'}", file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
    return result


def _report_window(gen, window_s: float, n: int, compiles: int,
                   err) -> None:
    per = [e - s for s, e in gen.calls]
    line = (f"window: {window_s:.3f} s, {n} {gen.label} calls, "
            f"{compiles} compiles inside")
    if per:
        line += f", call min {min(per):.4f} s max {max(per):.4f} s"
    late = getattr(gen, "lateness", None)
    if late:
        line += (f", start lateness vs schedule: median "
                 f"{sorted(late)[len(late) // 2]:.3f} s, "
                 f"last {late[-1]:.3f} s")
    print(line, file=err)


def _traced_window(gen, spec, traffic, devices, cfg, compiles, err,
                   trace_dir=None):
    """A short window under the profiler, reduced to per-layer metrics.

    The trace is written to a temporary directory and removed, or kept
    in ``trace_dir`` where one is given.
    """
    import contextlib
    import jax
    from bench import trace as tr
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    keep = (contextlib.nullcontext(trace_dir) if trace_dir else
            tempfile.TemporaryDirectory(prefix="bench_trace_"))
    with keep as log_dir:
        before = compiles.count
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            window_s, n = run_window(gen, float(traffic["trace_seconds"]))
        finally:
            jax.profiler.stop_trace()
        _report_window(gen, window_s, n, compiles.count - before, err)
        summary = tr.summarize(tr.find_xplane(log_dir),
                               (gen.label,) + tuple(gen.spans))
    used = sorted(summary.busy_ns)[:len(devices)]
    ctx = {"trace": summary, "devices": used, "gen": gen,
           "cfg": cfg, "traffic": traffic}
    metrics = {}
    for m in spec["per_layer"]:
        value = load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            print(f"trace: {m['name']} found nothing to read in this "
                  "trace and is left out", file=err)
    extra = {"calls": n,
             "device_extra": {"busy_s": summary.busy_s(used),
                              "window_s": summary.window_s},
             "breakdown": tr.breakdown(summary)}
    print(f"trace: window {summary.window_s:.3f} s, busy "
          f"{summary.busy_s(used):.3f} s on {used}", file=err)
    return metrics, extra
