"""The run of one cell: set-up, the measured window, the comparison.

``run_cell`` drives one workload of ``BENCHMARK.json`` from the files the
names point at:

- ``bench/configs/<config>.json``: the configuration as run;
- ``bench/traffic/<traffic>.json``: the mix; its ``kind`` names the
  driver in ``bench/common/kinds/`` and the rest are that driver's
  parameters;
- ``bench/limits/<workload>.json``: the limit of each number compared;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric.

A driver exposes ``setup()``, ``unit(i)`` (one whole unit of work),
``after_window()``, ``check()`` (the numbers compared, units attempted,
units failed), ``end_to_end(records)``, ``records`` (what the per-layer
readers read) and ``close()``.  The window starts a new unit only while
the elapsed time plus the last unit's duration stays within ``seconds``,
and always runs at least one.
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import shutil
import tempfile
import time
from collections import defaultdict
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent


def read_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def spec() -> Dict:
    return read_json(CHECKOUT / "BENCHMARK.json")


def cell_files(workload: str, bench: Optional[Dict] = None) -> Dict:
    bench = bench or spec()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return {
        "workload": w,
        "config": read_json(CHECKOUT / cfg["file"]),
        "traffic": read_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        "limits": read_json(BENCH / "limits" / f"{workload}.json"),
        "end_to_end": [m for m in bench["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in bench["per_layer"]
                      if workload in m.get("workloads", [workload])],
    }


class Recorder:
    """Host spans kept in memory.  Each is also a ``TraceAnnotation`` so
    the profiler's trace holds it on the device's clock."""

    def __init__(self):
        self.spans: Dict[str, List[float]] = defaultdict(list)

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation("bench." + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans[name].append(time.perf_counter() - t0)


class CompileCounter:
    """Counts the executables JAX built or loaded from its cache."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == self.EVENT:
            self.count += 1


def window(unit: Callable[[int], None], *, seconds: float,
           trace_dir: Optional[str] = None) -> Dict:
    """Run whole units; trace the first when ``trace_dir`` is given.
    The profiler's start and stop are left out of the window's time."""
    import jax
    n, last, paused = 0, 0.0, 0.0
    t0 = time.perf_counter()
    while n == 0 or time.perf_counter() - t0 - paused + last <= seconds:
        traced = trace_dir is not None and n == 0
        if traced:
            p0 = time.perf_counter()
            jax.profiler.start_trace(trace_dir)
            paused += time.perf_counter() - p0
        u0 = time.perf_counter()
        if traced:
            with jax.profiler.TraceAnnotation("bench.window"):
                unit(n)
        else:
            unit(n)
        last = time.perf_counter() - u0
        if traced:
            p0 = time.perf_counter()
            jax.profiler.stop_trace()
            paused += time.perf_counter() - p0
        n += 1
    return {"units": n, "seconds": time.perf_counter() - t0 - paused}


def driver(kind: str):
    return importlib.import_module(f"bench.common.kinds.{kind}")


def metric_reader(name: str):
    """``bench/metrics/<name>.py`` (a name may hold dots, so it is loaded
    by path, not as a dotted module)."""
    path = BENCH / "metrics" / f"{name}.py"
    spec_ = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(mod)
    return mod


def run_cell(workload: str, *, seed: int, seconds: float, trace: bool,
             t_start: float, files: Optional[Dict] = None,
             chips: int = 1) -> Dict:
    """One run of one cell; returns the result line's object."""
    from bench.common import device, trace as tracemod

    files = files or cell_files(workload)
    traffic = files["traffic"]
    work = Path(tempfile.mkdtemp(prefix="bench-"))
    rec = Recorder()
    compiles = CompileCounter()
    d = None
    try:
        d = driver(traffic["kind"]).Driver(
            config=files["config"], traffic=traffic, seed=seed,
            root=work / "ckpt", rec=rec)
        d.setup()
        setup_s = time.time() - t_start
        rec.spans.clear()               # the warm-up's spans are set-up
        trace_dir = str(work / "trace") if trace else None
        c0 = compiles.count
        win = window(d.unit, seconds=seconds, trace_dir=trace_dir)
        in_window = compiles.count - c0
        dev = device.info(chips)
        dev["memory_peak_bytes"] = device.peak_bytes()
        d.after_window()
        checks, attempted, failed = d.check()
        records = dict(d.records, window_s=win["seconds"],
                       units=win["units"], chips=dev["count"],
                       device_kind=dev["kind"], spans=dict(rec.spans))
        out = {"attempted": attempted, "failed": failed}
        if trace:
            reduced = tracemod.reduce(tracemod.load(
                tracemod.find_xplane(trace_dir)))
            records["trace"] = reduced
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]
            metrics = {}
            for m in files["per_layer"]:
                value = metric_reader(m["name"]).read(records)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            out["breakdown"] = reduced["breakdown"]
        else:
            values = dict(d.end_to_end(records), setup_s=setup_s)
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in files["end_to_end"]}
        limits = files["limits"]
        compared = {k: {"value": v, "limit": limits[k]}
                    for k, v in checks.items()}
        correct = all(v["value"] <= v["limit"] for v in compared.values())
        return {"correct": correct, **out, "metrics": metrics,
                "device": dev, "compiles_in_window": in_window,
                "checks": compared}
    finally:
        if d is not None:
            d.close()
        shutil.rmtree(work, ignore_errors=True)
