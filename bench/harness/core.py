"""What every cell shares: finding the cell's files by name, the device
check, the compile cache, tracing, metric readers and the result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its
configuration file names the ``system`` that serves it
(``bench/systems/<system>.py``); its traffic file is
``bench/traffic/<traffic>.json``; each metric is read by
``bench/metrics/<metric>.py``.  Nothing here knows a cell by name.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")


class BenchError(SystemExit):
    """A run that cannot measure: exits non-zero and prints no result."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise BenchError(f"no file {os.path.relpath(path, ROOT)}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A cell's entries and files, found by the names in BENCHMARK.json."""

    def __init__(self, name: str, bench_dir: str = BENCH,
                 benchmark: Optional[Dict] = None):
        self.bench_dir = bench_dir
        root = os.path.dirname(bench_dir)
        self.benchmark = benchmark or load_json(
            os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.benchmark["workloads"]}
        if name not in cells:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json; "
                             f"known: {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        confs = {c["name"]: c for c in self.benchmark["configs"]}
        self.config_entry = confs[self.entry["config"]]
        self.config = load_json(os.path.join(root,
                                             self.config_entry["file"]))
        self.traffic = load_json(os.path.join(
            bench_dir, "traffic", self.entry["traffic"] + ".json"))
        self.chips = int(self.entry["chips"])

    def metrics(self, kind: str) -> List[Dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.benchmark[kind]
                if self.name in m.get("workloads", [self.name])]

    def system(self):
        return load_module(os.path.join(
            self.bench_dir, "systems", self.config["system"] + ".py"),
            "bench_system_" + self.config["system"])

    def reader(self, metric: str) -> Callable:
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        metric + ".py"),
                           "bench_metric_" + metric.replace(".", "_")).read


def require_accelerator(chips: int):
    """The devices of the run: ``chips`` TPU chips, or an error."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (first device: "
                         f"{devs[0].platform}); the benchmark runs on a "
                         "chip only")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    from peaks import peaks_of
    peaks_of(devs[0].device_kind)
    return devs[:chips]


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache: JAX_COMPILATION_CACHE_DIR where
    it is set, else the fixed directory ``<checkout>/.jax_cache``."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA backend compilations, to show none happen in a window."""

    def __init__(self):
        from jax import monitoring
        self.count = 0

        def on_event(name, _secs, **_kw):
            if name.endswith("backend_compile_duration"):
                self.count += 1
        monitoring.register_event_duration_secs_listener(on_event)


class Run:
    """One run of one cell: what the cell's system module measures lands here,
    and the metric readers read it."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t_start: float, devices, compile_counter: "CompileCounter"):
        self.cell = cell
        self.compile_counter = compile_counter
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.t_start = t_start
        self.devices = devices
        self.t_setup_done: Optional[float] = None
        self.host: Dict = {}          # the system module's own records
        self.trace_summary = None     # tracing.TraceSummary, --trace 1
        self.compared: Dict[str, Dict] = {}
        self.correct = False
        self.attempted = 0
        self.failed = 0
        self.memory_peak_bytes: Optional[int] = None
        self._trace_dir: Optional[str] = None
        self._traced = False

    # -- timing ------------------------------------------------------------
    def setup_done(self) -> float:
        self.t_setup_done = time.time()
        return time.perf_counter()

    @property
    def setup_s(self) -> float:
        return self.t_setup_done - self.t_start

    # -- tracing -----------------------------------------------------------
    def span(self, name: str):
        """A host span on the profiler's clock, in traced runs only."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def traced(self):
        """The run's one traced slice (a no-op after the first, and in
        untraced runs)."""
        if not self.trace or self._traced:
            yield False
            return
        import jax
        from jax.profiler import ProfileOptions
        self._traced = True
        self._trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.traced"):
                yield True
        finally:
            jax.profiler.stop_trace()

    def reduce_trace(self) -> None:
        if self._trace_dir is None:
            return
        import shutil
        from harness import tracing
        try:
            self.trace_summary = tracing.reduce_dir(self._trace_dir,
                                                    len(self.devices))
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)

    # -- memory ------------------------------------------------------------
    def read_memory_peak(self) -> None:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None

    # -- the comparison -----------------------------------------------------
    def compare(self, name: str, value: float, limit: float) -> bool:
        """One number compared, with its limit; NaN fails."""
        ok = value <= limit
        if not math.isfinite(value):      # JSON has no infinity
            value = 1e300
        self.compared[name] = {"value": value, "limit": limit}
        return bool(ok)


def quantile(values, q: float) -> Optional[float]:
    """The q-quantile (0..1), interpolated between order statistics;
    None if there are no values."""
    values = list(values)
    return float(np.percentile(values, 100 * q)) if values else None


def device_info(run: Run) -> Dict:
    d = run.devices[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(run.devices),
            "memory_peak_bytes": run.memory_peak_bytes}
    if run.trace and run.trace_summary is not None:
        info["busy_s"] = run.trace_summary.busy_s
        info["window_s"] = run.trace_summary.window_s
    return info


def result_line(run: Run) -> Dict:
    kind = "per_layer" if run.trace else "end_to_end"
    metrics = {}
    for m in run.cell.metrics(kind):
        value = run.cell.reader(m["name"])(run)
        if value is None:
            continue
        if not math.isfinite(value):
            raise BenchError(f"metric {m['name']} read {value}")
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": bool(run.correct), "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics,
           "device": device_info(run)}
    if run.trace and run.trace_summary is not None:
        out["breakdown"] = run.trace_summary.breakdown()
    out["compared"] = run.compared
    return out


def emit(run: Run) -> Dict:
    out = result_line(run)
    print(f"setup_s {run.setup_s:.3f}; compilations inside the window: "
          f"{run.host.get('window_compiles')}", file=sys.stderr, flush=True)
    for name, c in run.compared.items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices) -> Dict:
    """Everything of a run after the look for the chip: serve the cell,
    check it, print and return the result line."""
    r = Run(cell, seed, seconds, trace, t_start, devices, CompileCounter())
    cell.system().run(r)
    r.reduce_trace()
    return emit(r)
