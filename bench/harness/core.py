"""One run of one cell: set-up, the measured window, the check, the line.

``run_cell`` is the whole run after the command line: the driver builds
the cell's data or weights from the seed and warms every shape it will
use (set-up), whole iterations run until ``seconds`` have passed (the
window), the program's state is freed and the driver compares what the
window produced with its plain reference (``correct``), and the result
line is assembled. With ``trace`` the profiler records the window (or
its first ``trace_iterations`` iterations, a traffic parameter) and the
cell's per-layer metrics are reduced from that trace.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

from bench import BENCH_DIR, ROOT
from bench.harness import peaks as peaks_lib
from bench.harness import trace as trace_lib

# fired for every program JAX hands to XLA, compiled or read from the
# persistent cache; the second event marks the reads
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


# ---------------------------------------------------------------------------
# finding the parts of a cell by name
# ---------------------------------------------------------------------------

def benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def load_json(kind: str, name: str) -> dict:
    with open(BENCH_DIR / kind / f"{name}.json") as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = BENCH_DIR / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_parts(bench: dict, workload: str):
    """(cell, config, traffic, end_to_end metrics, per_layer metrics,
    limits). ``limits/<cell>.json`` holds the limit of each number the
    check compares; a number without one is compared exactly (limit 0)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]
    limits = (load_json("limits", workload)
              if (BENCH_DIR / "limits" / f"{workload}.json").is_file() else {})
    return (cell, load_json("configs", cell["config"]),
            load_json("traffic", cell["traffic"]),
            mine(bench["end_to_end"]), mine(bench["per_layer"]), limits)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared, with its limit: correct iff value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class RunData:
    """What a per-layer metric's reducer reads."""
    config: dict
    traffic: dict
    peak: Optional[dict]
    records: List[dict]          # one per traced iteration, from the driver
    trace: Optional[trace_lib.Trace]


def devices_or_fail(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devices[0].platform!r}); "
                     f"this benchmark runs only on a chip")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class _CompileCounter:
    """Inside ``with``, counts the programs JAX loads and how many of them
    it compiles rather than reads from the persistent cache: none should
    compile in the window, since set-up warmed every shape."""

    def __enter__(self):
        import jax
        self.programs = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._program)
        jax.monitoring.register_event_listener(self._hit)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._program)
        jax.monitoring.unregister_event_listener(self._hit)

    def _program(self, event, duration, **kw):
        if event == BACKEND_COMPILE_EVENT:
            self.programs += 1

    def _hit(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    @property
    def compiled(self) -> int:
        return self.programs - self.hits


def run_cell(cell: dict, config: dict, traffic: dict, e2e: List[dict],
             per_layer: List[dict], limits: Dict[str, float], seed: int,
             seconds: float, trace: bool,
             t_process: float, require_chip: bool = True,
             log: Callable[[str], None] = None, driver_cls=None) -> dict:
    """Everything after the command line. Returns the result dict, whose
    ``checks`` key comes last. ``driver_cls`` stands in for the traffic's
    driver (the controls, ``bench/control.py``)."""
    import jax

    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    if require_chip:
        devices = devices_or_fail(int(cell["chips"]))
    else:
        devices = jax.devices()
    kind = devices[0].device_kind
    peak = peaks_lib.peaks(kind) if (trace and require_chip) else None

    if driver_cls is None:
        driver_cls = load_module("drivers", traffic["driver"]).Driver
    driver = driver_cls(config, traffic, seed, log)
    driver.setup()
    setup_s = time.perf_counter() - t_process
    log(f"[bench] {cell['name']}: set-up {setup_s:.3f} s")

    limit = traffic.get("trace_iterations") if trace else None
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    with _CompileCounter() as compiles, \
            jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
        t0 = time.perf_counter()
        n = 0
        while True:
            driver.step()
            n += 1
            if time.perf_counter() - t0 >= seconds or (limit and n >= limit):
                break
        window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    log(f"[bench] window {window_s:.3f} s, {n} iterations; inside it "
        f"{compiles.programs} programs loaded, {compiles.compiled} of them "
        f"compiled")

    mem = memory_peak_bytes(devices)
    metrics: Dict[str, Any] = {}
    if not trace:
        values = dict(driver.end_to_end(window_s), setup_s=setup_s)
        for m in e2e:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    records = driver.records()
    driver.release()
    gc.collect()
    t_check = time.perf_counter()
    checks = [Check(name, value, float(limits.get(name, 0.0)))
              for name, value in driver.check().items()]
    log(f"[bench] check {time.perf_counter() - t_check:.3f} s")

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem}
    extra: Dict[str, Any] = {}
    if trace:
        t_trace = time.perf_counter()
        try:
            tr = trace_lib.load(trace_lib.find_xplane(log_dir))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        device["busy_s"] = trace_lib.busy_s(tr)
        device["window_s"] = trace_lib.window_s(tr)
        data = RunData(config, traffic, peak, records, tr)
        for m in per_layer:
            value = load_module("metrics", m["name"]).reduce(data)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        extra["breakdown"] = trace_lib.breakdown(tr)
        log(f"[bench] trace read and reduced in "
            f"{time.perf_counter() - t_trace:.3f} s")
    attempted, failed = driver.counts()
    result = {"correct": all(c.ok for c in checks), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device,
              **extra, "window_programs": compiles.programs,
              "window_compiles": compiles.compiled,
              "checks": {c.name: {"value": c.value, "limit": c.limit}
                         for c in checks}}
    for c in checks:
        log(f"check {c.name}: {c.value!r} (limit {c.limit!r})"
            f"{'' if c.ok else '  FAILED'}")
    return result


def main(argv=None, t_process: Optional[float] = None) -> int:
    import argparse

    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"bench: the repository's src/ is not next to bench/ ({e})",
              file=sys.stderr)
        return 2
    parts = cell_parts(benchmark(), args.workload)
    cache = enable_compile_cache()
    import jax
    # every program goes into the persistent cache, however fast it
    # compiled, so that no run after the first compiles anything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"[bench] compile cache {cache}", file=sys.stderr, flush=True)
    try:
        result = run_cell(*parts, args.seed, args.seconds, bool(args.trace),
                          t_process)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0
