"""The benchmark's run of one cell: set-up, measured window, optional trace,
reference check, result line.

Everything that belongs to one configuration, one traffic mix or one metric
is found by name from ``BENCHMARK.json``:

- ``bench/configs/<config>.json``: the deployment's sizes, its guarantees
  and the limits of the comparison that decides ``correct``;
- ``bench/traffic/<traffic>.json``: the mix's parameters, among them the
  ``driver`` that runs it;
- ``bench/drivers/<driver>.py``: builds the system under test, makes one
  call of the window, and checks the window's answers against the plain
  reference (general code shared by every cell of that kind);
- ``bench/metrics/<metric>.py``: a reader ``read(run)`` that returns the
  metric's value, or None where it finds nothing to read;
- ``bench/controls/<driver>.py``: the control of the driver's comparison,
  which ``bench/control.py`` reads and no run does.

Adding a cell or a metric is therefore new files and new entries only.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

__all__ = ["ROOT", "main", "load_benchmark", "load_module", "RunRecord",
           "CallRecord", "BenchError"]

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")   # fixed: part of the cache key
CALL_SPAN = "bench.call"        # host span around every traced call
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """The run cannot produce a result (wrong platform, missing file)."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Finding the pieces by name
# ---------------------------------------------------------------------------
def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise BenchError(f"missing file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import the Python file at ``path`` under a name of its own (metric
    files carry dots in their names, so they are loaded by path)."""
    if not os.path.isfile(path):
        raise BenchError(f"missing file {os.path.relpath(path, ROOT)}")
    name = "bench_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, BENCH))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _overlay(base: dict, rehearse: bool) -> dict:
    """The file as run: with ``rehearse``, its ``rehearsal`` block replaces
    the keys it names (tiny sizes that a CPU test can hold)."""
    out = {k: v for k, v in base.items() if k != "rehearsal"}
    if rehearse:
        out.update(base.get("rehearsal", {}))
    return out


def resolve(bench: dict, workload: str, rehearse: bool = False) -> dict:
    """The cell ``workload`` with its configuration, traffic, driver and
    metric entries resolved from their files."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    cfg = _overlay(_read_json(os.path.join(
        BENCH, "configs", cell["config"] + ".json")), rehearse)
    traffic = _overlay(_read_json(os.path.join(
        BENCH, "traffic", cell["traffic"] + ".json")), rehearse)

    def applies(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell,
        "config": cfg,
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


# ---------------------------------------------------------------------------
# What a run records, for the metric readers
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class CallRecord:
    index: int
    start_s: float          # host clock, relative to the window's start
    wall_s: float
    work: int               # answers the call produced (queries, histograms)
    stats: Any              # the program's per-call SearchStats
    traced: bool


@dataclasses.dataclass
class RunRecord:
    workload: str
    setup_s: float
    setup_phases: Dict[str, float]
    calls: List[CallRecord]
    window_s: float
    device_kind: str
    driver: Any
    trace: Optional[dict] = None        # reduce_trace(...) of the traced calls
    trace_data: Any = None              # the TraceData it was reduced from

    @property
    def traced_calls(self) -> List[CallRecord]:
        return [c for c in self.calls if c.traced]


class _CompileCounter:
    """Counts executables that JAX compiles or loads from its persistent
    cache while active (one event per executable either way)."""

    def __init__(self):
        self.count = 0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.count += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self)
        return False


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
def _devices_or_fail(chips: int, rehearse: bool):
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not rehearse:
        raise BenchError(f"needs a TPU; JAX found {dev.platform} "
                         f"({dev.device_kind}).  No CPU fallback.")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chip(s); "
                         f"JAX sees {len(devices)}")
    return devices[:chips]


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at ``CACHE_DIR`` in the
    checkout, whatever the environment names: the program takes the
    directory that JAX_COMPILATION_CACHE_DIR gives it."""
    import jax

    from repro.api import enable_compile_cache

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return enable_compile_cache(CACHE_DIR)


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def _window(driver, seconds: float, trace_seconds: float, trace_dir):
    """Calls back to back until ``seconds`` have passed; the window ends
    when the last call completes.  With ``trace_dir`` the calls of the
    first ``trace_seconds`` (at least one) run under the profiler."""
    import jax

    calls: List[CallRecord] = []
    tracing = trace_dir is not None
    if tracing:
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    i = 0
    try:
        while True:
            ctx = (jax.profiler.TraceAnnotation(CALL_SPAN) if tracing
                   else contextlib.nullcontext())
            c0 = time.perf_counter()
            with ctx:
                work, stats = driver.call(i)
            c1 = time.perf_counter()
            calls.append(CallRecord(i, c0 - t0, c1 - c0, work, stats,
                                    tracing))
            i += 1
            if tracing and c1 - t0 >= trace_seconds:
                jax.profiler.stop_trace()
                tracing = False
            if c1 - t0 >= seconds:
                break
    finally:
        if tracing:
            jax.profiler.stop_trace()
    return calls, calls[-1].start_s + calls[-1].wall_s


def run_cell(args, *, rehearse: bool) -> dict:
    """One run of one cell; returns the result object (the last line)."""
    t_start = time.perf_counter()
    bench = load_benchmark()
    spec = resolve(bench, args.workload, rehearse)
    cell, cfg, traffic = spec["cell"], spec["config"], spec["traffic"]
    driver_mod = load_module(os.path.join(
        BENCH, "drivers", traffic["driver"] + ".py"))
    readers = {m["name"]: load_module(os.path.join(
        BENCH, "metrics", m["name"] + ".py"))
        for m in spec["end_to_end"] + spec["per_layer"]}

    src = os.path.join(ROOT, "src")
    if os.path.isdir(os.path.join(src, "repro")) and src not in sys.path:
        sys.path.insert(0, src)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise BenchError(f"the system under test is not importable: {e}")
    devices = _devices_or_fail(int(cell["chips"]), rehearse)
    dev = devices[0]
    if not rehearse:
        log(f"[setup] {use_compile_cache()}")
    log(f"[setup] {cell['name']}: {dev.platform} {dev.device_kind} "
        f"x{len(devices)} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace}")

    phases: Dict[str, float] = {}

    @contextlib.contextmanager
    def phase(name: str):
        t = time.perf_counter()
        yield
        phases[name] = phases.get(name, 0.0) + time.perf_counter() - t

    driver = driver_mod.Driver(cfg, traffic, args.seed, log=log)
    driver.setup(phase)
    setup_s = time.perf_counter() - t_start
    log(f"[setup] setup_s={setup_s:.3f} " + " ".join(
        f"{k}_s={v:.3f}" for k, v in phases.items()))

    audit0 = driver.compile_count()
    trace_dir = None
    tmp = None
    if args.trace:
        tmp = tempfile.TemporaryDirectory(prefix="bench-trace-")
        trace_dir = tmp.name
    try:
        with _CompileCounter() as counter:
            calls, window_s = _window(driver, float(args.seconds),
                                      float(traffic.get("trace_seconds", 1)),
                                      trace_dir)
        audit = driver.compile_count() - audit0
        memory_peak = _memory_peak(devices)
        log(f"[window] calls={len(calls)} window_s={window_s:.3f} "
            f"work={sum(c.work for c in calls)} "
            f"call_wall_s={[round(c.wall_s, 3) for c in calls]}")
        log(f"[window] compiles_in_window={counter.count} "
            f"program_audit_compiles={audit} memory_peak_bytes={memory_peak}")
        record = RunRecord(
            workload=cell["name"], setup_s=setup_s, setup_phases=phases,
            calls=calls, window_s=window_s, device_kind=dev.device_kind,
            driver=driver,
        )
        if trace_dir is not None:
            from bench.lib.trace import load_trace, reduce_trace

            t = time.perf_counter()
            record.trace_data = load_trace(trace_dir)
            try:
                record.trace = reduce_trace(record.trace_data,
                                            window_span=CALL_SPAN)
            except ValueError as e:
                if not rehearse:
                    raise BenchError(f"trace: {e}")
                # the CPU runs its operations on host threads: a rehearsal
                # has no device plane, and the trace's metrics stay silent
                log(f"[trace] {e}")
                record.trace_data = None
            else:
                log(f"[trace] traced_calls={len(record.traced_calls)} "
                    f"busy_s={record.trace['busy_s']:.6f} "
                    f"window_s={record.trace['window_s']:.6f} "
                    f"read_s={time.perf_counter() - t:.3f}")
    finally:
        if tmp is not None:
            tmp.cleanup()

    # metrics first: readers may look at the shapes and stats in run.driver,
    # which release() drops with the program's state
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in spec[kind]:
        value = readers[m["name"]].read(record)
        if value is None:
            log(f"[metric] {m['name']}: nothing to read")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    driver.release()
    record.trace_data = None
    gc.collect()
    t = time.perf_counter()
    checks, attempted, failed = driver.check(calls)
    log(f"[check] reference_s={time.perf_counter() - t:.3f}")
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())
    for name, c in checks.items():
        if not math.isfinite(c["value"]):   # strict JSON has no inf or NaN
            c["value"] = sys.float_info.max
        log(f"check {name}={c['value']!r} limit={c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")

    result = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(memory_peak),
        },
    }
    if record.trace is not None:
        result["device"]["busy_s"] = record.trace["busy_s"]
        result["device"]["window_s"] = record.trace["window_s"]
        result["breakdown"] = {
            "device_ops": record.trace["op_s"],
            "idle_gaps": record.trace["idle_by_host"],
        }
    result["checks"] = checks
    return result


def main(argv=None, *, run: Callable = run_cell) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once and print its result line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="allow the CPU and run the files' rehearsal sizes "
                         "(tests only; results are not measurements)")
    args = ap.parse_args(argv)
    try:
        result = run(args, rehearse=args.rehearse)
    except BenchError as e:
        log(f"bench: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0
