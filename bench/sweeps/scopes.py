#!/usr/bin/env python3
"""Device seconds by ``jax.named_scope`` scope in traced calls of one cell:
not a metric, the check that the scopes cover a program's device time.

    python bench/sweeps/scopes.py --workload photo10m-knn-batch \\
        --seed 2147483821 --calls 1 --prefix knn. --module _chunk_round

It sets the cell up as a run does, traces ``--calls`` whole calls, reads
the trace with ``bench.lib.trace.load_trace`` and prints one JSON line:
each scope named in the events' ``tf_op`` paths that starts with
``--prefix``, with its device seconds (``spans.scope_seconds``); their
sum and union; the device seconds of the programs whose name holds
``--module``; and how long ``load_trace`` took.  A TPU is required.
"""

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time
import types

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.lib import harness, readers, spans, trace as tr  # noqa: E402


def scope_names(data: tr.TraceData, prefix: str) -> list:
    """The path components starting with ``prefix`` in the device events'
    scope paths, sorted."""
    return sorted({part for evs in data.device.values() for e in evs
                   for part in e.scope.split("/") if part.startswith(prefix)})


def read_scopes(data: tr.TraceData, wall_s: float, prefix: str,
                module: str) -> dict:
    red = tr.reduce_trace(data, window_span=harness.CALL_SPAN)
    run = types.SimpleNamespace(trace=red, trace_data=data)
    by_scope = {s: spans.scope_seconds(run, s)
                for s in scope_names(data, prefix)}
    module_s = readers.device_seconds(run, line=tr.MODULES_LINE,
                                      patterns=(module,))
    total = sum(v for v in by_scope.values() if v)
    union = spans.scope_seconds(run, prefix)
    return {
        "wall_s": wall_s,
        "busy_s": red["busy_s"],
        "window_s": red["window_s"],
        "module_s": module_s,
        "scopes_s": by_scope,
        "scopes_sum_s": total,
        "scopes_union_s": union,
        "covered": None if not module_s or union is None
        else union / module_s,
        "share_of_wall": {s: readers.share(v, wall_s)
                          for s, v in by_scope.items()},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=1)
    ap.add_argument("--prefix", default="knn.")
    ap.add_argument("--module", default="_chunk_round")
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        harness.log("scopes: needs a TPU.  No CPU fallback.")
        return 1
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    harness.log(f"[setup] {harness.use_compile_cache()}")
    spec = harness.resolve(harness.load_benchmark(), args.workload)
    drv = harness.load_module(os.path.join(
        harness.BENCH, "drivers", spec["traffic"]["driver"] + ".py"))
    d = drv.Driver(spec["config"], spec["traffic"], args.seed,
                   log=harness.log)
    d.setup(lambda name: contextlib.nullcontext())
    with tempfile.TemporaryDirectory(prefix="bench-scopes-") as tmp:
        jax.profiler.start_trace(tmp)
        wall = 0.0
        try:
            for i in range(args.calls):
                with jax.profiler.TraceAnnotation(harness.CALL_SPAN):
                    t = time.perf_counter()
                    d.call(i)
                    wall += time.perf_counter() - t
        finally:
            jax.profiler.stop_trace()
        t = time.perf_counter()
        data = tr.load_trace(tmp)
        load_s = time.perf_counter() - t
    out = read_scopes(data, wall, args.prefix, args.module)
    out["load_trace_s"] = load_s
    out["device_events"] = sum(len(v) for v in data.device.values())
    out["workload"], out["seed"], out["calls"] = (args.workload, args.seed,
                                                  args.calls)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
