#!/usr/bin/env python3
"""Rehearse one cell on several virtual CPU devices, in a process of its own.

    python tests/bench/on_devices.py --workload <name> --devices 4 \\
        --seed <n> --fault-seed <n> --control-seeds 1,2,3

The test process sees one CPU device, and a cell with ``chips: 4`` needs
four.  This sets ``--xla_force_host_platform_device_count`` before JAX
loads and runs, in this one process, what the tests run in process for a
one-chip cell: the rehearsal untraced and traced, the control on each
control seed, and one run under each of the cell's driver's planted faults
(``tests/bench/faults/<driver>.py``).  It prints one JSON object as its
last line; the tests judge what it holds.
"""

import argparse
import contextlib
import io
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
SECONDS = "0.5"     # each rehearsal's window, as the one-chip rehearsals run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--devices", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fault-seed", type=int, required=True)
    ap.add_argument("--control-seeds", required=True)
    args = ap.parse_args(argv)
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"),
        f"--xla_force_host_platform_device_count={args.devices}")))
    os.environ["JAX_PLATFORMS"] = "cpu"
    for p in (HERE, ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    import pytest

    jax.config.update("jax_cpu_enable_async_dispatch", False)
    import bench_cells
    from bench import control
    from bench.lib import harness

    cell = bench_cells.cells()[args.workload]
    out = {"devices": len(jax.devices()), "rehearsals": {}, "controls": {},
           "faults": {}}
    for trace in ("0", "1"):
        so, se = io.StringIO(), io.StringIO()
        with contextlib.redirect_stdout(so), contextlib.redirect_stderr(se):
            rc = harness.main(["--workload", args.workload, "--seed",
                               str(args.seed), "--seconds", SECONDS,
                               "--trace", trace, "--rehearse"])
        out["rehearsals"][trace] = {"rc": rc, "out": so.getvalue(),
                                    "err": se.getvalue()}
    for seed in args.control_seeds.split(","):
        out["controls"][seed] = control.read_control(args.workload, int(seed),
                                                     rehearse=True)
    for fault in bench_cells.faults_of(bench_cells.driver_of(cell)):
        run = argparse.Namespace(workload=args.workload, seed=args.fault_seed,
                                 seconds=float(SECONDS), trace=0)
        with pytest.MonkeyPatch.context() as mp:
            fault(mp)
            try:
                got = harness.run_cell(run, rehearse=True)
            except Exception as e:  # noqa: BLE001 - reported, judged by the test
                got = {"error": f"{type(e).__name__}: {e}"}
        out["faults"][fault.__name__] = got
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
