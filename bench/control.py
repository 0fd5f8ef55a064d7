#!/usr/bin/env python3
"""Read the control of a cell's comparison on several seeds.

    python bench/control.py --workload <name> --seeds 1,2,3

For each seed it makes the cell's data as a run would, puts the control of
the cell's driver (``bench/controls/<driver>.py``, built on
``bench.lib.controls``) in the program's place (the reference one precision
below the configuration's), and compares it with the plain reference by the
cell's own comparison.  It prints each number beside its limit; the control
has to exceed a limit on every seed.  The benchmark's runs never run this.
A TPU is required unless ``--rehearse`` (tests, tiny sizes).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.lib import harness  # noqa: E402


def read_control(workload: str, seed: int, rehearse: bool = False) -> dict:
    """The control's numbers for one seed: {name: {"value", "limit"}}.

    The control of a driver is ``bench/controls/<driver>.py``: ``read(d,
    seed)`` gives the compared numbers for the driver ``d`` once it has
    made the seed's data."""
    spec = harness.resolve(harness.load_benchmark(), workload, rehearse)
    cfg, traffic = spec["config"], spec["traffic"]
    name = traffic["driver"]
    drv = harness.load_module(os.path.join(harness.BENCH, "drivers",
                                           name + ".py"))
    ctl = harness.load_module(os.path.join(harness.BENCH, "controls",
                                           name + ".py"))
    d = drv.Driver(cfg, traffic, seed, log=harness.log)
    d.make_data()
    got = ctl.read(d, seed)
    return {n: {"value": got[n], "limit": cfg["limits"][n]}
            for n in drv.COMPARED}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        harness.log("control: needs a TPU.  No CPU fallback.")
        return 1
    out = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        got = read_control(args.workload, seed, args.rehearse)
        out[seed] = got
        fails = [n for n, c in got.items() if c["value"] > c["limit"]]
        harness.log(f"control seed={seed} {time.perf_counter() - t:.1f}s "
                    + " ".join(f"{n}={c['value']!r}(limit {c['limit']!r})"
                               for n, c in got.items())
                    + f" -> {'not correct' if fails else 'CORRECT'}")
    print(json.dumps({"workload": args.workload, "controls": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
