#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell, its configuration, its traffic and
its metrics are read from ``BENCHMARK.json`` and the files under ``bench/``
(see ``bench/lib/harness.py``).  On any platform other than TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result;
``--rehearse`` lets the tests run a cell on the CPU at tiny sizes.
"""

import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.lib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
