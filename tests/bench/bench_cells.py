"""What the benchmark's tests run for each cell, found by name from
``BENCHMARK.json`` and the files under the root, so that a cell, a driver
or a four-chip cell joins the tests by new files alone:

- the cell's driver is its traffic file's ``driver``;
- the driver's planted faults are ``FAULTS`` in
  ``tests/bench/faults/<driver>.py`` (none where the file is missing: the
  contract test names the driver);
- a cell of more than one chip is rehearsed by ``on_devices.py`` in a
  process of its own that sees that many CPU devices.
"""

import json
import os

from bench.lib import harness

HERE = os.path.dirname(os.path.abspath(__file__))
FAULTS_DIR = os.path.join(HERE, "faults")


def cells(root: str = harness.ROOT) -> dict:
    """Each cell's entry in ``BENCHMARK.json``, by name."""
    return {w["name"]: w for w in harness.load_benchmark(root)["workloads"]}


def driver_of(cell: dict, root: str = harness.ROOT):
    """The driver named by the cell's traffic file; None where the file
    cannot be read (the contract test names what is missing)."""
    path = os.path.join(root, "bench", "traffic", cell["traffic"] + ".json")
    try:
        with open(path) as f:
            return json.load(f)["driver"]
    except (OSError, ValueError, KeyError):
        return None


def faults_of(driver, faults_dir: str = FAULTS_DIR) -> list:
    """The driver's planted faults, in the order of its ``FAULTS``."""
    path = os.path.join(faults_dir, f"{driver}.py")
    if driver is None or not os.path.isfile(path):
        return []
    return list(harness.load_module(path).FAULTS)


def system_env() -> dict:
    """The environment of a child process that runs the system under test
    on the CPU: ``repro`` importable from wherever this process found it."""
    import repro

    src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(filter(None, (src,
                                                      env.get("PYTHONPATH"))))
    return env
