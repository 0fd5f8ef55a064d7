"""Every cell rehearsed end to end on the CPU at tiny size (Pallas in
interpret mode), the refusals of the command, the controls, and faults
planted under the timed path that must turn ``correct`` false.

A one-chip cell runs in this process.  A cell of more than one chip runs
all of that in one process of its own that sees as many CPU devices
(``on_devices.py``); each case here judges its part.  The faults of a cell
are those of its driver (``tests/bench/faults/<driver>.py``)."""

import argparse
import functools
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import bench_cells
from bench import control
from bench.lib import harness

ROOT = harness.ROOT
CELL = bench_cells.cells()
CELLS = list(CELL)
REHEARSAL_SEED = 4294967311
FAULT_SEED = 11
CONTROL_SEEDS = (1, 2, 3)
FAULTS = {w: {f.__name__: f for f in bench_cells.faults_of(
    bench_cells.driver_of(CELL[w]))} for w in CELLS}


def _args(workload, seed=3_000_000_019, seconds=0.5, trace=0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)


def _run(workload, **kw):
    return harness.run_cell(_args(workload, **kw), rehearse=True)


def _assert_well_formed(result, workload, trace):
    bench = harness.load_benchmark()
    spec = harness.resolve(bench, workload)
    assert list(result)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in result
    assert result["attempted"] > 0 and result["failed"] == 0
    dev = result["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in spec[kind]}
    assert set(result["metrics"]) <= set(allowed)
    for name, m in result["metrics"].items():
        assert m["unit"] == allowed[name] and np.isfinite(m["value"])
    if not trace:
        # end-to-end metrics come from the host clock: all present
        assert set(result["metrics"]) == set(allowed)
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}


@functools.lru_cache(maxsize=None)
def _on_devices(workload):
    """The rehearsals, controls and faults of a cell of several chips, from
    one process that sees that many CPU devices."""
    p = subprocess.run(
        [sys.executable, os.path.join(bench_cells.HERE, "on_devices.py"),
         "--workload", workload, "--devices", str(CELL[workload]["chips"]),
         "--seed", str(REHEARSAL_SEED), "--fault-seed", str(FAULT_SEED),
         "--control-seeds", ",".join(map(str, CONTROL_SEEDS))],
        cwd=ROOT, env=bench_cells.system_env(), capture_output=True,
        text=True, timeout=1200)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _several_chips(workload) -> bool:
    return CELL[workload]["chips"] > 1


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearses_and_prints_a_well_formed_last_line(
        workload, trace, capsys):
    if _several_chips(workload):
        got = _on_devices(workload)["rehearsals"][str(trace)]
        rc, out, err = got["rc"], got["out"], got["err"]
    else:
        rc = harness.main(["--workload", workload, "--seed",
                           str(REHEARSAL_SEED), "--seconds", "0.5",
                           "--trace", str(trace), "--rehearse"])
        out, err = capsys.readouterr()
    assert rc == 0, err[-4000:]
    result = json.loads(out.strip().splitlines()[-1])
    _assert_well_formed(result, workload, trace)
    assert result["device"]["count"] == CELL[workload]["chips"]
    assert result["correct"] is True
    # the compared numbers close standard error, each beside its limit
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert all(line.startswith("check ") and " limit=" in line
               for line in tail)
    assert "compiles_in_window=0 " in err


def _command(args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_command_refuses_the_cpu_without_the_rehearsal_switch():
    p = _command(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_command_refuses_a_directory_without_the_system(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in harness.load_benchmark()["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                  "--trace", "0", "--rehearse"], cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("workload", CELLS)
def test_control_comes_out_not_correct(workload):
    for seed in CONTROL_SEEDS:
        if _several_chips(workload):
            got = _on_devices(workload)["controls"][str(seed)]
        else:
            got = control.read_control(workload, seed, rehearse=True)
        assert any(c["value"] > c["limit"] for c in got.values()), got


# ---------------------------------------------------------------------------
# Faults planted under the timed path (tests/bench/faults/<driver>.py)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("workload,fault", [
    (w, f) for w in CELLS for f in FAULTS[w]])
def test_planted_fault_turns_correct_false(workload, fault, monkeypatch):
    if _several_chips(workload):
        result = _on_devices(workload)["faults"][fault]
        assert "error" not in result, result
    else:
        FAULTS[workload][fault](monkeypatch)
        result = _run(workload, seed=FAULT_SEED)
    assert result["correct"] is False, result["checks"]
    # the line stays strict JSON however far off a reading is
    json.loads(json.dumps(result), parse_constant=pytest.fail)
