"""A cell joins the benchmark by new files and entries alone.

In a copy of the benchmark's files (``BENCHMARK.json`` and its ``paths``,
nothing of the repo besides), the files under ``joining/files`` are added,
none of them replacing a file of the copy, and the entries of
``joining/entries.json`` are appended to the copy's ``BENCHMARK.json``.  The
one touch of an existing entry is the new cells' names appended to the
``workloads`` of the end-to-end metric they report.  The two cells are

- a one-chip cell of the ``knn_batch`` driver on a second configuration;
- a four-chip cell of a new driver, ``knn_forest``, which subclasses
  ``knn_batch``'s and runs the ``forest`` engine, with its own control and
  planted faults.

The copy's own tests then run from the copy: its contract tests, and for
each new cell the rehearsals untraced and traced, the control and every
planted fault, each under the name it would have in the repo.
"""

import json
import os
import shutil
import subprocess
import sys
import xml.etree.ElementTree as ET

import bench_cells
from bench.lib import harness

JOINING = os.path.join(bench_cells.HERE, "joining")
FILES = os.path.join(JOINING, "files")
ENTRY_LISTS = ("configs", "workloads", "end_to_end", "per_layer")
RUNS = "tests/bench/test_bench_runs.py"
CONTRACT = "tests/bench/test_bench_contract.py"


def _tree(root):
    """Every file under ``root`` with its bytes, by relative path."""
    out = {}
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


def _copy_benchmark(dst):
    bench = harness.load_benchmark()
    os.makedirs(dst)
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), dst)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(harness.ROOT, p), os.path.join(dst, p),
                        ignore=shutil.ignore_patterns("__pycache__"))


def _join(dst):
    """Add the new files and entries; returns the new cells' names."""
    for rel, data in _tree(FILES).items():
        target = os.path.join(dst, rel)
        assert not os.path.exists(target), f"{rel} would replace a file"
        os.makedirs(os.path.dirname(target), exist_ok=True)
        with open(target, "wb") as f:
            f.write(data)
    with open(os.path.join(JOINING, "entries.json")) as f:
        entries = json.load(f)
    path = os.path.join(dst, "BENCHMARK.json")
    bench = harness.load_benchmark(dst)
    for key in ENTRY_LISTS:
        bench[key].extend(entries.get(key, []))
    for metric, cells in entries["join"].items():
        (m,) = [m for m in bench["end_to_end"] if m["name"] == metric]
        m["workloads"].extend(cells)
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    return [w["name"] for w in entries["workloads"]]


def _node_ids(dst, cells):
    """The copy's test cases of the new cells, as the repo would name
    them."""
    ids = []
    faults_dir = os.path.join(dst, "tests", "bench", "faults")
    for name, cell in bench_cells.cells(dst).items():
        if name not in cells:
            continue
        for trace in (0, 1):
            ids.append(f"{RUNS}::test_cell_rehearses_and_prints_a_well_"
                       f"formed_last_line[{name}-{trace}]")
        ids.append(f"{RUNS}::test_control_comes_out_not_correct[{name}]")
        faults = bench_cells.faults_of(bench_cells.driver_of(cell, dst),
                                       faults_dir)
        assert faults, name
        ids.extend(f"{RUNS}::test_planted_fault_turns_correct_false"
                   f"[{name}-{f.__name__}]" for f in faults)
    return ids


def test_a_one_chip_and_a_four_chip_cell_join_by_new_files_alone(tmp_path):
    dst = str(tmp_path / "checkout")
    _copy_benchmark(dst)
    before = _tree(dst)
    old = harness.load_benchmark(dst)
    cells = _join(dst)

    # no file of the copy changed but BENCHMARK.json, and there every entry
    # is as it was but the joined metric's list, which only grew at its end
    after = _tree(dst)
    assert {r: after[r] for r in before if r != "BENCHMARK.json"} \
        == {r: d for r, d in before.items() if r != "BENCHMARK.json"}
    new = harness.load_benchmark(dst)
    touched = []
    for key in old:
        if key not in ENTRY_LISTS:
            assert new[key] == old[key], key
            continue
        assert len(new[key]) >= len(old[key])
        for a, b in zip(old[key], new[key]):
            if a != b:
                assert set(a) == set(b) and all(
                    a[k] == b[k] for k in a if k != "workloads"), a["name"]
                assert b["workloads"][:len(a["workloads"])] == a["workloads"]
                assert set(b["workloads"][len(a["workloads"]):]) <= set(cells)
                touched.append(a["name"])
    assert touched == ["batch_qps"]
    assert sorted(w["chips"] for w in new["workloads"]
                  if w["name"] in cells) == [1, 4]

    ids = _node_ids(dst, cells)
    xml = tmp_path / "joined.xml"
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", "-c", os.devnull,
         "--rootdir", dst, "--junitxml", str(xml), CONTRACT, *ids],
        cwd=dst, env=bench_cells.system_env(), capture_output=True,
        text=True, timeout=1200)
    assert p.returncode == 0, (p.stdout[-6000:], p.stderr[-3000:])
    print(p.stdout[-3000:])
    cases = ET.parse(xml).getroot().iter("testcase")
    passed = {f"{c.get('classname').replace('.', '/')}.py::{c.get('name')}"
              for c in cases if not len(c)}
    assert set(ids) <= passed, sorted(set(ids) - passed)
    assert any(x.startswith(CONTRACT) for x in passed)
