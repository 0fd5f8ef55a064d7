"""BENCHMARK.json keeps to the benchmark's contract, and every piece it names
has its file."""

import json
import os
import re

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and \
        "\n" not in text and "\t" not in text


def test_top_level_keys_and_command(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bench["command"]) <= 32
    assert all(_line(w) for w in bench["command"])
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    for w in bench["command"][1:]:
        if "/" in w:   # a file of the repo named by the command
            assert any(w.startswith(p + "/") for p in bench["paths"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_run_seconds_fits_a_full_check_of_24_cells(bench):
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    cells = 24
    total = (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200
    assert total <= 43200


def test_names_units_and_entry_keys(bench):
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) or k == "dim"
                       for k in c["reduced"])
        names.append(c["name"])
    assert len(set(names)) == len(names)
    cells = []
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        cells.append(w["name"])
    assert len(set(cells)) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) \
        == len(cells)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(cells) // 2)
    metrics = []
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        metrics.append(m)
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
        metrics.append(m)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in bench["end_to_end"])


def test_every_piece_has_its_file(bench):
    b = os.path.join(ROOT, "bench")
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used
        assert c["file"] == f"bench/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert "limits" in cfg
    for w in bench["workloads"]:
        with open(os.path.join(b, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert os.path.isfile(os.path.join(b, "drivers",
                                           traffic["driver"] + ".py"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.isfile(os.path.join(b, "metrics", m["name"] + ".py"))


def test_every_driver_has_its_control_and_faults(bench):
    """Each driver that a cell's traffic names brings its control,
    ``bench/controls/<driver>.py`` with ``read(driver, seed)``, and its
    planted faults, ``tests/bench/faults/<driver>.py`` with a non-empty
    ``FAULTS``: the tests find both by the driver's name."""
    import bench_cells
    from bench.lib import harness

    missing = []
    for w in bench["workloads"]:
        driver = bench_cells.driver_of(w, ROOT)
        ctl = os.path.join(ROOT, "bench", "controls", f"{driver}.py")
        if not os.path.isfile(ctl) or not callable(
                getattr(harness.load_module(ctl), "read", None)):
            missing.append(f"{w['name']}: bench/controls/{driver}.py read()")
        if not bench_cells.faults_of(driver):
            missing.append(f"{w['name']}: tests/bench/faults/{driver}.py "
                           "FAULTS")
    assert not missing, missing


def test_every_cell_reports_what_it_must(bench):
    """Every cell reports ``setup_s``, one more end-to-end metric and one
    per-layer metric.  An end-to-end metric with a ``workloads`` list is
    reported only by the cells it lists: a cell joins such a metric by
    appending its own name to that list, the one touch of an existing entry
    that a PR adding a cell makes.  Its per-layer metrics are new entries
    with files of their own."""
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        target = e2e[m["moves"]]
        for cell in m.get("workloads", []):
            assert cell in target.get("workloads", [cell])
    cells = [w["name"] for w in bench["workloads"]]
    for cell in cells:
        got = [n for n, m in e2e.items() if cell in m.get("workloads", [cell])]
        assert "setup_s" in got and len(got) >= 2
        assert any(cell in m.get("workloads", [cell])
                   for m in bench["per_layer"])
    for m in bench["per_layer"] + bench["end_to_end"]:
        assert set(m.get("workloads", [])) <= set(cells)
