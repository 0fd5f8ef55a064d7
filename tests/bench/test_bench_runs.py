"""Every cell rehearsed end to end on the CPU at tiny size (Pallas in
interpret mode), the refusals of the command, the controls, and faults
planted under the timed path that must turn ``correct`` false."""

import argparse
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import control
from bench.lib import harness

ROOT = harness.ROOT
CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


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


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearses_and_prints_a_well_formed_last_line(
        workload, trace, capsys):
    rc = harness.main(["--workload", workload, "--seed", "4294967311",
                       "--seconds", "0.5", "--trace", str(trace),
                       "--rehearse"])
    out, err = capsys.readouterr()
    assert rc == 0
    result = json.loads(out.strip().splitlines()[-1])
    _assert_well_formed(result, workload, trace)
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
    for seed in (1, 2, 3):
        got = control.read_control(workload, seed, rehearse=True)
        assert any(c["value"] > c["limit"] for c in got.values()), got


# ---------------------------------------------------------------------------
# Faults planted under the timed path
# ---------------------------------------------------------------------------
def _knn_answer_altered(mp):
    import repro.core.lazysearch as ls

    orig = ls.finalize_candidates

    def bad(tree, queries, gi):
        d, i = orig(tree, queries, gi)
        i = i.copy()
        i[:, -1] = (i[:, -1] + 1) % tree.n
        return d, i

    mp.setattr(ls, "finalize_candidates", bad)


def _knn_half_batch(mp):
    from repro.api import KNNIndex

    orig = KNNIndex.query

    def half(self, queries, k=None):
        res = orig(self, queries[: len(queries) // 2], k)
        m = len(queries)
        d = np.full((m, res.k), np.inf, np.float32)
        i = np.full((m, res.k), -1, np.int64)
        d[: len(res.dists)], i[: len(res.idx)] = res.dists, res.idx
        return type(res)(dists=d, idx=i, stats=res.stats, engine=res.engine,
                         k=res.k)

    mp.setattr(KNNIndex, "query", half)


def _knn_state_unchanged(mp):
    import jax.numpy as jnp

    import repro.core.chunked_jit as cj

    orig = cj._chunk_round

    def unchanged(node, fromc, leaf, knn_d, knn_i, *a, **kw):
        out = orig(node, fromc, leaf, knn_d, knn_i, *a, **kw)
        # the round advances the traversal but hands back its initial
        # neighbour state, as if the scan and merge never happened
        return (*out[:3], jnp.full_like(out[3], jnp.inf),
                jnp.full_like(out[4], -1), out[5])

    unchanged._cache_size = orig._cache_size    # the program's audit
    mp.setattr(cj, "_chunk_round", unchanged)


def _pc_answer_altered(mp):
    from repro.core.dualtree import DualTree

    orig = DualTree.pair_count

    def bad(self, edges):
        hist, stats = orig(self, edges)
        hist = hist.copy()
        hist[len(hist) // 2] += 2
        return hist, stats

    mp.setattr(DualTree, "pair_count", bad)


def _pc_half_batch(mp):
    import repro.core.dualtree as dt

    orig = dt._pair_hist_kernel

    def half(*a):
        h = orig(*a)
        # the second half of every leaf-pair batch is left out
        return h.at[h.shape[0] // 2:].set(0)

    half._cache_size = orig._cache_size         # the program's audit
    mp.setattr(dt, "_pair_hist_kernel", half)


def _pc_state_unchanged(mp):
    import jax.numpy as jnp

    import repro.core.dualtree as dt

    orig = dt._pair_hist_kernel

    def nothing(*a):
        return jnp.zeros_like(orig(*a))

    nothing._cache_size = orig._cache_size      # the program's audit
    mp.setattr(dt, "_pair_hist_kernel", nothing)


FAULTS = {
    "photo10m-knn-batch": [_knn_answer_altered, _knn_half_batch,
                           _knn_state_unchanged],
    "lattice131k-2pcf": [_pc_answer_altered, _pc_half_batch,
                         _pc_state_unchanged],
}


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w in CELLS for f in FAULTS[w]],
    ids=lambda x: getattr(x, "__name__", x))
def test_planted_fault_turns_correct_false(workload, fault, monkeypatch):
    fault(monkeypatch)
    result = _run(workload, seed=11)
    assert result["correct"] is False, result["checks"]
    # the line stays strict JSON however far off a reading is
    json.loads(json.dumps(result), parse_constant=pytest.fail)
