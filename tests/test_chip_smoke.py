"""chip_smoke.py rehearsed on CPU at tiny size.

The phase functions run with the leaf scan pinned to ``pallas_interpret`` (the
kernel body the chip compiles), against the script's own float64 host oracles.
``main`` must refuse a CPU platform.  The four-device phase runs in a
subprocess on four virtual CPU devices; the CPU backend reports no
``memory_stats``, so there the held bytes are counted from the live arrays.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_knn_oracle_matches_sorted_brute():
    rng = np.random.default_rng(3)
    pts = rng.normal(size=(3000, 10)).astype(np.float32)
    q = rng.normal(size=(40, 10)).astype(np.float32)
    d, i = chip_smoke.knn_oracle(pts, q, 6, block=512, sample=700)
    full = np.sqrt(((q[:, None, :].astype(np.float64) - pts[None]) ** 2).sum(-1))
    order = np.argsort(full, axis=1, kind="stable")[:, :6]
    np.testing.assert_array_equal(i, order)
    np.testing.assert_allclose(d, np.take_along_axis(full, order, 1))


def test_pair_count_oracle_matches_float64_histogram():
    pos = chip_smoke.lattice_catalog(700, span=64)
    edge_sq = (7, 31, 103, 407)
    hist = chip_smoke.pair_count_oracle(pos, edge_sq, block=128)
    p = pos.astype(np.float64)
    dist = np.sqrt(((p[:, None, :] - p[None]) ** 2).sum(-1))
    off = ~np.eye(len(p), dtype=bool)
    ref, _ = np.histogram(dist[off], bins=np.sqrt(edge_sq))
    np.testing.assert_array_equal(hist, ref)


def test_compare_knn_flags_a_wrong_neighbour():
    rng = np.random.default_rng(4)
    pts = rng.normal(size=(2000, 10)).astype(np.float32)
    q = rng.normal(size=(16, 10)).astype(np.float32)
    ref = chip_smoke.knn_oracle(pts, q, 6, block=256, sample=500)
    d, i = ref[0][:, :5].astype(np.float32), ref[1][:, :5].copy()
    assert chip_smoke.compare_knn(d, i, *ref, pts, q)["ok"]
    i[0, 4] = np.setdiff1d(np.arange(2000), ref[1][0])[0]   # not a top-6
    assert not chip_smoke.compare_knn(d, i, *ref, pts, q)["ok"]


def test_agree_within_accepts_near_tie_swaps_only():
    rng = np.random.default_rng(5)
    pts = rng.normal(size=(500, 10)).astype(np.float32)
    q = rng.normal(size=(4, 10)).astype(np.float32)
    d, i = chip_smoke.knn_oracle(pts, q, 5, block=128, sample=200)
    # a second point as near to query 0 as its 5th neighbour, up to ~1e-6
    twin = q[0] + (pts[i[0, 4]] - q[0]) * np.float32(1 + 1e-6)
    pts = np.vstack([pts, twin[None]])
    a = (d.astype(np.float32), i)
    swapped = i.copy()
    swapped[0, 4] = 500
    assert chip_smoke.agree_within(a, (a[0], swapped), pts, q)["ok"]
    swapped[0, 4] = np.setdiff1d(np.arange(500), i[0])[0]   # a far point
    assert not chip_smoke.agree_within(a, (a[0], swapped), pts, q)["ok"]


def test_one_chip_phases_rehearsal_interpret(capsys):
    chip_smoke.run_one(
        n=6000, m=512, n_sub=256, n_check=64, n_served=16, n_chunks=2,
        pc_points=1500, pc_height=4,
        backend="pallas_interpret", expect_backend="pallas_interpret",
    )
    out = capsys.readouterr().out
    for tag in ("[phase1 resident]", "[phase2 out-of-core]",
                "[phase3 served]", "[phase4 pair_count]"):
        assert tag in out
    assert "engine=chunked backend=pallas_interpret" in out
    assert "bins_differ=0" in out


def test_main_refuses_cpu(capsys):
    assert chip_smoke.main([]) != 0
    cap = capsys.readouterr()
    assert '"ok"' not in cap.out
    assert "needs a TPU" in cap.err


_FOUR = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    jax.config.update("jax_cpu_enable_async_dispatch", False)  # see conftest
    sys.path.insert(0, {root!r})
    import chip_smoke

    def bytes_in_use(devices):
        held = {{d.id: 0 for d in devices}}
        for a in jax.live_arrays():
            for s in a.addressable_shards:
                if s.device.id in held:
                    held[s.device.id] += s.data.nbytes
        return [held[d.id] for d in devices]

    chip_smoke._bytes_in_use = bytes_in_use
    chip_smoke.run_four(jax.devices(), n=8192, m=256, n_check=64,
                        backend="pallas_interpret")
    print("FOUR_OK")
""")


def test_four_device_phase_rehearsal():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", _FOUR.format(root=ROOT)],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    assert out.returncode == 0, f"subprocess failed:\n{out.stderr[-3000:]}"
    assert "FOUR_OK" in out.stdout
    for label in ("planner", "sharded", "one-device"):
        assert f"[four-chip {label}] oracle:" in out.stdout
    assert "engine=forest" in out.stdout


def test_catalog_rows_fall_in_the_prefix():
    _, q, rows = chip_smoke._catalog(5000, 400, 100, 32)
    assert q.shape == (400, chip_smoke.DIM)
    assert rows.size == 32 and rows.max() < 100 and len(set(rows)) == 32
