"""The benchmark's shared arithmetic: trace reduction, work counts, peaks,
generators and references."""

import numpy as np
import pytest

from bench.lib import oracles, peaks, trace as tr, work

# ---------------------------------------------------------------------------
# Trace reduction
# ---------------------------------------------------------------------------
MS = 1_000_000.0


def _ev(line, name, start_ms, dur_ms, detail=""):
    return tr.Event(line, name, start_ms * MS, dur_ms * MS, detail)


def _known_trace():
    """Window [10, 110) ms on the host.  Device ops: [5, 20) (half outside),
    [30, 40) and [35, 50) overlapping, [60, 70), [120, 130) outside.
    Busy inside the window: 10 + 20 + 10 = 40 ms; idle 60 ms in gaps
    [20, 30), [50, 60), [70, 110)."""
    dev = [
        _ev(tr.OPS_LINE, "fusion.1", 5, 15),
        _ev(tr.OPS_LINE, "%leaf_scan_pallas.3 = (f32[8,128,10]{2,1,0}, "
            "s32[8,128,10]{2,1,0}) custom-call(f32[8,128,16]{2,1,0} %a)", 30, 10),
        _ev(tr.OPS_LINE, "fusion.2", 35, 15),
        _ev(tr.OPS_LINE, "%leaf_scan_pallas.3 = (f32[8,128,10]{2,1,0}, "
            "s32[8,128,10]{2,1,0}) custom-call(f32[8,128,16]{2,1,0} %a)", 60, 10),
        _ev(tr.OPS_LINE, "fusion.1", 120, 10),
        _ev(tr.MODULES_LINE, "jit__chunk_round(3)", 30, 20),
    ]
    host = [
        _ev("main", "bench.call", 10, 40),
        _ev("main", "bench.call", 50, 60),
        _ev("py", "$run.py:1 call", 10, 100),
        _ev("py", "$chunked_jit.py:582 harvest", 18, 14),   # gap [20, 30)
        _ev("py", "$array.py:631 _value", 52, 6),           # gap [50, 60)
        _ev("rt", "ToLiteral", 51, 8),      # a runtime thread: not named
        _ev("py", "$lazysearch.py:65 finalize", 72, 36),    # gap [70, 110)
    ]
    return tr.TraceData(device={"/device:TPU:0": dev}, host=host)


def test_reduce_trace_known_busy_and_idle():
    red = tr.reduce_trace(_known_trace(), window_span="bench.call")
    assert red["window_s"] == pytest.approx(0.100)
    assert red["busy_s"] == pytest.approx(0.040)
    assert red["devices"] == 1
    idle = dict(red["idle_by_host"])
    assert idle == pytest.approx({"$chunked_jit.py:582 harvest": 0.010,
                                  "$array.py:631 _value": 0.010,
                                  "$lazysearch.py:65 finalize": 0.040})
    ops = dict(red["op_s"])
    assert ops["%leaf_scan_pallas.3"] == pytest.approx(0.020)
    assert ops["fusion.1"] == pytest.approx(0.010)     # clipped to the window
    assert ops["fusion.2"] == pytest.approx(0.015)


def test_reduce_trace_averages_devices_and_refuses_empty():
    t = _known_trace()
    t.device["/device:TPU:1"] = [_ev(tr.OPS_LINE, "x", 10, 100)]
    red = tr.reduce_trace(t, window_span="bench.call")
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx((0.040 + 0.100) / 2)
    with pytest.raises(ValueError):
        tr.reduce_trace(tr.TraceData(device={}, host=t.host),
                        window_span="bench.call")
    with pytest.raises(ValueError):
        tr.reduce_trace(t, window_span="no.such.span")


def test_short_name_drops_the_hlo_text():
    assert tr.short_name("%fusion.2 = f32[65536]{0:T(1024)S(1)} fusion("
                         "f32[65536,16]{0,1} %x)") == "%fusion.2 f32[65536]"
    assert tr.short_name("%while.4 = (s32[]{:T(128)}, f32[2]{0}) while(%t)") \
        == "%while.4"
    assert tr.short_name("jit__chunk_round(123)") == "jit__chunk_round(123)"


def test_device_time_and_merge_intervals():
    t = _known_trace()
    evs = t.device["/device:TPU:0"]
    assert tr.device_time(evs, line=tr.OPS_LINE,
                          patterns=("%leaf_scan_pallas",)) \
        == pytest.approx(0.020)
    assert tr.device_time(evs, line=tr.MODULES_LINE,
                          patterns=("_chunk_round",), lo=40 * MS,
                          hi=100 * MS) == pytest.approx(0.010)
    assert tr.merge_intervals([(3, 4), (0, 2), (1, 3), (6, 6)]) == [(0, 4)]


def test_load_trace_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    data = tr.load_trace(str(tmp_path))
    calls = [e for e in data.host if e.name == "bench.call"]
    assert len(calls) == 3
    assert all(e.dur_ns > 0 for e in calls)


# ---------------------------------------------------------------------------
# Work counts and peaks
# ---------------------------------------------------------------------------
def test_leaf_scan_work_matches_a_hand_count():
    # tq=128, l_pad=1024 (2 tiles of 512), d_pad=16, k=10, 3 units:
    # cross 2*128*1024*16 = 4,194,304; query norms 2*128*16*2 = 8,192;
    # slab norms 2*1024*16 = 32,768; combine 4*128*1024 = 524,288
    ops, nbytes = work.leaf_scan_work(units=3, tq=128, l_pad=1024, d_pad=16,
                                      k=10)
    assert ops == 3 * (4_194_304 + 8_192 + 32_768 + 524_288)
    # query tile 128*16*4 = 8,192; slab 1024*16*4 = 65,536;
    # out 128*10*8 = 10,240
    assert nbytes == 3 * (8_192 + 65_536 + 10_240)
    # a slab shorter than one tile is one tile
    ops1, _ = work.leaf_scan_work(units=1, tq=8, l_pad=100, d_pad=8, k=2)
    assert ops1 == 2 * 8 * 100 * 8 + 2 * 8 * 8 + 2 * 100 * 8 + 4 * 8 * 100


def test_roofline_share_takes_the_larger_bound():
    share, bound = work.roofline_share(197e12, 819e9 / 2, 2.0,
                                       peak_ops=197e12, peak_bytes_per_s=819e9)
    assert share == pytest.approx(50.0) and bound == "compute"
    share, bound = work.roofline_share(1.0, 819e9, 4.0, peak_ops=197e12,
                                       peak_bytes_per_s=819e9)
    assert share == pytest.approx(25.0) and bound == "memory"
    assert work.roofline_share(1, 1, 0.0, peak_ops=1, peak_bytes_per_s=1) \
        is None


def test_peaks_table_knows_v5e_and_refuses_others():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["int8_ops"] == 393e12
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


# ---------------------------------------------------------------------------
# Generators and references
# ---------------------------------------------------------------------------
def test_generators_follow_the_seed():
    c = oracles.cluster_centers(32, 10, 2015)
    a = oracles.gaussian_mixture(c, 0.15, 500, 2**31 + 5, 0)
    b = oracles.gaussian_mixture(c, 0.15, 500, 2**31 + 5, 0)
    other = oracles.gaussian_mixture(c, 0.15, 500, 2**31 + 6, 0)
    assert a.dtype == np.float32 and a.shape == (500, 10)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, other)
    lat = oracles.lattice_catalog(400, span=2048, n_clusters=64,
                                  radius=40.96, centers_seed=2004, seed=9)
    assert lat.dtype == np.float32 and (lat == np.rint(lat)).all()
    assert lat.min() >= 0 and lat.max() <= 2047
    np.testing.assert_array_equal(
        lat, oracles.lattice_catalog(400, span=2048, n_clusters=64,
                                     radius=40.96, centers_seed=2004, seed=9))


def test_knn_oracle_matches_sorted_brute():
    rng = np.random.default_rng(3)
    pts = rng.normal(size=(3000, 10)).astype(np.float32)
    q = rng.normal(size=(40, 10)).astype(np.float32)
    d, i = oracles.knn_oracle(pts, q, 6, block=512, sample=700)
    full = np.sqrt(((q[:, None, :].astype(np.float64) - pts[None]) ** 2).sum(-1))
    order = np.argsort(full, axis=1, kind="stable")[:, :6]
    np.testing.assert_array_equal(i, order)
    np.testing.assert_allclose(d, np.take_along_axis(full, order, 1))


def test_compare_knn_counts_each_fault():
    rng = np.random.default_rng(4)
    pts = rng.normal(size=(2000, 10)).astype(np.float32)
    q = rng.normal(size=(16, 10)).astype(np.float32)
    ref = oracles.knn_oracle(pts, q, 6, block=256, sample=500)
    d, i = ref[0][:, :5].astype(np.float32), ref[1][:, :5].copy()
    got = oracles.compare_knn(d, i, *ref, pts, q, tie_rtol=1e-6)
    assert got["max_rel_dist_err"] < 1e-6 and got["index_mismatches"] == 0
    assert got["bad_rows"] == 0
    far = np.setdiff1d(np.arange(2000), ref[1][0])[0]
    i2 = i.copy()
    i2[0, 4] = far
    got = oracles.compare_knn(d, i2, *ref, pts, q, tie_rtol=1e-6)
    assert got["index_mismatches"] == 1 and got["max_rel_own_err"] > 1e-3
    i3 = i.copy()
    i3[1, 2] = -1
    assert oracles.compare_knn(d, i3, *ref, pts, q, tie_rtol=1e-6)["bad_rows"] == 1
    d4 = d * np.float32(1 + 1e-4)
    assert oracles.compare_knn(d4, i, *ref, pts, q,
                               tie_rtol=1e-6)["max_rel_dist_err"] > 5e-5


def test_pair_count_references_agree_with_float64_histogram():
    pos = oracles.lattice_catalog(900, span=2048, n_clusters=8, radius=40.96,
                                  centers_seed=1, seed=2)
    edge_sq = (7, 31, 103, 407, 1607, 6407)
    brute = oracles.pair_count_oracle(pos, edge_sq, block=128)
    kd = oracles.pair_count_kdtree(pos, edge_sq)
    p = pos.astype(np.float64)
    dist = np.sqrt(((p[:, None, :] - p[None]) ** 2).sum(-1))
    off = ~np.eye(len(p), dtype=bool)
    ref, _ = np.histogram(dist[off], bins=np.sqrt(edge_sq))
    np.testing.assert_array_equal(brute, ref)
    np.testing.assert_array_equal(kd, ref)
    assert ref.sum() > 0
