"""core/dualtree: node-pair frontier ops vs brute-force oracles.

Parity fixtures use integer-lattice points so every squared pair distance
is an exact fp32 integer, and radii / histogram edges whose squares are
NON-integers — no distance can straddle a boundary between the kernels'
fp32 arithmetic and the oracles' float64, making radius and pair_count
bit-exact comparisons rather than tolerance games.  KDE is checked
against its declared contract: ``|approx - exact| <= rtol*exact + atol``
(plus fp32 kernel rounding slack).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.chunked import ChunkedLeafStore
from repro.core.dualtree import (
    PAIR_RUNGS,
    DualTree,
    _pair_hist_kernel,
    _pairwise_d2,
    dualtree_cache_size,
    kde_brute,
    node_bounds,
    pair_count_brute,
    radius_brute,
)
from repro.core.lazysearch import SearchStats
from repro.core.toptree import PAD_COORD, build_top_tree

# non-integer-squared boundaries (see module doc)
EDGES = np.array([0.5, 3.5, 7.5, 11.5, 16.5, 25.5])
RADIUS = float(np.sqrt(7.5))


def lattice(n, d, seed=0, span=12):
    rng = np.random.default_rng(seed)
    return rng.integers(0, span, size=(n, d)).astype(np.float32)


def clustered(n, d, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(8, d)).astype(np.float32)
    pts = centers[rng.integers(0, 8, n)] + 0.05 * rng.normal(
        size=(n, d)
    ).astype(np.float32)
    return pts.astype(np.float32)


def csr_rows_equal(ip_a, ix_a, ip_b, ix_b):
    """Same neighbor SETS per row (tie order among equal distances is
    stream-dependent); indptr must match exactly."""
    assert np.array_equal(ip_a, ip_b)
    for i in range(len(ip_a) - 1):
        assert set(ix_a[ip_a[i]:ip_a[i + 1]].tolist()) == set(
            ix_b[ip_b[i]:ip_b[i + 1]].tolist()
        ), f"row {i}"


class TestNodeBounds:
    def test_boxes_match_brute_leaf_partition(self):
        pts = lattice(500, 3, seed=1)
        tree = build_top_tree(pts, 4)
        b = node_bounds(tree)
        nl = tree.n_leaves
        sizes = tree.leaf_sizes()
        # leaves: box over each leaf's real rows
        for j in range(nl):
            rows = tree.points_padded[j, : sizes[j], : tree.d]
            np.testing.assert_array_equal(b.lo[nl + j], rows.min(0))
            np.testing.assert_array_equal(b.hi[nl + j], rows.max(0))
            assert b.count[nl + j] == sizes[j]
        # internal nodes: union of children, counts add
        for v in range(nl - 1, 0, -1):
            np.testing.assert_array_equal(
                b.lo[v], np.minimum(b.lo[2 * v], b.lo[2 * v + 1])
            )
            np.testing.assert_array_equal(
                b.hi[v], np.maximum(b.hi[2 * v], b.hi[2 * v + 1])
            )
            assert b.count[v] == b.count[2 * v] + b.count[2 * v + 1]
        assert b.count[1] == 500


def stores(pts, height):
    """The store variants every op must agree across: resident, chunked,
    and quantized (which forces DualTree's private fp32 rebuild)."""
    tree = build_top_tree(pts, height)
    yield "resident", DualTree(tree)
    slabs = tree.points_padded
    dp = max(8, -(-tree.d // 8) * 8)
    if dp != tree.d:
        pad = np.zeros((slabs.shape[0], slabs.shape[1], dp - tree.d), np.float32)
        slabs = np.concatenate([slabs, pad], axis=-1)
    yield "chunked3", DualTree(
        tree,
        ChunkedLeafStore(
            slabs, n_chunks=3, uniform=True, leaf_sizes=tree.leaf_sizes()
        ),
    )
    yield "quantized", DualTree(
        tree,
        ChunkedLeafStore(
            slabs, n_chunks=2, uniform=True, leaf_sizes=tree.leaf_sizes(),
            precision="int8",
        ),
    )


class TestRadius:
    @pytest.mark.parametrize("n,m,d,height", [(2000, 150, 3, 4), (700, 64, 5, 5)])
    def test_parity_all_store_variants(self, n, m, d, height):
        pts = lattice(n, d, seed=n)
        q = lattice(m, d, seed=n + 1)
        bi, bj, bd = radius_brute(q, pts, RADIUS)
        for name, dual in stores(pts, height):
            ip, ix, dd, stats = dual.radius(q, RADIUS)
            csr_rows_equal(ip, ix, bi, bj)
            # distances ascending within each row, all <= r
            for i in range(m):
                row = dd[ip[i]:ip[i + 1]]
                assert np.all(np.diff(row) >= 0), (name, i)
            assert np.all(dd <= np.float32(RADIUS))
            assert isinstance(stats, SearchStats)
            assert stats.units_scanned > 0

    def test_prunes_vs_all_pairs(self):
        # two well-separated lattice blocks: cross pairs must prune
        pts = np.concatenate([lattice(600, 3, seed=2),
                              lattice(600, 3, seed=3) + 1000.0])
        q = pts[::10] + 0.25
        dual = DualTree(build_top_tree(pts, 5))
        ip, ix, dd, stats = dual.radius(q, RADIUS)
        total = dual.tree.n_leaves * -(-len(q) // 64)
        assert stats.units_scanned < total  # leaf pairs visited < full grid
        bi, bj, _ = radius_brute(q, pts, RADIUS)
        csr_rows_equal(ip, ix, bi, bj)

    def test_single_query_fallback(self):
        pts = lattice(300, 4, seed=4)
        dual = DualTree(build_top_tree(pts, 3))
        for q in (pts[:1] + 0.25, np.zeros((0, 4), np.float32)):
            ip, ix, dd, stats = dual.radius(q, RADIUS)
            bi, bj, _ = radius_brute(q, pts, RADIUS)
            csr_rows_equal(ip, ix, bi, bj)

    def test_negative_radius_rejected(self):
        dual = DualTree(build_top_tree(lattice(64, 2), 2))
        with pytest.raises(ValueError):
            dual.radius(np.zeros((3, 2), np.float32), -1.0)


class TestKDE:
    @pytest.mark.parametrize("kernel", ["gaussian", "tophat"])
    def test_within_declared_tolerance(self, kernel):
        pts = clustered(3000, 3, seed=5)
        q = clustered(200, 3, seed=6)
        h, rtol, atol = 0.3, 1e-2, 1e-9
        exact = kde_brute(q, pts, h, kernel=kernel).astype(np.float64)
        for name, dual in stores(pts, 4):
            dens, err, stats = dual.kde(
                q, h, rtol=rtol, atol=atol, kernel=kernel
            )
            # declared contract + fp32 kernel rounding slack
            bound = rtol * exact + atol + 1e-5 * np.maximum(exact, 1.0)
            assert np.all(np.abs(dens.astype(np.float64) - exact) <= bound), name
            assert err >= 0.0

    def test_tophat_exact_and_consistent_with_radius(self):
        pts = lattice(1500, 3, seed=7)
        q = lattice(100, 3, seed=8)
        dual = DualTree(build_top_tree(pts, 4))
        dens, err, _ = dual.kde(q, RADIUS, kernel="tophat")
        assert err == 0.0  # tophat prune is exact
        ip, _, _, _ = dual.radius(q, RADIUS)
        counts = np.diff(ip)
        np.testing.assert_allclose(
            dens, counts.astype(np.float32) / len(pts), rtol=1e-6
        )

    def test_approximation_actually_prunes(self):
        # clustered data with a loose tolerance must midpoint-approximate
        # some far-field pairs (fewer leaf pairs than the exact run)
        pts = clustered(4000, 3, seed=9)
        q = clustered(256, 3, seed=10)
        dual = DualTree(build_top_tree(pts, 5))
        _, err_loose, s_loose = dual.kde(q, 0.1, rtol=0.3, atol=1e-6)
        _, _, s_tight = dual.kde(q, 0.1, rtol=1e-12, atol=0.0)
        assert s_loose.units_scanned < s_tight.units_scanned
        assert err_loose > 0.0

    def test_bad_kernel_rejected(self):
        dual = DualTree(build_top_tree(lattice(64, 2), 2))
        with pytest.raises(ValueError):
            dual.kde(np.zeros((3, 2), np.float32), 1.0, kernel="sinc")


class TestPairCount:
    @pytest.mark.parametrize("n,d,height", [(1500, 3, 4), (900, 5, 5)])
    def test_parity_all_store_variants(self, n, d, height):
        pts = lattice(n, d, seed=n)
        ref = pair_count_brute(pts, EDGES)
        np_ref, _ = np.histogram(np.float32(0), bins=EDGES)  # shape check only
        assert ref.shape == np_ref.shape
        for name, dual in stores(pts, height):
            hist, stats = dual.pair_count(EDGES)
            assert np.array_equal(hist, ref), name
            assert stats.units_scanned >= 0

    def test_matches_numpy_histogram_oracle(self):
        pts = lattice(800, 3, seed=11)
        diff = pts[:, None, :].astype(np.float64) - pts[None, :, :]
        dist = np.sqrt((diff * diff).sum(-1))
        mask = ~np.eye(len(pts), dtype=bool)
        ref, _ = np.histogram(dist[mask], bins=EDGES)
        hist, _ = DualTree(build_top_tree(pts, 4)).pair_count(EDGES)
        assert np.array_equal(hist, ref.astype(np.int64))

    def test_zero_leading_edge_excludes_self_pairs(self):
        pts = lattice(500, 3, seed=12)
        edges = np.array([0.0, 3.5, 7.5, 16.5])
        diff = pts[:, None, :].astype(np.float64) - pts[None, :, :]
        dist = np.sqrt((diff * diff).sum(-1))
        mask = ~np.eye(len(pts), dtype=bool)
        ref, _ = np.histogram(dist[mask], bins=edges)
        hist, _ = DualTree(build_top_tree(pts, 4)).pair_count(edges)
        assert np.array_equal(hist, ref.astype(np.int64))

    def test_total_count_conserved(self):
        pts = lattice(600, 4, seed=13, span=6)
        span_max = 4 * 6 * 6 * 4  # > any possible squared distance
        edges = np.array([0.0, 1.5, float(np.sqrt(span_max))])
        hist, _ = DualTree(build_top_tree(pts, 4)).pair_count(edges)
        n = len(pts)
        assert hist.sum() == n * (n - 1)  # every ordered non-self pair

    def test_bad_edges_rejected(self):
        dual = DualTree(build_top_tree(lattice(64, 2), 2))
        for bad in ([1.0], [2.0, 1.0], [-1.0, 2.0]):
            with pytest.raises(ValueError):
                dual.pair_count(np.asarray(bad, np.float64))


@jax.jit
def _masked_dist(aslab, bslab, ia, ib, sa, sb):
    """The kernel's distances: pad rows beyond sa/sb masked to +inf."""
    d2 = _pairwise_d2(aslab[ia], bslab[ib])
    rows = jnp.arange(d2.shape[1])
    cols = jnp.arange(d2.shape[2])
    valid = (rows[None, :, None] < sa[:, None, None]) & (
        cols[None, None, :] < sb[:, None, None]
    )
    return jnp.where(valid, jnp.sqrt(d2), jnp.inf)


@jax.jit
def _bincount_hist(dist, edges):
    """The scatter formulation: searchsorted, the closed last edge, then one
    bincount per pair over E + 2 slots, the outer two dropped."""
    E = edges.shape[0] - 1
    flat = dist.reshape(dist.shape[0], -1)
    r = jnp.searchsorted(edges, flat, side="right").astype(jnp.int32)
    r = jnp.where(flat == edges[-1], E, r)
    hist = jax.vmap(lambda b: jnp.bincount(b, length=E + 2))(r)
    return hist[:, 1:E + 1]


class TestPairHistKernel:
    @pytest.mark.parametrize("n_edges", [2, 9, 33])
    def test_matches_bincount_formulation(self, n_edges):
        rng = np.random.default_rng(n_edges)
        n_leaves, lp, P = 6, 24, 16
        sizes = rng.integers(1, lp + 1, n_leaves)
        sizes[0] = lp
        slab = rng.random((n_leaves, lp, 8)).astype(np.float32) * 3.0
        for j, s in enumerate(sizes):
            slab[j, s:] = PAD_COORD
        ia = rng.integers(0, n_leaves, P).astype(np.int32)
        ib = rng.integers(0, n_leaves, P).astype(np.int32)
        sa, sb = sizes[ia].astype(np.int32), sizes[ib].astype(np.int32)
        sa[0] = 0  # an empty side
        args = (slab, slab, ia, ib, sa, sb)
        dist = np.asarray(_masked_dist(*args))
        # edges ARE realized f32 distances, so values sit exactly on every
        # edge, the closed last one included, with finite values below e_0
        # and above e_E and +inf on the masked rows
        real = np.unique(dist[np.isfinite(dist)])
        pick = np.linspace(0.1, 0.9, n_edges) * (real.size - 1)
        edges = real[pick.astype(np.int64)]
        assert np.all(np.diff(edges) > 0)
        assert np.all(np.isin(edges, dist))
        assert (dist < edges[0]).any() and np.isinf(dist).any()
        assert (np.isfinite(dist) & (dist > edges[-1])).any()
        got = np.asarray(_pair_hist_kernel(*args, edges))
        want = np.asarray(_bincount_hist(dist, edges))
        assert got.dtype == np.int32 and got.shape == (P, n_edges - 1)
        assert np.array_equal(got, want)
        assert not got[0].any()

    @pytest.mark.parametrize("rung", [8, 128])
    def test_lowers_without_scatter(self, rung):
        slab = jax.ShapeDtypeStruct((4, 256, 8), jnp.float32)
        ids = jax.ShapeDtypeStruct((rung,), jnp.int32)
        edges = jax.ShapeDtypeStruct((9,), jnp.float32)
        text = _pair_hist_kernel.lower(
            slab, slab, ids, ids, ids, ids, edges
        ).as_text()
        assert not re.search(r"\bscatter", text)
        # the witness: the formulation it replaced does lower to one
        dist = jax.ShapeDtypeStruct((rung, 256, 256), jnp.float32)
        assert re.search(r"\bscatter", _bincount_hist.lower(dist, edges).as_text())


class TestRecompileDiscipline:
    def test_warm_then_new_operands_no_compiles(self):
        pts = lattice(2500, 3, seed=14)
        q = lattice(300, 3, seed=15)
        dual = DualTree(build_top_tree(pts, 4))
        dual.warm(("radius", "kde", "pair_count"), m=len(q), n_edges=len(EDGES))
        before = dualtree_cache_size()
        # new radii / bandwidths / edge VALUES are operands, not shapes
        for r in (0.5, RADIUS, 9.0):
            dual.radius(q, r)
        for h in (0.4, 2.0):
            dual.kde(q, h)
            dual.kde(q, h, kernel="tophat")
        dual.pair_count(EDGES)
        dual.pair_count(EDGES * 2.0)
        assert dualtree_cache_size() == before
        # a different edge COUNT is a new kernel shape: compiles once more
        dual.pair_count(np.array([0.5, 1.5, 2.5]))
        assert dualtree_cache_size() == before + 1

    def test_rungs_cover_pair_batches(self):
        assert tuple(sorted(PAIR_RUNGS)) == PAIR_RUNGS
        assert PAIR_RUNGS[0] >= 1
