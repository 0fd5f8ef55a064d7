"""Chunk-resident bulk-synchronous engine: plan boundaries, exact parity
with the host k-d tree reference across chunk counts, and the recompile-free
guarantee (one compiled round per configuration, independent of flush
sizes)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BufferKDTree, build_top_tree, knn_host_kdtree
from repro.core.buffers import build_work_plan
from repro.core.chunked import ChunkedLeafStore
from repro.core.chunked_jit import chunk_round_cache_size
from repro.core.jitsearch import _build_plan
from repro.core.lazysearch import PLAN_LADDER, _plan_pad


def _data(n, m, d, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, d)).astype(np.float32),
            rng.normal(size=(m, d)).astype(np.float32))


class TestBuildPlanBoundary:
    def _units_of(self, leaf, tq, n_leaves):
        ul, uq, nu = _build_plan(jnp.asarray(leaf, jnp.int32), tq, n_leaves)
        return np.asarray(ul), np.asarray(uq), int(nu)

    def _check_complete(self, leaf, tq, n_leaves):
        """Every live query appears exactly once, in a unit of its leaf."""
        ul, uq, nu = self._units_of(leaf, tq, n_leaves)
        w_max = -(-len(leaf) // tq) + n_leaves
        assert nu <= w_max
        # numpy reference plan: same number of units
        live = leaf >= 0
        ref = build_work_plan(leaf[live], np.nonzero(live)[0], tq)
        assert nu == ref.n_units
        seen = uq[:nu][uq[:nu] >= 0]
        assert sorted(seen.tolist()) == np.nonzero(live)[0].tolist()
        for u in range(nu):
            qs = uq[u][uq[u] >= 0]
            assert (leaf[qs] == ul[u]).all()
        # everything past the occupied prefix is padding
        assert (uq[nu:] == -1).all()

    def test_densest_packing_hits_w_max_region(self):
        """tq+1 queries per leaf = 2 units per leaf, the worst padding case:
        unit count must reach 2*n_leaves and still lose no query."""
        tq, n_leaves = 4, 8
        leaf = np.repeat(np.arange(n_leaves), tq + 1).astype(np.int32)
        ul, uq, nu = self._units_of(leaf, tq, n_leaves)
        assert nu == 2 * n_leaves
        assert nu <= -(-len(leaf) // tq) + n_leaves  # the W_max bound
        self._check_complete(leaf, tq, n_leaves)

    def test_single_query_per_leaf(self):
        """One query per leaf: n_leaves units, maximum slot padding."""
        tq, n_leaves = 8, 16
        leaf = np.arange(n_leaves).astype(np.int32)
        self._check_complete(leaf, tq, n_leaves)

    def test_retired_queries_go_to_dump(self):
        tq, n_leaves = 4, 4
        leaf = np.array([2, -1, 0, -1, 2, 2, 1, -1], np.int32)
        self._check_complete(leaf, tq, n_leaves)

    def test_all_retired(self):
        ul, uq, nu = self._units_of(np.full((6,), -1, np.int32), 4, 4)
        assert nu == 0
        assert (uq == -1).all()

    def test_plan_ladder_monotone_and_bounded(self):
        assert all(_plan_pad(w) >= w for w in range(1, 2000, 7))
        # the ladder is FIXED: only len(PLAN_LADDER) distinct pads below max
        pads = {_plan_pad(w) for w in range(1, PLAN_LADDER[-1] + 1, 13)}
        assert pads <= set(PLAN_LADDER)


class TestChunkedParity:
    @pytest.mark.parametrize("n_chunks", [1, 2, 3, 7])
    def test_exact_vs_host_kdtree(self, n_chunks):
        """The chunked engine must be EXACT (same rescored distances, same
        indices) vs the classic host k-d tree for every chunk count."""
        pts, q = _data(6000, 400, 6, seed=11)
        idx = BufferKDTree(pts, height=5, n_chunks=n_chunks, tile_q=32)
        dd, di = idx.query(q, k=9)
        hd, hi = knn_host_kdtree(q, idx.tree, 9)
        np.testing.assert_allclose(dd, hd, rtol=1e-6, atol=1e-7)
        np.testing.assert_array_equal(di, hi)

    @pytest.mark.parametrize("n_chunks", [1, 3])
    def test_chunked_engine_matches_host_engine(self, n_chunks):
        """Both engine tiers answer identically on the same tree."""
        pts, q = _data(3000, 200, 5, seed=7)
        fast = BufferKDTree(pts, height=4, n_chunks=n_chunks, tile_q=32)
        slow = BufferKDTree(pts, height=4, n_chunks=n_chunks, tile_q=32,
                            engine="host")
        fd, fi = fast.query(q, k=5)
        sd, si = slow.query(q, k=5)
        np.testing.assert_allclose(fd, sd, rtol=1e-6, atol=1e-7)
        np.testing.assert_array_equal(fi, si)

    def test_k_edges_and_duplicates(self):
        rng = np.random.default_rng(3)
        base = rng.normal(size=(64, 4)).astype(np.float32)
        pts = np.concatenate([base] * 3)
        idx = BufferKDTree(pts, height=3, n_chunks=2, tile_q=16)
        dd, di = idx.query(base[:20] + 1e-3, k=3)
        hd, hi = knn_host_kdtree(base[:20] + 1e-3, idx.tree, 3)
        np.testing.assert_allclose(dd, hd, rtol=1e-5, atol=1e-6)

    def test_stats_populated(self):
        pts, q = _data(4000, 128, 6, seed=5)
        idx = BufferKDTree(pts, height=4, n_chunks=2, tile_q=32)
        idx.query(q, k=4)
        st = idx.stats
        assert st.iterations > 0 and st.chunk_rounds >= st.iterations
        assert st.units_scanned > 0
        # tree pruning: far fewer points scanned than brute force
        assert st.points_scanned < 0.7 * 128 * 4000


class TestRecompileFree:
    def test_no_new_round_compiles_across_flushes(self):
        """Varying flush sizes / work-unit counts / query values must reuse
        the one compiled round (the W dimension is a while-loop bound, not a
        shape)."""
        pts, q = _data(4096, 256, 6, seed=1)
        idx = BufferKDTree(pts, height=4, n_chunks=2, tile_q=32)
        idx.query(q, k=5)                       # warm: compiles the round
        before = chunk_round_cache_size()
        rng = np.random.default_rng(9)
        for s in range(3):                      # same shapes, new content
            idx.query(rng.normal(size=(256, 6)).astype(np.float32), k=5)
        assert chunk_round_cache_size() == before

    def test_host_engine_plan_shapes_bounded(self):
        """The legacy path pads plans onto the fixed ladder: distinct padded
        shapes seen across ALL flushes stay tiny (no per-W recompiles)."""
        pts, q = _data(4096, 256, 6, seed=2)
        idx = BufferKDTree(pts, height=4, n_chunks=2, tile_q=32,
                           engine="host", buffer_size=64)
        idx.query(q, k=5)
        assert 1 <= idx.stats.plan_shapes <= 3


class TestUniformStore:
    def test_uniform_padding_shapes(self):
        slabs = np.arange(8 * 4 * 2, dtype=np.float32).reshape(8, 4, 2)
        store = ChunkedLeafStore(slabs, n_chunks=3, uniform=True)
        assert store.chunk_leaves == 3
        shapes = set()
        for cid, buf, lo in store.stream([0, 1, 2]):
            shapes.add(tuple(buf.shape))
            c_lo, c_hi = store.chunk_leaf_range(cid)
            # real rows match the original slabs
            np.testing.assert_allclose(
                np.asarray(buf)[: c_hi - c_lo], slabs[c_lo:c_hi]
            )
        assert shapes == {(3, 4, 2)}

    def test_uniform_chunk_of_leaf_covers_real_leaves(self):
        slabs = np.zeros((10, 2, 2), np.float32)
        store = ChunkedLeafStore(slabs, n_chunks=4, uniform=True)
        ids = store.chunk_of_leaf(np.arange(10))
        assert ids.min() >= 0 and ids.max() < 4
        for j in range(4):
            lo, hi = store.chunk_leaf_range(j)
            assert (ids[lo:hi] == j).all()


class TestSlabTiling:
    """Leaves longer than one kernel slab tile (512) whose length is not a
    multiple of it: the slab is padded to whole tiles, so the Pallas scan
    runs the engine path for any n (before, the kernel raised
    ``L_pad=... not a multiple of tx=512``)."""

    @pytest.mark.parametrize("n_chunks", [1, 2])
    def test_engine_path_interpret_leaf_above_tile(self, n_chunks):
        pts, q = _data(2400, 40, 5, seed=31)     # 4 leaves of 600 points
        tree = BufferKDTree(pts, height=2, n_chunks=n_chunks,
                            backend="pallas_interpret")
        d, i = tree.query(q, k=7)
        bd, bi = knn_host_kdtree(q, build_top_tree(pts, 2), 7)
        np.testing.assert_allclose(d, bd, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(i, bi)
        assert tree.tree.leaf_sizes().max() == 600
        assert tree.store.host.shape[1] == 1024
