"""Top-tree construction invariants (paper §2.3/§3.1)."""

import numpy as np
import pytest
from hypothesis_compat import given, st

from repro.core.toptree import PAD_COORD, build_top_tree, slab_len, suggest_height


def _mk(n, d, seed=0):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


class TestBuild:
    def test_leaf_partition(self):
        pts = _mk(1000, 5)
        t = build_top_tree(pts, 4)
        sizes = t.leaf_sizes()
        assert sizes.sum() == 1000
        assert sizes.min() >= 1
        assert sizes.max() - sizes.min() <= 1
        # slabs tile [0, n) exactly
        assert t.leaf_start[0] == 0
        assert t.leaf_end[-1] == 1000
        assert (t.leaf_start[1:] == t.leaf_end[:-1]).all()

    def test_orig_idx_is_permutation(self):
        pts = _mk(257, 3)
        t = build_top_tree(pts, 3)
        assert sorted(t.orig_idx.tolist()) == list(range(257))
        np.testing.assert_allclose(t.points, pts[t.orig_idx])

    def test_split_property(self):
        """Left subtree keys <= split value <= right subtree keys, at every
        internal node (the invariant pruning correctness rests on)."""
        pts = _mk(512, 4, seed=3)
        h = 4
        t = build_top_tree(pts, h)
        first_leaf = 1 << h

        def leaves_under(v):
            while v < first_leaf:
                v = 2 * v
            lo = v - first_leaf
            v2 = v
            # rightmost leaf: walk right spine
            return lo

        # recursive check via ranges
        def node_range(v):
            if v >= first_leaf:
                leaf = v - first_leaf
                return int(t.leaf_start[leaf]), int(t.leaf_end[leaf])
            l0, _ = node_range(2 * v)
            _, r1 = node_range(2 * v + 1)
            return l0, r1

        for v in range(1, first_leaf):
            dim, val = int(t.split_dim[v]), float(t.split_val[v])
            ll, lr = node_range(2 * v)
            rl, rr = node_range(2 * v + 1)
            assert t.points[ll:lr, dim].max() <= val + 1e-7
            assert t.points[rl:rr, dim].min() >= val - 1e-7

    def test_padded_slabs(self):
        pts = _mk(100, 3)
        t = build_top_tree(pts, 3, leaf_pad_multiple=8)
        assert t.points_padded.shape[0] == 8
        assert t.points_padded.shape[1] % 8 == 0
        sizes = t.leaf_sizes()
        for leaf in range(8):
            sz = sizes[leaf]
            np.testing.assert_allclose(
                t.points_padded[leaf, :sz],
                t.points[t.leaf_start[leaf]:t.leaf_end[leaf]],
            )
            assert (t.points_padded[leaf, sz:] == PAD_COORD).all()

    def test_errors(self):
        with pytest.raises(ValueError):
            build_top_tree(_mk(7, 2), 3)  # 2**3 > 7
        with pytest.raises(ValueError):
            build_top_tree(_mk(10, 2), 0)
        with pytest.raises(ValueError):
            build_top_tree(np.zeros((10,), np.float32), 1)

    def test_widest_dim_rule(self):
        pts = _mk(256, 6, seed=5)
        pts[:, 2] *= 100.0  # dominant spread
        t = build_top_tree(pts, 2, dim_rule="widest")
        assert int(t.split_dim[1]) == 2

    def test_suggest_height(self):
        assert suggest_height(2_000_000, target_leaf=4096) in (8, 9)
        assert suggest_height(100) >= 1
        assert suggest_height(10**12) <= 20


@given(
    n=st.integers(40, 400),
    d=st.integers(1, 8),
    h=st.integers(1, 5),
    seed=st.integers(0, 10_000),
)
def test_build_invariants_fuzz(n, d, h, seed):
    if (1 << h) > n:
        return
    pts = _mk(n, d, seed)
    t = build_top_tree(pts, h)
    assert t.leaf_sizes().sum() == n
    assert t.leaf_sizes().min() >= 1
    assert sorted(t.orig_idx.tolist()) == list(range(n))


@pytest.mark.parametrize(
    "max_leaf,multiple,expect",
    [(1, 8, 8), (196, 8, 200), (512, 8, 512), (513, 8, 1024),
     (4883, 8, 5120), (600, 64, 1024), (40, 64, 64)],
)
def test_slab_len_whole_kernel_tiles(max_leaf, multiple, expect):
    assert slab_len(max_leaf, multiple) == expect


def test_planner_slab_bytes_match_build():
    """The planner's byte estimate counts the slab the build pads to."""
    from repro.api.planner import estimate_slab_bytes

    pts = _mk(2400, 5)
    t = build_top_tree(pts, 2)                  # leaves of 600 -> 1024
    assert t.leaf_pad == 1024
    assert estimate_slab_bytes(2400, 5, 2) == 4 * 1024 * 8 * 4
