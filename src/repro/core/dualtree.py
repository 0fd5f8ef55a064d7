"""Dual-tree traversals over ``TopTree`` + ``ChunkedLeafStore``.

The paper's astronomy motivation goes past plain kNN: radius search,
kernel density estimation and 2-point correlation (Gray & Moore,
"Multi-Tree Methods for Statistics on Very Large Datasets in Astronomy")
are all *node-pair frontier* traversals — instead of a per-query work
queue, the unit of work is a pair of tree nodes whose distance bounds
either prune the pair wholesale or hand its leaf-pair product to a fused
per-leaf kernel.  This module reuses the buffer-k-d-tree machinery:

  * the pointerless ``TopTree`` supplies the spatial partition (per-node
    bounding boxes are derived here, bottom-up over the implicit heap —
    the top tree itself stores only splits);
  * the ``ChunkedLeafStore`` supplies the leaf coordinate slabs, streamed
    chunk-by-chunk exactly like the kNN round loop (leaf-pair batches are
    grouped by the chunk that owns their reference leaf, so each chunk is
    uploaded once per call, double-buffered by the store);
  * the recompile-free rung discipline carries over: leaf-pair batches
    are padded to the fixed ``PAIR_RUNGS`` shapes and the query-side slab
    count to ``QLEAF_RUNGS``, so every op compiles once per rung
    (``dualtree_cache_size`` is the audit hook, mirror of
    ``chunked_jit.chunk_round_cache_size``).

Three operations::

    dt = DualTree(tree, store)
    indptr, indices, dists, stats = dt.radius(queries, r)
    density, err_bound, stats    = dt.kde(queries, bandwidth, rtol=1e-2)
    hist, stats                  = dt.pair_count(edges)

Semantics (shared with the brute references below, which the ``brute``
engine and the parity suite use as oracles):

  radius      all reference points with Euclidean ``dist <= r`` (inclusive),
              CSR over query rows, per-row neighbors sorted by distance;
  kde         mean kernel value ``density[i] = (1/n) * sum_j K(|q_i - x_j|)``
              with K gaussian ``exp(-d^2 / 2h^2)`` or tophat ``1[d <= h]``
              (no normalization constant — multiply by ``(2 pi h^2)^(-d/2)``
              etc. yourself).  Gaussian satisfies ``|approx - exact| <=
              rtol*exact + atol`` per query (the prune rule's invariant: a
              node pair may be midpoint-approximated only when the error
              it adds is within rtol times a lower bound of its own true
              contribution, or within the atol allowance spread over the
              whole set); tophat is exact.
  pair_count  histogram over ``edges`` (np.histogram bin semantics,
              last edge closed) of the distances of all ORDERED pairs
              (i, j), i != j — twice the unordered 2-point count.

Distances are computed in fp32 on device; a distance within fp32 epsilon
of a bin edge / radius may land on either side (the parity tests pin
fixtures whose realized distances keep a margin from every boundary).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.chunked import ChunkedLeafStore
from repro.core.lazysearch import SearchStats
from repro.core.toptree import PAD_COORD, TopTree, build_top_tree

__all__ = [
    "DualTree",
    "NodeBounds",
    "node_bounds",
    "dualtree_cache_size",
    "radius_brute",
    "kde_brute",
    "pair_count_brute",
    "PAIR_RUNGS",
    "QLEAF",
    "QLEAF_RUNGS",
]

# Leaf-pair batches are padded up to these fixed sizes: at most
# len(PAIR_RUNGS) compiles per kernel per slab geometry, and full batches
# run at the top rung.  Mirrors chunked_jit's compaction-ladder discipline.
PAIR_RUNGS = (8, 32, 128)

# Query-side tree leaves are built to hold <= QLEAF points and padded to
# exactly QLEAF rows, so the gathered query slab's trailing dims never vary.
QLEAF = 64

# The query-side slab COUNT (2**q_height) is padded up to these rungs so
# the device gather source keeps a fixed shape across query batch sizes.
QLEAF_RUNGS = (2, 8, 32, 128, 512, 2048, 8192)

_KERNELS = ("gaussian", "tophat")


def _rung_up(x: int, rungs: Sequence[int]) -> int:
    for r in rungs:
        if x <= r:
            return r
    return rungs[-1]


# ---------------------------------------------------------------------------
# Per-node bounding boxes over the implicit heap
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class NodeBounds:
    """Axis-aligned boxes + point counts for every heap node of a TopTree.

    Heap-indexed (index 0 unused, root at 1, leaves at
    ``first_leaf_heap .. 2*first_leaf_heap - 1``).  Empty nodes (all their
    leaf slabs empty) carry ``lo=+inf, hi=-inf, count=0`` and must be
    pruned by count before their box is used.  float64: the frontier's
    prune decisions should not wobble with fp32 rounding.
    """

    lo: np.ndarray      # f64[2*n_leaves, d]
    hi: np.ndarray      # f64[2*n_leaves, d]
    count: np.ndarray   # i64[2*n_leaves]
    first_leaf: int


def node_bounds(tree: TopTree) -> NodeBounds:
    """Compute per-leaf boxes from the slabs, then merge bottom-up."""
    nl, d = tree.n_leaves, tree.d
    pp = tree.points_padded[:, :, :d].astype(np.float64)
    sizes = tree.leaf_sizes().astype(np.int64)
    valid = np.arange(tree.leaf_pad)[None, :] < sizes[:, None]
    lo = np.full((2 * nl, d), np.inf)
    hi = np.full((2 * nl, d), -np.inf)
    lo[nl:] = np.where(valid[:, :, None], pp, np.inf).min(axis=1)
    hi[nl:] = np.where(valid[:, :, None], pp, -np.inf).max(axis=1)
    count = np.zeros(2 * nl, np.int64)
    count[nl:] = sizes
    v = nl // 2
    while v >= 1:
        sl = slice(v, 2 * v)
        lo[sl] = np.minimum(lo[2 * v:4 * v:2], lo[2 * v + 1:4 * v:2])
        hi[sl] = np.maximum(hi[2 * v:4 * v:2], hi[2 * v + 1:4 * v:2])
        count[sl] = count[2 * v:4 * v:2] + count[2 * v + 1:4 * v:2]
        v //= 2
    return NodeBounds(lo=lo, hi=hi, count=count, first_leaf=nl)


def _box_dist2(
    a: NodeBounds, u: np.ndarray, b: NodeBounds, v: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """(min, max) squared distance between node boxes a[u] and b[v]."""
    alo, ahi = a.lo[u], a.hi[u]
    blo, bhi = b.lo[v], b.hi[v]
    gap = np.maximum(np.maximum(alo - bhi, blo - ahi), 0.0)
    dmin2 = (gap * gap).sum(axis=1)
    far = np.maximum(ahi - blo, bhi - alo)
    dmax2 = (far * far).sum(axis=1)
    return dmin2, dmax2


# ---------------------------------------------------------------------------
# Fused leaf-pair kernels (jitted once per rung shape)
# ---------------------------------------------------------------------------
def _pairwise_d2(A: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """Squared distances [P, a, b] via the |a|^2 + |b|^2 - 2ab expansion
    (no [P, a, b, d] intermediate).  PAD_COORD rows against real rows come
    out huge (~1e36, excluded by any real radius/edge); PAD against PAD
    cancels to garbage near 0 — callers mask or row-slice those.

    The cross term runs at HIGHEST precision (a default f32 dot may be one
    bf16 pass on a TPU) and the sum is ordered (|a|^2 - 2ab) + |b|^2: for
    non-negative coordinates every partial sum is bounded by
    max(|a|^2, |b|^2), so integer coordinates whose squared norms fit in
    f32's 24-bit mantissa give exact squared distances."""
    a2 = jnp.sum(A * A, axis=-1)
    b2 = jnp.sum(B * B, axis=-1)
    cross = jnp.einsum(
        "pad,pbd->pab", A, B, precision=jax.lax.Precision.HIGHEST
    )
    return jnp.maximum(a2[:, :, None] - 2.0 * cross + b2[:, None, :], 0.0)


@jax.jit
def _radius_kernel(qslab, rslab, iq, ir):
    """Masked squared distances of query-leaf x ref-leaf pair batches.

    qslab f32[QL, qlp, dp] (device query slab), rslab f32[C, lp, dp]
    (chunk slab), iq/ir i32[P].  Returns f32[P, qlp, lp]; the host
    compares against r^2 and row-slices valid query rows (PAD x PAD
    cancellation can fake a 0 on pad rows — never on valid ones).
    """
    return _pairwise_d2(qslab[iq], rslab[ir])


@jax.jit
def _kde_gauss_kernel(qslab, rslab, iq, ir, scale):
    """Per-query-row gaussian mass from each pair: sum_j exp(-d2*scale),
    f32[P, qlp].  scale = 1/(2 h^2).  PAD ref rows contribute exp(-huge)=0;
    pad QUERY rows collect junk and are sliced off on the host."""
    d2 = _pairwise_d2(qslab[iq], rslab[ir])
    return jnp.exp(-d2 * scale).sum(axis=-1)


@jax.jit
def _kde_tophat_kernel(qslab, rslab, iq, ir, h2):
    """Per-query-row tophat count from each pair: #{j : d2 <= h^2}."""
    d2 = _pairwise_d2(qslab[iq], rslab[ir])
    return (d2 <= h2).astype(jnp.float32).sum(axis=-1)


@jax.jit
def _pair_hist_kernel(aslab, bslab, ia, ib, sa, sb, edges):
    """Distance histogram of leaf x leaf pair batches, np.histogram bins.

    Both sides gather from chunk slabs; sa/sb i32[P] are the real row
    counts (PAD x PAD rows can cancel to a fake 0 distance, so they are
    masked to +inf, which no bin holds).  Returns i32[P, E] integer
    counts for E = len(edges) - 1 bins; the last edge is closed, matching
    np.histogram.

    Bins are counted by comparison, not by a scatter: per pair,
    G[j] = #{dist >= e_j} for j < E and G[E] = #{dist > e_E}, and bin i
    is G[i] - G[i+1].  Distances below e_0 or above e_E (+inf included)
    fall in every term or in none, so they cancel.  The named scopes
    ``pc.distance`` and ``pc.bin`` tag its device operations for the
    profiler.
    """
    with jax.named_scope("pc.distance"):
        d2 = _pairwise_d2(aslab[ia], bslab[ib])
        rows = jnp.arange(d2.shape[1], dtype=jnp.int32)
        cols = jnp.arange(d2.shape[2], dtype=jnp.int32)
        valid = (rows[None, :, None] < sa[:, None, None]) & (
            cols[None, None, :] < sb[:, None, None]
        )
        dist = jnp.where(valid, jnp.sqrt(d2), jnp.inf)
    with jax.named_scope("pc.bin"):
        ge = jnp.sum(dist[:, None] >= edges[:-1, None, None], axis=(2, 3),
                     dtype=jnp.int32)
        gt = jnp.sum(dist > edges[-1], axis=(1, 2), dtype=jnp.int32)
        g = jnp.concatenate([ge, gt[:, None]], axis=1)
        return g[:, :-1] - g[:, 1:]


def dualtree_cache_size() -> int:
    """Total compiled-variant count of the dual-tree leaf-pair kernels —
    the recompile-accounting hook benchmarks assert on (one compile per
    entered rung shape, none on later calls with new r/bandwidth/edges)."""
    return sum(
        k._cache_size()
        for k in (
            _radius_kernel, _kde_gauss_kernel, _kde_tophat_kernel,
            _pair_hist_kernel,
        )
    )


# ---------------------------------------------------------------------------
# The traversal engine
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _TraceStats:
    """Mutable counters one traversal accumulates, frozen into SearchStats."""

    levels: int = 0
    leaf_pairs: int = 0
    batches: int = 0
    chunk_visits: int = 0
    points_paired: int = 0
    shapes: set = dataclasses.field(default_factory=set)

    def freeze(self, m: int) -> SearchStats:
        return SearchStats(
            iterations=self.levels,
            flushes=self.batches,
            units_scanned=self.leaf_pairs,
            points_scanned=self.points_paired,
            queries_advanced=m,
            chunk_rounds=self.chunk_visits,
            plan_shapes=len(self.shapes),
        )


class DualTree:
    """Node-pair frontier ops over a built ``TopTree`` + leaf store.

    ``store`` is the index's ``ChunkedLeafStore`` when its slabs are fp32;
    a quantized store (fp16/int8 codes) cannot feed the distance kernels
    directly, so a private fp32 store with the same chunk layout is built
    from the tree's retained fp32 slabs — dual-tree ops stay exact at any
    index precision, trading host memory (one fp32 slab copy), not
    correctness.
    """

    def __init__(
        self,
        tree: TopTree,
        store: Optional[ChunkedLeafStore] = None,
        *,
        device=None,
    ):
        self.tree = tree
        if store is not None and not store.quantized:
            self.store = store
        else:
            n_chunks = store.n_chunks if store is not None else 1
            device = device if device is not None else (
                store.device if store is not None else None
            )
            dp = (
                store.host.shape[2] if store is not None
                else max(8, -(-tree.d // 8) * 8)
            )
            slabs = tree.points_padded
            if dp != tree.d:
                pad = np.zeros(
                    (slabs.shape[0], slabs.shape[1], dp - tree.d), np.float32
                )
                slabs = np.concatenate([slabs, pad], axis=-1)
            self.store = ChunkedLeafStore(
                slabs, n_chunks=n_chunks, device=device, uniform=True,
                leaf_sizes=tree.leaf_sizes(),
            )
        self.device = self.store.device
        self.bounds = node_bounds(tree)
        self.d_pad = self.store.host.shape[2]
        self._leaf_sizes = tree.leaf_sizes().astype(np.int64)
        # device slab cache for pair_count's (chunk_a, chunk_b) groups:
        # at most two chunk slabs resident, mirroring the store's two slots
        self._slab_cache: Dict[int, jax.Array] = {}

    # -- query-side tree -------------------------------------------------
    def _build_qtree(self, queries: np.ndarray) -> Tuple[TopTree, NodeBounds, jax.Array]:
        """Top tree over the query batch with a FIXED leaf pad (QLEAF) and
        a rung-padded slab count, so the device query slab's shape depends
        only on the batch-size rung — one kernel compile per rung."""
        m = queries.shape[0]
        h = max(1, math.ceil(math.log2(max(2, -(-m // QLEAF)))))
        qt = build_top_tree(queries, h, leaf_pad_multiple=QLEAF)
        qb = node_bounds(qt)
        slab = qt.points_padded
        if self.d_pad != qt.d:
            pad = np.zeros(
                (slab.shape[0], slab.shape[1], self.d_pad - qt.d), np.float32
            )
            slab = np.concatenate([slab, pad], axis=-1)
        ql_pad = _rung_up(slab.shape[0], QLEAF_RUNGS)
        if ql_pad != slab.shape[0]:
            fill = np.full(
                (ql_pad - slab.shape[0], slab.shape[1], self.d_pad),
                np.float32(PAD_COORD),
            )
            fill[:, :, qt.d:] = 0.0
            slab = np.concatenate([slab, fill], axis=0)
        return qt, qb, jax.device_put(slab, self.device)

    # -- frontier expansion ----------------------------------------------
    def _qr_leaf_pairs(
        self, qb: NodeBounds, prune, trace: _TraceStats
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Expand the (query-node, ref-node) frontier down to leaf pairs.

        ``prune(u, v, dmin2, dmax2)`` returns a boolean drop mask (True =
        the pair is fully handled: out of range, or accumulated by the
        op's approximation rule).  Returns (q_leaf_ids, ref_leaf_ids).
        """
        rb = self.bounds
        u = np.array([1], np.int64)
        v = np.array([1], np.int64)
        out_q, out_r = [], []
        while u.size:
            trace.levels += 1
            alive = (qb.count[u] > 0) & (rb.count[v] > 0)
            u, v = u[alive], v[alive]
            if not u.size:
                break
            dmin2, dmax2 = _box_dist2(qb, u, rb, v)
            drop = prune(u, v, dmin2, dmax2)
            u, v = u[~drop], v[~drop]
            q_leaf = u >= qb.first_leaf
            r_leaf = v >= rb.first_leaf
            done = q_leaf & r_leaf
            out_q.append(u[done] - qb.first_leaf)
            out_r.append(v[done] - rb.first_leaf)
            u, v = u[~done], v[~done]
            if not u.size:
                continue
            ql = u >= qb.first_leaf
            rl = v >= rb.first_leaf
            # expand every non-leaf side (both at once when both are
            # internal: 4 children pairs; else 2)
            nu = np.where(ql, u, 2 * u)
            nu2 = np.where(ql, u, 2 * u + 1)
            nv = np.where(rl, v, 2 * v)
            nv2 = np.where(rl, v, 2 * v + 1)
            # a leaf side repeats itself in its two "children", so the
            # 4-way product contains duplicate combos — unique()d away.
            # Child pairs from DISTINCT parents never collide: within one
            # frontier level each side's components all sit at one depth.
            pairs = np.unique(
                np.stack(
                    [
                        np.concatenate([nu, nu2, nu, nu2]),
                        np.concatenate([nv, nv, nv2, nv2]),
                    ],
                    axis=1,
                ),
                axis=0,
            )
            u, v = pairs[:, 0], pairs[:, 1]
        if out_q:
            return np.concatenate(out_q), np.concatenate(out_r)
        return np.zeros(0, np.int64), np.zeros(0, np.int64)

    def _self_leaf_pairs(
        self, prune, trace: _TraceStats
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Symmetric (ref x ref) frontier for pair_count.

        Pairs carry an explicit ordered-pair weight: the diagonal root
        (1, 1) starts at weight 1; expanding a diagonal pair (a, a) yields
        (2a, 2a) w, (2a, 2a+1) 2w, (2a+1, 2a+1) w — the cross pair covers
        both orders.  Off-diagonal pairs have disjoint subtrees, so their
        children inherit the weight unchanged.  ``prune(a, b, w, dmin2,
        dmax2)`` may accumulate and drop.  Returns leaf (a, b, w) arrays.
        """
        rb = self.bounds
        a = np.array([1], np.int64)
        b = np.array([1], np.int64)
        w = np.array([1], np.int64)
        out_a, out_b, out_w = [], [], []
        while a.size:
            trace.levels += 1
            alive = (rb.count[a] > 0) & (rb.count[b] > 0)
            a, b, w = a[alive], b[alive], w[alive]
            if not a.size:
                break
            dmin2, dmax2 = _box_dist2(rb, a, rb, b)
            drop = prune(a, b, w, dmin2, dmax2)
            a, b, w = a[~drop], b[~drop], w[~drop]
            leaf = a >= rb.first_leaf  # a <= b and leaves share one level,
            done = leaf & (b >= rb.first_leaf)
            out_a.append(a[done] - rb.first_leaf)
            out_b.append(b[done] - rb.first_leaf)
            out_w.append(w[done])
            a, b, w = a[~done], b[~done], w[~done]
            if not a.size:
                continue
            diag = a == b
            da = a[diag]
            na = [2 * da, 2 * da, 2 * da + 1]
            nb = [2 * da, 2 * da + 1, 2 * da + 1]
            nw = [w[diag], 2 * w[diag], w[diag]]
            oa, ob, ow = a[~diag], b[~diag], w[~diag]
            if oa.size:
                # both sides are internal here: one tree means every pair's
                # components sit at the same depth, so an off-diagonal pair
                # mixing a leaf with an internal node cannot arise
                na.append(
                    np.concatenate([2 * oa, 2 * oa + 1, 2 * oa, 2 * oa + 1])
                )
                nb.append(
                    np.concatenate([2 * ob, 2 * ob, 2 * ob + 1, 2 * ob + 1])
                )
                nw.append(np.tile(ow, 4))
            a = np.concatenate(na)
            b = np.concatenate(nb)
            w = np.concatenate(nw)
            lohi = np.sort(np.stack([a, b], axis=1), axis=1)
            a, b = lohi[:, 0], lohi[:, 1]
        if out_a:
            return (
                np.concatenate(out_a), np.concatenate(out_b),
                np.concatenate(out_w),
            )
        return (np.zeros(0, np.int64),) * 3

    # -- leaf-pair batching ----------------------------------------------
    def _batches(self, n: int):
        """Yield (lo, hi, rung) slices covering [0, n) at PAIR_RUNGS sizes."""
        top = PAIR_RUNGS[-1]
        lo = 0
        while lo < n:
            take = min(top, n - lo)
            yield lo, lo + take, _rung_up(take, PAIR_RUNGS)
            lo += take

    def _pad_pairs(self, arrs, lo, hi, rung):
        out = []
        for arr in arrs:
            sl = np.asarray(arr[lo:hi], np.int32)
            if sl.size < rung:
                sl = np.concatenate([sl, np.zeros(rung - sl.size, np.int32)])
            out.append(sl)
        return out

    # -- ops ----------------------------------------------------------------
    def radius(
        self, queries: np.ndarray, r: float
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, SearchStats]:
        """All reference points within Euclidean ``r`` (inclusive) of each
        query row, as CSR (indptr i64[m+1], indices i64[nnz] into the
        original point ordering, dists f32[nnz] ascending per row)."""
        queries = np.asarray(queries, np.float32)
        m = queries.shape[0]
        r = float(r)
        if r < 0:
            raise ValueError(f"radius must be >= 0, got {r}")
        trace = _TraceStats()
        if m < 2:
            ip, ix, dd = radius_brute(queries, self.tree.points, r)
            ix = self.tree.orig_idx.astype(np.int64)[ix]
            return ip, ix, dd, trace.freeze(m)
        qt, qb, qslab = self._build_qtree(queries)
        r2 = r * r

        def prune(u, v, dmin2, dmax2):
            return dmin2 > r2

        ql, rl = self._qr_leaf_pairs(qb, prune, trace)
        q_ids, r_ids, dists = [], [], []
        q_start = qt.leaf_start.astype(np.int64)
        q_sizes = qt.leaf_sizes().astype(np.int64)
        r_start = self.tree.leaf_start.astype(np.int64)
        for buf, qsel, rsel, rung, iq, ir in self._stream_ref(ql, rl, trace):
            d2 = np.asarray(_radius_kernel(qslab, buf, iq, ir))
            trace.shapes.add((rung, qslab.shape[0]))
            qlp = d2.shape[1]
            rowok = np.arange(qlp)[None, :] < q_sizes[qsel][:, None]
            hit = (d2[:qsel.size] <= r2) & rowok[:, :, None]
            p, qi, rj = np.nonzero(hit)
            if p.size:
                q_ids.append(q_start[qsel[p]] + qi)
                r_ids.append(r_start[rsel[p]] + rj)
                dists.append(np.sqrt(d2[p, qi, rj]))
        if q_ids:
            qrow = qt.orig_idx.astype(np.int64)[np.concatenate(q_ids)]
            ridx = self.tree.orig_idx.astype(np.int64)[np.concatenate(r_ids)]
            dd = np.concatenate(dists).astype(np.float32)
            order = np.lexsort((dd, qrow))
            qrow, ridx, dd = qrow[order], ridx[order], dd[order]
        else:
            qrow = np.zeros(0, np.int64)
            ridx = np.zeros(0, np.int64)
            dd = np.zeros(0, np.float32)
        indptr = np.zeros(m + 1, np.int64)
        np.cumsum(np.bincount(qrow, minlength=m), out=indptr[1:])
        return indptr, ridx, dd, trace.freeze(m)

    def kde(
        self,
        queries: np.ndarray,
        bandwidth: float,
        *,
        rtol: float = 1e-2,
        atol: float = 1e-9,
        kernel: str = "gaussian",
    ) -> Tuple[np.ndarray, float, SearchStats]:
        """Mean kernel value per query (see module doc for semantics).

        A node pair is midpoint-approximated when the error that adds is
        within ``rtol`` times a lower bound of the pair's own true
        contribution OR within ``atol`` spread over the whole point set —
        so every density satisfies ``|approx - exact| <= rtol*exact +
        atol`` (the atol term is what lets far-field pairs with tiny but
        nonzero kernel mass prune at all).

        Returns (density f32[m], err_bound, stats): ``err_bound`` is the
        largest per-query ABSOLUTE error bound the prune rule actually
        accumulated (0.0 when everything was computed exactly — always
        for tophat, whose prune is exact).  The bound covers traversal
        approximation only; the exact-part kernels run in fp32, which adds
        ordinary fp32 rounding on top.
        """
        queries = np.asarray(queries, np.float32)
        m = queries.shape[0]
        h = float(bandwidth)
        if h <= 0:
            raise ValueError(f"bandwidth must be > 0, got {h}")
        if kernel not in _KERNELS:
            raise ValueError(f"kernel={kernel!r} not in {_KERNELS}")
        rtol = float(rtol)
        atol = float(atol)
        trace = _TraceStats()
        n = self.tree.n
        if m < 2:
            dens = kde_brute(queries, self.tree.points, h, kernel=kernel)
            return dens, 0.0, trace.freeze(m)
        qt, qb, qslab = self._build_qtree(queries)
        h2 = h * h
        rb = self.bounds
        # midpoint contributions accumulated on QUERY heap nodes, pushed
        # down to rows after the traversal
        contrib = np.zeros(2 * qb.first_leaf)
        err = np.zeros(2 * qb.first_leaf)

        if kernel == "gaussian":
            def prune(u, v, dmin2, dmax2):
                kmax = np.exp(-dmin2 / (2.0 * h2))
                kmin = np.exp(-dmax2 / (2.0 * h2))
                # midpoint error (kmax-kmin)/2 per point, accepted against
                # rtol * kmin (a lower bound of the pair's own per-point
                # contribution) or the atol allowance: summed over a
                # query's accepted pairs, err <= rtol*density + atol
                ok = (kmax - kmin) <= 2.0 * np.maximum(rtol * kmin, atol)
                if ok.any():
                    c = rb.count[v[ok]].astype(np.float64)
                    np.add.at(
                        contrib, u[ok], c * 0.5 * (kmax[ok] + kmin[ok]) / n
                    )
                    np.add.at(err, u[ok], c * 0.5 * (kmax[ok] - kmin[ok]) / n)
                return ok
        else:
            def prune(u, v, dmin2, dmax2):
                inside = dmax2 <= h2
                if inside.any():
                    np.add.at(
                        contrib, u[inside],
                        rb.count[v[inside]].astype(np.float64) / n,
                    )
                return inside | (dmin2 > h2)

        ql, rl = self._qr_leaf_pairs(qb, prune, trace)
        density = np.zeros(qt.n)
        kern = _kde_gauss_kernel if kernel == "gaussian" else _kde_tophat_kernel
        karg = (
            jnp.float32(1.0 / (2.0 * h2)) if kernel == "gaussian"
            else jnp.float32(h2)
        )
        q_start = qt.leaf_start.astype(np.int64)
        q_sizes = qt.leaf_sizes().astype(np.int64)
        for buf, qsel, rsel, rung, iq, ir in self._stream_ref(ql, rl, trace):
            part = np.asarray(kern(qslab, buf, iq, ir, karg), np.float64) / n
            trace.shapes.add((rung, qslab.shape[0]))
            for p in range(qsel.size):
                leaf = int(qsel[p])
                s = q_sizes[leaf]
                density[q_start[leaf]:q_start[leaf] + s] += part[p, :s]
        # push node contributions down the query heap to its leaves
        v = 1
        while v < qb.first_leaf:
            sl = slice(v, 2 * v)
            contrib[2 * v:4 * v:2] += contrib[sl]
            contrib[2 * v + 1:4 * v:2] += contrib[sl]
            err[2 * v:4 * v:2] += err[sl]
            err[2 * v + 1:4 * v:2] += err[sl]
            v *= 2
        for leaf in range(qb.first_leaf):
            s = q_sizes[leaf]
            density[q_start[leaf]:q_start[leaf] + s] += contrib[
                qb.first_leaf + leaf
            ]
        out = np.zeros(m, np.float64)
        out[qt.orig_idx.astype(np.int64)] = density
        bound = float(err[qb.first_leaf:].max()) if err.any() else 0.0
        return out.astype(np.float32), bound, trace.freeze(m)

    def pair_count(
        self, edges: np.ndarray
    ) -> Tuple[np.ndarray, SearchStats]:
        """2-point correlation: histogram (np.histogram semantics) of the
        distances of all ordered pairs (i, j), i != j, of the reference
        set against itself.  Returns (hist i64[E], stats)."""
        with TraceAnnotation("pc.call"):
            edges = np.asarray(edges, np.float64).ravel()
            if edges.size < 2 or not np.all(np.diff(edges) > 0):
                raise ValueError(
                    "edges must be >= 2 strictly increasing values"
                )
            if edges[0] < 0:
                raise ValueError("distance edges must be >= 0")
            E = edges.size - 1
            trace = _TraceStats()
            hist = np.zeros(E, np.int64)
            e2 = edges * edges
            rb = self.bounds

            def prune(a, b, w, dmin2, dmax2):
                below = dmax2 < e2[0]
                above = dmin2 > e2[-1]
                bl = np.searchsorted(e2, dmin2, side="right")
                bh = np.searchsorted(e2, dmax2, side="right")
                onebin = (bl == bh) & (bl >= 1) & (bl <= E)
                if onebin.any():
                    width = (
                        w[onebin] * rb.count[a[onebin]] * rb.count[b[onebin]]
                    )
                    np.add.at(hist, bl[onebin] - 1, width)
                return below | above | onebin

            with TraceAnnotation("pc.frontier"):
                la, lb, lw = self._self_leaf_pairs(prune, trace)
                # group leaf pairs by their (chunk_a, chunk_b) so at most two
                # chunk slabs are device-resident at a time (the store's own
                # slot count)
                ca = np.asarray(self.store.chunk_of_leaf(la))
                cb = np.asarray(self.store.chunk_of_leaf(lb))
                order = np.lexsort((lb, la, cb, ca))
                la, lb, lw = la[order], lb[order], lw[order]
                ca, cb = ca[order], cb[order]
                cut = (np.diff(ca) != 0) | (np.diff(cb) != 0)
                group = np.concatenate(
                    [[0], np.nonzero(cut)[0] + 1, [la.size]]
                )
            edges_dev = jnp.asarray(edges, jnp.float32)
            sizes = self._leaf_sizes
            for g in range(group.size - 1):
                glo, ghi = int(group[g]), int(group[g + 1])
                if glo == ghi:
                    continue
                ja, jb = int(ca[glo]), int(cb[glo])
                buf_a, lo_a = self._chunk_slab(ja, trace)
                buf_b, lo_b = self._chunk_slab(jb, trace)
                for lo, hi, rung in self._batches(ghi - glo):
                    lo, hi = glo + lo, glo + hi
                    with TraceAnnotation("pc.batch", batch=trace.batches,
                                         rung=rung):
                        with TraceAnnotation("pc.dispatch"):
                            iq, ir = self._pad_pairs(
                                (la - lo_a, lb - lo_b), lo, hi, rung
                            )
                            sa, sb = self._pad_pairs(
                                (sizes[la], sizes[lb]), lo, hi, rung
                            )
                            h_dev = _pair_hist_kernel(
                                buf_a, buf_b, iq, ir, sa, sb, edges_dev
                            )
                        with TraceAnnotation("pc.readback"):
                            h = np.asarray(h_dev, np.int64)
                            trace.shapes.add((rung, "pc"))
                            trace.batches += 1
                            real = hi - lo
                            trace.leaf_pairs += real
                            trace.points_paired += int(
                                (sizes[la[lo:hi]] * sizes[lb[lo:hi]]).sum()
                            )
                            hist += (h[:real] * lw[lo:hi, None]).sum(axis=0)
            # the traversal counts ordered pairs INCLUDING the diagonal; the
            # n self-pairs sit at distance 0 — remove them from whichever bin
            # holds 0 (if any)
            zbin = np.searchsorted(edges, 0.0, side="right")
            if zbin == 0 and edges[0] == 0.0:
                zbin = 1
            if 1 <= zbin <= E:
                hist[zbin - 1] -= self.tree.n
            return hist, trace.freeze(0)

    # -- chunk streaming helpers ----------------------------------------
    def _stream_ref(self, ql, rl, trace: _TraceStats):
        """Group (query-leaf, ref-leaf) pairs by the chunk owning the ref
        leaf and stream each chunk once (double-buffered by the store),
        yielding rung-padded batches with device-local ref indices."""
        if ql.size == 0:
            return
        chunks = np.asarray(self.store.chunk_of_leaf(rl))
        order = np.argsort(chunks, kind="stable")
        ql, rl, chunks = ql[order], rl[order], chunks[order]
        bounds = np.concatenate(
            [[0], np.nonzero(np.diff(chunks) != 0)[0] + 1, [rl.size]]
        )
        chunk_ids = [int(chunks[b]) for b in bounds[:-1]]
        starts = {c: (int(lo), int(hi)) for c, lo, hi in zip(
            chunk_ids, bounds[:-1], bounds[1:]
        )}
        for j, buf, leaf_lo in self.store.stream(chunk_ids):
            trace.chunk_visits += 1
            glo, ghi = starts[j]
            for lo, hi, rung in self._batches(ghi - glo):
                lo, hi = glo + lo, glo + hi
                iq, ir = self._pad_pairs((ql, rl - leaf_lo), lo, hi, rung)
                trace.batches += 1
                trace.leaf_pairs += hi - lo
                trace.points_paired += int(
                    self._leaf_sizes[rl[lo:hi]].sum()
                )
                yield buf, ql[lo:hi], rl[lo:hi], rung, iq, ir

    def _chunk_slab(self, j: int, trace: _TraceStats) -> Tuple[jax.Array, int]:
        """Device slab for chunk ``j`` with a two-entry cache (pair_count
        needs two chunks at once, which the store's stream cannot serve)."""
        lo, hi = self.store._slab_range(j)
        if j not in self._slab_cache:
            if len(self._slab_cache) >= 2:
                # drop the slab the current chunk-pair group doesn't use
                self._slab_cache.pop(next(iter(self._slab_cache)))
            self._slab_cache[j] = jax.device_put(
                self.store.host[lo:hi], self.device
            )
            trace.chunk_visits += 1
        return self._slab_cache[j], lo

    # -- warmup ----------------------------------------------------------
    def warm(
        self,
        ops: Sequence[str] = ("radius", "kde", "pair_count"),
        *,
        m: Optional[int] = None,
        n_edges: int = 9,
    ) -> None:
        """Precompile every leaf-pair kernel the given ops can hit, at
        every PAIR_RUNGS size (and, for the query-side ops, the QLEAF
        rung ``m`` maps to), so live calls never compile: new radii,
        bandwidths and edge vectors are plain operands.

        ``m`` is the expected query batch size for radius/kde (defaults
        to one query-leaf's worth); ``n_edges`` the expected pair_count
        edge count (bin count + 1) — a DIFFERENT edge count is a new
        kernel shape and would compile once more.
        """
        C = self.store.host.shape[0] // self.store.n_chunks
        lp = self.store.host.shape[1]
        buf = jax.device_put(
            np.full((C, lp, self.d_pad), np.float32(PAD_COORD)), self.device
        )
        mm = int(m) if m else QLEAF
        qh = max(1, math.ceil(math.log2(max(2, -(-mm // QLEAF)))))
        qn = _rung_up(1 << qh, QLEAF_RUNGS)
        qbuf = jax.device_put(
            np.full((qn, QLEAF, self.d_pad), np.float32(PAD_COORD)),
            self.device,
        )
        for rung in PAIR_RUNGS:
            iq = np.zeros(rung, np.int32)
            ir = np.zeros(rung, np.int32)
            if "radius" in ops:
                jax.block_until_ready(_radius_kernel(qbuf, buf, iq, ir))
            if "kde" in ops:
                jax.block_until_ready(
                    _kde_gauss_kernel(qbuf, buf, iq, ir, jnp.float32(1.0))
                )
                jax.block_until_ready(
                    _kde_tophat_kernel(qbuf, buf, iq, ir, jnp.float32(1.0))
                )
            if "pair_count" in ops:
                sz = np.zeros(rung, np.int32)
                edges = jnp.asarray(
                    np.linspace(0.0, 1.0, int(n_edges)), jnp.float32
                )
                jax.block_until_ready(
                    _pair_hist_kernel(buf, buf, iq, ir, sz, sz, edges)
                )


# ---------------------------------------------------------------------------
# Naive all-pairs references (the brute engine's ops + the bench baseline)
# ---------------------------------------------------------------------------
def radius_brute(
    queries: np.ndarray, points: np.ndarray, r: float, *, tile_q: int = 512
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Exact all-pairs radius search (fp32 distances, CSR like
    ``DualTree.radius``; indices into ``points``' own ordering)."""
    queries = np.asarray(queries, np.float32)
    points = np.asarray(points, np.float32)
    m = queries.shape[0]
    # square in f64, like DualTree.radius: fp32 squaring can round the
    # threshold below an exactly-representable boundary distance
    r2 = float(r) ** 2
    rows, cols, dists = [], [], []
    for lo in range(0, m, tile_q):
        q = queries[lo:lo + tile_q]
        # summed in _pairwise_d2's order, so fp32 rounding matches it
        d2 = (
            (q * q).sum(1)[:, None] - 2.0 * (q @ points.T)
            + (points * points).sum(1)[None, :]
        ).astype(np.float32)
        np.maximum(d2, 0.0, out=d2)
        qi, rj = np.nonzero(d2 <= r2)
        rows.append(qi + lo)
        cols.append(rj)
        dists.append(np.sqrt(d2[qi, rj]))
    qrow = np.concatenate(rows) if rows else np.zeros(0, np.int64)
    ridx = np.concatenate(cols) if cols else np.zeros(0, np.int64)
    dd = np.concatenate(dists) if dists else np.zeros(0, np.float32)
    order = np.lexsort((dd, qrow))
    qrow, ridx, dd = qrow[order], ridx[order].astype(np.int64), dd[order]
    indptr = np.zeros(m + 1, np.int64)
    np.cumsum(np.bincount(qrow, minlength=m), out=indptr[1:])
    return indptr, ridx, dd.astype(np.float32),


def kde_brute(
    queries: np.ndarray,
    points: np.ndarray,
    bandwidth: float,
    *,
    kernel: str = "gaussian",
    tile_q: int = 512,
) -> np.ndarray:
    """Exact mean kernel value per query (float64 accumulation)."""
    if kernel not in _KERNELS:
        raise ValueError(f"kernel={kernel!r} not in {_KERNELS}")
    queries = np.asarray(queries, np.float64)
    points = np.asarray(points, np.float64)
    h2 = float(bandwidth) ** 2
    n = points.shape[0]
    out = np.zeros(queries.shape[0])
    for lo in range(0, queries.shape[0], tile_q):
        q = queries[lo:lo + tile_q]
        d2 = (
            (q * q).sum(1)[:, None] + (points * points).sum(1)[None, :]
            - 2.0 * (q @ points.T)
        )
        np.maximum(d2, 0.0, out=d2)
        if kernel == "gaussian":
            out[lo:lo + tile_q] = np.exp(-d2 / (2.0 * h2)).sum(1) / n
        else:
            out[lo:lo + tile_q] = (d2 <= h2).sum(1) / n
    return out.astype(np.float32)


@jax.jit
def _brute_hist_tile(q, points, edges):
    """One tile of the naive pair_count baseline: distances of q x points,
    histogrammed with np.histogram semantics (device-accelerated so the
    dual-tree speedup is measured against an honest baseline)."""
    E = edges.shape[0] - 1
    d2 = jnp.maximum(
        (q * q).sum(1)[:, None]
        - 2.0 * jnp.matmul(q, points.T, precision=jax.lax.Precision.HIGHEST)
        + (points * points).sum(1)[None, :],
        0.0,
    )
    dist = jnp.sqrt(d2).reshape(-1)
    r = jnp.searchsorted(edges, dist, side="right").astype(jnp.int32)
    r = jnp.where(dist == edges[-1], E, r)
    return jnp.bincount(r, length=E + 2)[1:E + 1]


def pair_count_brute(
    points: np.ndarray, edges: np.ndarray, *, tile_q: int = 1024
) -> np.ndarray:
    """Exact all-ordered-pairs (i != j) distance histogram — the naive
    baseline ``benchmarks/dualtree_bench.py`` measures the dual tree
    against.  Tiles the query side only (no PAD x PAD cancellations) and
    removes the n self-pairs from the bin containing 0."""
    points = np.asarray(points, np.float32)
    n = points.shape[0]
    edges = np.asarray(edges, np.float64).ravel()
    E = edges.size - 1
    edges_dev = jnp.asarray(edges, jnp.float32)
    pts = jnp.asarray(points)
    hist = np.zeros(E, np.int64)
    pad = -(-n // tile_q) * tile_q
    qpad = np.full((pad, points.shape[1]), np.float32(PAD_COORD))
    qpad[:n] = points
    for lo in range(0, pad, tile_q):
        hist += np.asarray(
            _brute_hist_tile(jnp.asarray(qpad[lo:lo + tile_q]), pts, edges_dev),
            np.int64,
        )
    zbin = np.searchsorted(edges, 0.0, side="right")
    if zbin == 0 and edges[0] == 0.0:
        zbin = 1
    if 1 <= zbin <= E:
        hist[zbin - 1] -= n
    return hist
