"""Data generators and plain references of the benchmark.

Everything here is independent of the code under test: it imports nothing
of ``repro`` and takes nothing the program has made.  The generators follow
the recipes of ``PointCloud`` (``src/repro/data/pipeline.py``) and the smoke
run's ``lattice_catalog``; the references and agreement checks are copies of
the float64 / integer NumPy oracles that the one-chip smoke run proved.
Later changes to the program therefore cannot move them.

Files in ``bench/lib`` are shared arithmetic: later benchmarks add to them
and never edit what is here.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

__all__ = [
    "seed_sequence",
    "cluster_centers",
    "gaussian_mixture",
    "lattice_catalog",
    "knn_oracle",
    "compare_knn",
    "pair_count_oracle",
    "pair_count_kdtree",
]


def seed_sequence(seed: int, *salt: int) -> np.random.SeedSequence:
    """A SeedSequence for any whole ``seed`` (negative ones are folded into
    64 bits), salted so that each stream of a run is its own."""
    return np.random.SeedSequence([int(seed) % (1 << 64), *salt])


# ---------------------------------------------------------------------------
# Generators
# ---------------------------------------------------------------------------
def cluster_centers(n_clusters: int, dim: int, centers_seed: int,
                    low: float = -1.0, high: float = 1.0) -> np.ndarray:
    """The fixed cluster centres of a deployment, uniform in [low, high)^dim."""
    rng = np.random.default_rng(seed_sequence(centers_seed, 2))
    return rng.uniform(low, high, size=(n_clusters, dim)).astype(np.float32)


def gaussian_mixture(centers: np.ndarray, spread: float, count: int,
                     seed: int, salt: int) -> np.ndarray:
    """``count`` points of the Gaussian mixture around ``centers`` (the
    PointCloud recipe: a uniformly chosen centre plus N(0, spread^2) noise
    in every coordinate), float32, drawn in bulk from (seed, salt)."""
    rng = np.random.default_rng(seed_sequence(seed, 3, salt))
    which = rng.integers(0, centers.shape[0], size=count)
    pts = rng.standard_normal((count, centers.shape[1]), dtype=np.float32)
    pts *= np.float32(spread)
    pts += centers[which]
    return pts


def lattice_catalog(n: int, *, span: int, n_clusters: int, radius: float,
                    centers_seed: int, seed: int) -> np.ndarray:
    """Clustered 3-D positions on the integer lattice [0, span)^3: uniform
    balls of ``radius`` around fixed centres, rounded to the lattice
    (float32 holding integers)."""
    crng = np.random.default_rng(seed_sequence(centers_seed, 5))
    centers = crng.uniform(radius, span - 1 - radius, size=(n_clusters, 3))
    rng = np.random.default_rng(seed_sequence(seed, 5))
    u = rng.normal(size=(n, 3))
    u /= np.linalg.norm(u, axis=1, keepdims=True)
    radial = radius * rng.random(n) ** (1.0 / 3.0)
    pos = centers[rng.integers(0, n_clusters, n)] + u * radial[:, None]
    return np.clip(np.rint(pos), 0, span - 1).astype(np.float32)


# ---------------------------------------------------------------------------
# References
# ---------------------------------------------------------------------------
def _host_map(fn, items, workers: int = 12):
    """Map ``fn`` over ``items`` on a thread pool: NumPy's matmuls and ufuncs
    release the GIL, so blocks of an oracle run on the host's cores (all
    but one, at most ``workers``)."""
    workers = max(1, min(workers, (os.cpu_count() or 2) - 1))
    with ThreadPoolExecutor(max_workers=workers) as ex:
        return list(ex.map(fn, items))


def knn_oracle(points: np.ndarray, queries: np.ndarray, k: int,
               block: int = 32768, sample: int = 16384):
    """Exact kNN in float64 on the host, blocked over the reference set.

    Returns (Euclidean dists f64[m, k], ids i64[m, k]) ascending.  Every
    block scores |x|^2 - 2 q.x with one float64 matmul and keeps the points
    at or below the k-th best score among the first ``sample`` points (an
    upper bound on the final k-th); the survivors are rescored directly as
    sum((x - q)^2).
    """
    q = np.asarray(queries, np.float64)
    m = q.shape[0]
    qt = -2.0 * q.T

    def scores(lo, size=block):
        x = np.asarray(points[lo:lo + size], np.float64)
        v = x @ qt
        v += np.einsum("nd,nd->n", x, x)[:, None]          # [b, m]
        return v

    v0 = scores(0, max(sample, k))
    thr = np.partition(v0.T, k - 1, axis=1)[:, k - 1]
    del v0

    def candidates(lo):
        v = scores(lo)
        rows, cols = np.nonzero(v <= thr[None, :])
        return cols, v[rows, cols], rows + lo

    parts = _host_map(candidates, range(0, points.shape[0], block))
    cq, cv, ci = (np.concatenate(p) for p in zip(*parts))
    order = np.lexsort((ci, cv, cq))
    cq, ci = cq[order], ci[order]
    rank = np.arange(cq.size) - np.searchsorted(cq, np.arange(m))[cq]
    best_i = ci[rank < k].reshape(m, k)
    diff = np.asarray(points[best_i], np.float64) - q[:, None, :]
    d = np.sqrt(np.einsum("mkd,mkd->mk", diff, diff))
    order = np.argsort(d, axis=1, kind="stable")
    return np.take_along_axis(d, order, 1), np.take_along_axis(best_i, order, 1)


def compare_knn(dists, idx, ref_d, ref_i, points, queries,
                tie_rtol: float) -> dict:
    """Numbers by which one kNN answer departs from the oracle.

    ``ref_d``/``ref_i`` hold k + 1 oracle neighbours so that a tie across
    rank k is visible.  Returns:

    - ``max_rel_dist_err``: largest |d - d_ref| / d_ref over all ranks;
    - ``max_rel_own_err``: largest gap between a returned distance and the
      float64 distance of the id returned with it, relative;
    - ``index_mismatches``: ranks whose id differs from the oracle's where
      the oracle's own distances do not tie a neighbouring rank within
      ``tie_rtol``;
    - ``bad_rows``: rows holding an invalid (negative) or repeated id.
    """
    k = dists.shape[1]
    d = np.asarray(dists, np.float64)
    rd = ref_d[:, :k]
    rel = np.abs(d - rd) / np.maximum(rd, 1e-30)
    tie_next = np.diff(ref_d, axis=1) <= tie_rtol * ref_d[:, 1:]   # r ~ r+1
    tied = tie_next[:, :k].copy()
    tied[:, 1:] |= tie_next[:, :k - 1]
    mism = (idx != ref_i[:, :k]) & ~tied
    safe = np.clip(idx, 0, None)
    diff = np.asarray(points[safe], np.float64) - np.asarray(
        queries, np.float64)[:, None, :]
    own = np.sqrt(np.einsum("mkd,mkd->mk", diff, diff))
    own_rel = np.abs(own - d) / np.maximum(own, 1e-30)
    srt = np.sort(idx, axis=1)
    bad = (idx < 0).any(axis=1) | (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    return {
        "max_rel_dist_err": float(np.nan_to_num(rel, nan=np.inf).max()),
        "max_rel_own_err": float(np.nan_to_num(own_rel, nan=np.inf).max()),
        "index_mismatches": int(mism.sum()),
        "bad_rows": int(bad.sum()),
        "tied_ranks": int(tied.sum()),
    }


def pair_count_oracle(pos: np.ndarray, edge_sq, block: int = 256):
    """Histogram of all ordered pairs (i != j) of integer positions over the
    edges sqrt(edge_sq), in exact integer arithmetic (squared distances of
    lattice points below 2^31).  O(n^2): the witness for the faster
    ``pair_count_kdtree`` at sizes a test can hold."""
    p = np.asarray(pos, np.int32)
    e2 = np.asarray(edge_sq, np.int64)
    cap = int(e2[-1]) + 1

    def counts(lo):
        a = p[lo:lo + block]
        d2 = np.zeros((a.shape[0], p.shape[0]), np.int32)
        for c in range(p.shape[1]):
            diff = np.subtract(a[:, c:c + 1], p[None, :, c])
            np.multiply(diff, diff, out=diff)
            d2 += diff
        np.minimum(d2, cap, out=d2)
        return np.bincount(d2.ravel(), minlength=cap + 1)

    total = np.sum(_host_map(counts, range(0, p.shape[0], block)), axis=0)
    # d2 never equals an edge (edge_sq = 7 mod 8); the self pairs sit at 0
    cum = np.concatenate([[0], np.cumsum(total)])
    return cum[e2[1:]] - cum[e2[:-1]]


def pair_count_kdtree(pos: np.ndarray, edge_sq) -> np.ndarray:
    """The same histogram as ``pair_count_oracle`` from SciPy's k-d tree
    neighbour counts in float64: ``count_neighbors`` gives, for each edge,
    the ordered pairs (self pairs included) at distance <= edge, and the
    bins are their differences.  Exact for lattice positions whose squared
    edges are 7 mod 8: no squared distance lies within a unit of an edge^2,
    far above float64 rounding."""
    from scipy.spatial import cKDTree

    p = np.asarray(pos, np.float64)
    tree = cKDTree(p)
    edges = np.sqrt(np.asarray(edge_sq, np.float64))
    cum = np.asarray(tree.count_neighbors(tree, edges), np.int64)
    return np.diff(cum)
