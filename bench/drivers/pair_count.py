"""Closed-loop 2-point correlation: one caller asks ``KNNIndex.pair_count``
for the whole histogram back to back on the catalog made at set-up.

Set-up makes the lattice catalog from the seed (fixed cluster centres),
builds the index for ``op="pair_count"``, warms the leaf-pair kernels at
their rung shapes (``KNNIndex.warm``) and makes one whole call.  No result
is cached between calls: each call runs the whole dual-tree traversal.

The check compares every histogram of the window, bin for bin, with the
reference histogram of ``bench.lib.oracles`` (exact integers): the bins the
leaf-pair kernels count and those the frontier counts wholesale.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench.lib import oracles

# the number that decides ``correct``: an exact comparison, limit 0
COMPARED = ("hist_abs_error",)


class Driver:
    def __init__(self, cfg, traffic, seed, *, log):
        self.cfg, self.traffic, self.seed, self.log = cfg, traffic, int(seed), log
        self.edge_sq = np.asarray(cfg["edge_sq"], np.int64)
        self.edges = np.sqrt(self.edge_sq.astype(np.float64))
        self.index = None
        self.hists = []

    def make_data(self) -> None:
        """The lattice catalog, from the seed."""
        cfg = self.cfg
        self.pos = oracles.lattice_catalog(
            cfg["n_points"], span=cfg["span"], n_clusters=cfg["n_clusters"],
            radius=cfg["cluster_radius"], centers_seed=cfg["centers_seed"],
            seed=self.seed)

    def setup(self, phase) -> None:
        from repro.api import IndexSpec, KNNIndex

        cfg = self.cfg
        with phase("data"):
            self.make_data()
        with phase("build"):
            self.index = KNNIndex.build(
                self.pos, spec=IndexSpec(**cfg["index_spec"]))
        self.log(self.index.describe())
        with phase("warm"):
            self.index.warm(ops=("pair_count",), n_edges=self.edges.size)
        with phase("warm_call"):
            self.index.pair_count(self.edges)
        self.log(f"[setup] pair_count n={cfg['n_points']} "
                 f"h={self.index.height} bins={self.edges.size - 1}")

    def call(self, i: int):
        res = self.index.pair_count(self.edges)
        self.hists.append(np.asarray(res.values, np.int64))
        return 1, res.stats

    def compile_count(self) -> int:
        from repro.api import dualtree_cache_size

        return int(dualtree_cache_size())

    def release(self) -> None:
        self.index = None
        gc.collect()

    def check(self, calls):
        """Every window histogram against the reference, bin for bin."""
        t = time.perf_counter()
        ref = oracles.pair_count_kdtree(self.pos, self.edge_sq)
        self.log(f"[check] pair-count reference: "
                 f"{time.perf_counter() - t:.3f} s; ref={ref.tolist()}")
        err = max(int(np.abs(h - ref).sum()) for h in self.hists)
        self.log(f"[check] first histogram={self.hists[0].tolist()}")
        limits = self.cfg["limits"]
        checks = {"hist_abs_error": {"value": err,
                                     "limit": limits["hist_abs_error"]}}
        return checks, len(self.hists), 0
