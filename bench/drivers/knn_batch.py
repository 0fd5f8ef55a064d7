"""Closed-loop batch kNN: one caller sends ``KNNIndex.query`` batches of m
queries back to back.

Set-up makes the catalog and a pool of query batches from the seed (the
configuration's mixture, fixed centres), builds the index the planner
chooses, warms it at the batch shape (``KNNIndex.warm``) and makes one
whole call, so that nothing compiles in the window.  Call i of the window
answers pool batch i (mod the pool).

The check draws ``check_queries`` of the window's answered rows from the
seed and compares them with the float64 brute force of ``bench.lib.oracles``:
the final ids and distances, after the chunked rounds, the leaf scan, the
merge, the compaction scatter and the host rescoring.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench.lib import oracles

# the numbers that decide ``correct``, each against the configuration's limit
COMPARED = ("max_rel_dist_err", "max_rel_own_err", "index_mismatches")


class Driver:
    def __init__(self, cfg, traffic, seed, *, log):
        self.cfg, self.traffic, self.seed, self.log = cfg, traffic, int(seed), log
        self.m = int(traffic["m"])
        self.k = int(cfg["k"])
        self.n_pool = int(traffic["pool_batches"])
        self.index = None
        self.answers = []           # (pool batch, dists, ids) per call

    # -- set-up ------------------------------------------------------------
    def make_data(self) -> None:
        """The catalog and the pool of query batches, from the seed."""
        cfg = self.cfg
        centers = oracles.cluster_centers(
            cfg["n_clusters"], cfg["dim"], cfg["centers_seed"])
        self.points = oracles.gaussian_mixture(
            centers, cfg["spread"], cfg["n_points"], self.seed, 0)
        pool = oracles.gaussian_mixture(
            centers, cfg["spread"], self.m * self.n_pool, self.seed, 1)
        self.pool = pool.reshape(self.n_pool, self.m, cfg["dim"])

    def setup(self, phase) -> None:
        from repro.api import IndexSpec, KNNIndex

        cfg = self.cfg
        with phase("data"):
            self.make_data()
        with phase("build"):
            self.index = KNNIndex.build(
                self.points, spec=IndexSpec(**cfg["index_spec"]))
        self.log(self.index.describe())
        with phase("warm"):
            self.index.warm(m=self.m, k=self.k)
        with phase("warm_call"):
            # the first whole call compiles what warm() does not reach
            # (the initial descent, the padding ops) and loads the rest
            self.index.query(self.pool[-1], k=self.k)
        self.shapes = self.read_shapes()
        self.log(f"[setup] knn m={self.m} k={self.k} pool={self.n_pool} "
                 f"engine={self.index.engine_name} h={self.index.height} "
                 f"shapes={self.shapes}")

    def read_shapes(self) -> dict:
        """The leaf scan's shapes, for the readers that count its work; an
        engine whose state is laid out otherwise overrides this."""
        store = self.index._state.store.host
        return {
            "tq": int(self.index._state.engine_tile_q),
            "l_pad": int(store.shape[1]),
            "d_pad": int(store.shape[2]),
            "k": self.k,
            "slab_itemsize": int(store.dtype.itemsize),
            "backend": self.index.scan_backend,
        }

    # -- the window ----------------------------------------------------------
    def call(self, i: int):
        b = i % self.n_pool
        res = self.index.query(self.pool[b], k=self.k)
        self.answers.append((b, res.dists, res.idx))
        return self.m, res.stats

    def compile_count(self) -> int:
        from repro.api import knn_round_cache_size
        from repro.core.chunked_jit import compaction_cache_size

        return int(knn_round_cache_size()) + int(compaction_cache_size())

    def release(self) -> None:
        self.index = None
        gc.collect()

    # -- the check -----------------------------------------------------------
    def check(self, calls):
        """Compare a sample of the window's answers with the oracle."""
        n_calls = len(self.answers)
        total = n_calls * self.m
        s = min(int(self.traffic["check_queries"]), total)
        rng = np.random.default_rng(oracles.seed_sequence(self.seed, 7))
        flat = np.sort(rng.choice(total, size=s, replace=False))
        ci, row = flat // self.m, flat % self.m
        queries = np.stack([self.pool[self.answers[c][0]][r]
                            for c, r in zip(ci, row)])
        dists = np.stack([self.answers[c][1][r] for c, r in zip(ci, row)])
        ids = np.stack([self.answers[c][2][r] for c, r in zip(ci, row)])
        t = time.perf_counter()
        ref = oracles.knn_oracle(self.points, queries, self.k + 1)
        self.log(f"[check] knn oracle over {s} sampled queries of "
                 f"{n_calls} call(s): {time.perf_counter() - t:.3f} s")
        limits = self.cfg["limits"]
        got = oracles.compare_knn(dists, ids, *ref, self.points, queries,
                                  tie_rtol=limits["tie_rtol"])
        self.log(f"[check] tied_ranks={got['tied_ranks']} of {s * self.k} "
                 f"bad_rows={got['bad_rows']}")
        checks = {name: {"value": got[name], "limit": limits[name]}
                  for name in COMPARED}
        return checks, total, 0
