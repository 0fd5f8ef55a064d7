"""Faults planted under the timed path of the ``knn_batch`` driver.

Each takes a ``pytest.MonkeyPatch`` and patches the program for the length
of one run, which must then read ``correct`` false."""

import numpy as np


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


FAULTS = [_knn_answer_altered, _knn_half_batch, _knn_state_unchanged]
