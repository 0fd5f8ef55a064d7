"""Faults planted under the timed path of the ``knn_forest`` driver: an
answer altered where the engine produces it, half of the batch left out
(that of ``knn_batch``), the search's state handed back unchanged, and the
exchange between chips left out."""

import os

from bench.lib import harness

_knn_half_batch = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "knn_batch.py"))._knn_half_batch


def _forest_answer_altered(mp):
    from repro.api.engines import ForestEngine

    orig = ForestEngine.query

    def bad(self, state, queries, k):
        d, i, stats = orig(self, state, queries, k)
        i = i.copy()
        i[:, -1] = (i[:, -1] + 1) % (i.max() + 1)
        return d, i, stats

    mp.setattr(ForestEngine, "query", bad)


def _forest_state_unchanged(mp):
    import jax.numpy as jnp

    import repro.distributed.forest as fo

    orig = fo.forest_knn

    def unchanged(*a, **kw):
        d, i = orig(*a, **kw)
        # every shard searched, but the lists handed back as they started
        return jnp.full_like(d, jnp.inf), jnp.full_like(i, -1)

    unchanged._cache_size = orig._cache_size     # the program's audit
    mp.setattr(fo, "forest_knn", unchanged)


def _forest_exchange_left_out(mp):
    import jax
    import numpy as np

    import repro.distributed.forest as fo

    orig = fo.forest_knn

    def alone(queries, tree_stk, offsets, *, mesh, **kw):
        # no all-gather of the candidate lists: the first chip's own answer
        dev = mesh.devices.flat[0]
        one = jax.sharding.Mesh(np.array([dev]), mesh.axis_names)
        tree = jax.tree.map(lambda a: jax.device_put(np.asarray(a[:1]), dev),
                            tree_stk)
        return orig(queries, tree, jax.device_put(np.asarray(offsets[:1]),
                                                  dev), mesh=one, **kw)

    alone._cache_size = orig._cache_size         # the program's audit
    mp.setattr(fo, "forest_knn", alone)


FAULTS = [_forest_answer_altered, _knn_half_batch, _forest_state_unchanged,
          _forest_exchange_left_out]
