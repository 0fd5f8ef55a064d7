"""Closed-loop batch kNN on the ``forest`` engine: one buffer k-d tree per
chip under ``shard_map``, the candidate lists merged by an all-gather.

It is the ``knn_batch`` driver (data, pool, window, check) with the index
built from the configuration's ``index_spec``, which names the engine and
its shards; only the shapes the readers count and the program's compile
audit differ, since the forest keeps its trees stacked by shard.
"""

import os

from bench.lib import harness

_batch = harness.load_module(os.path.join(harness.BENCH, "drivers",
                                          "knn_batch.py"))
COMPARED = _batch.COMPARED


class Driver(_batch.Driver):
    def read_shapes(self) -> dict:
        state = self.index._state
        slabs = state.stacked.slabs        # [shards, leaves, L_pad, d_pad]
        return {
            "tq": int(state.tq),
            "l_pad": int(slabs.shape[-2]),
            "d_pad": int(state.d_pad),
            "k": self.k,
            "slab_itemsize": int(slabs.dtype.itemsize),
            "backend": state.backend,
            "shards": int(slabs.shape[0]),
        }

    def compile_count(self) -> int:
        from repro.distributed.forest import forest_knn

        return int(forest_knn._cache_size())
