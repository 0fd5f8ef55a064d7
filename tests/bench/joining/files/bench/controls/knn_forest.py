"""Control of the ``knn_forest`` driver: that of ``knn_batch``, since the
forest answers the same queries over the same catalog and is compared by
the same numbers."""

import os

from bench.lib import harness

read = harness.load_module(os.path.join(harness.BENCH, "controls",
                                        "knn_batch.py")).read
