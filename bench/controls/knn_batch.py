"""Control of the ``knn_batch`` driver's comparison: ``check_queries`` rows
of the seed's query pool, answered by the brute-force control of
``bench.lib.controls`` (cross term at ``Precision.HIGH``) and compared with
the float64 brute force by ``oracles.compare_knn``, as a run compares the
window's answers."""

import numpy as np

from bench.lib import controls, oracles


def read(d, seed):
    """The control's numbers for driver ``d`` after ``make_data()``."""
    s = int(d.traffic["check_queries"])
    flat = d.pool.reshape(-1, d.pool.shape[-1])
    rng = np.random.default_rng(oracles.seed_sequence(seed, 7))
    queries = flat[np.sort(rng.choice(flat.shape[0], s, replace=False))]
    ref = oracles.knn_oracle(d.points, queries, d.k + 1)
    return oracles.compare_knn(*controls.knn_control(d.points, queries, d.k),
                               *ref, d.points, queries,
                               tie_rtol=d.cfg["limits"]["tie_rtol"])
