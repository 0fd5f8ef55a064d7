"""Control of the ``pair_count`` driver's comparison: the all-pairs
histogram of ``bench.lib.controls`` (cross term at ``Precision.HIGH``)
against the exact reference histogram, bin for bin."""

import numpy as np

from bench.lib import controls, oracles


def read(d, seed):
    """The control's numbers for driver ``d`` after ``make_data()``."""
    ref = oracles.pair_count_kdtree(d.pos, d.edge_sq)
    ctl = controls.pair_count_control(d.pos, d.edge_sq)
    return {"hist_abs_error": int(np.abs(ctl - ref).sum())}
