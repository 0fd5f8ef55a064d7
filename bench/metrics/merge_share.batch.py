"""merge_share.batch: device seconds of the operations under the
``knn.merge`` scope of the fused round (candidate masking, top-k, the
neighbour-state scatters) over the traced calls' wall time, %."""

from bench.lib.readers import share
from bench.lib.spans import scope_seconds


def read(run):
    wall = sum(c.wall_s for c in run.traced_calls)
    return share(scope_seconds(run, "knn.merge"), wall)
