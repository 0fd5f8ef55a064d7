"""sync_wait_share.batch: host seconds blocked on readbacks in the round
loop (``SearchStats.sync_wait_s``) over the traced calls' wall time, %."""

from bench.lib.readers import share, traced_sum


def read(run):
    wall = sum(c.wall_s for c in run.traced_calls)
    return share(traced_sum(run, "sync_wait_s"), wall)
