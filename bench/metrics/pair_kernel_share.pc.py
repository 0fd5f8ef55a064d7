"""pair_kernel_share.pc: device seconds of the leaf-pair histogram program
(``_pair_hist_kernel``) over the traced calls' wall time, %."""

from bench.lib import trace as tr
from bench.lib.readers import device_seconds, share

PATTERNS = ("_pair_hist_kernel",)


def read(run):
    wall = sum(c.wall_s for c in run.traced_calls)
    return share(device_seconds(run, line=tr.MODULES_LINE, patterns=PATTERNS),
                 wall)
