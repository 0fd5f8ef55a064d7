"""readback_idle_share.pc: device idle time inside the per-batch histogram
readback and host accumulate (the ``pc.readback`` span) over the traced
window, %."""

from bench.lib.spans import idle_share_in

SPANS = ("pc.readback",)


def read(run):
    return idle_share_in(run, SPANS, ("pc.",))
