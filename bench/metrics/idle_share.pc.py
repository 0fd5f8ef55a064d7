"""idle_share.pc: device idle share of the traced pair-count calls, %."""

from bench.lib.readers import idle_share


def read(run):
    return idle_share(run)
