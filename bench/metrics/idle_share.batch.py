"""idle_share.batch: device idle share of the traced batch calls, %."""

from bench.lib.readers import idle_share


def read(run):
    return idle_share(run)
