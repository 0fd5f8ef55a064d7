"""readback_idle_share.batch: device idle time inside the round loop's
blocking readbacks (the ``knn.harvest``, ``knn.compact`` and ``knn.drain``
spans) over the traced window, %.  ``sync_wait_share.batch`` counts the
host's blocking; this is what the blocking costs the device."""

from bench.lib.spans import idle_share_in

SPANS = ("knn.harvest", "knn.compact", "knn.drain")


def read(run):
    return idle_share_in(run, SPANS, ("knn.",))
