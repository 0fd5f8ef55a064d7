"""tail_round_share.batch: rounds run at a compacted ladder rung over all
rounds of the traced calls (``tail_rounds`` / ``iterations``), %."""

from bench.lib.readers import share, traced_sum


def read(run):
    return share(traced_sum(run, "tail_rounds"), traced_sum(run, "iterations"))
