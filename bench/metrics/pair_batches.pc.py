"""pair_batches.pc: leaf-pair kernel batches launched per pair_count call
(``SearchStats.flushes``), averaged over the traced calls."""

from bench.lib.readers import traced_sum


def read(run):
    n = len(run.traced_calls)
    total = traced_sum(run, "flushes")
    return total / n if n and total is not None else None
