"""tile_fill.batch: query rows that occupied a slot of a scanned leaf-scan
tile (``SearchStats.rows_scanned``) over the slots of the tiles scanned
(``units_scanned`` x tq), summed over the traced calls, %."""

from bench.lib.readers import share, traced_sum


def read(run):
    calls = run.traced_calls
    rows = sum(getattr(c.stats, "rows_scanned", 0) for c in calls)
    units = traced_sum(run, "units_scanned")
    if not rows or not units:
        return None
    return share(rows, units * run.driver.shapes["tq"])
