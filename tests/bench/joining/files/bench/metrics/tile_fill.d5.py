"""tile_fill.d5: query rows that occupied a slot of a scanned leaf-scan
tile over the slots of the tiles scanned, at d = 5, %."""

from bench.lib.readers import share, traced_sum


def read(run):
    units = traced_sum(run, "units_scanned")
    rows = traced_sum(run, "rows_scanned")
    if not rows or not units:
        return None
    return share(rows, units * run.driver.shapes["tq"])
