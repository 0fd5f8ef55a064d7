"""Scan-calibrated cost extraction (roofline methodology, DESIGN.md §6).

``cost_analysis()`` counts a ``lax.scan`` body once — *independent of the
trip count* — so the production (scanned) compile cannot yield total FLOPs.
Calibration therefore compiles two small UNROLLED variants of the same step
(scan_layers=False, G in {1, 2} layer groups, identical mesh/shardings),
where costs are exactly linear in G:

    F_group = F(2) - F(1);   F0 = F(1) - F_group;   F(G) = F0 + G * F_group

The same extrapolation applies to bytes-accessed and to collective bytes
parsed from the optimized HLO.  All other loops in the model are either
python-unrolled (chunked attention) or ``associative_scan`` (SSD/RG-LRU) —
both fully visible to cost analysis — so the group axis is the ONLY
calibrated axis.  The scanned full-depth compile is still used for the
memory-fit proof (scan residual stacks are explicit [G, ...] buffers).

Validated against fully-unrolled lowerings in
tests/test_roofline_calibration.py (scan_layers=False, same model).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

from repro.roofline.analysis import collective_bytes

__all__ = ["CellCosts", "calibrated_costs"]


@dataclasses.dataclass
class CellCosts:
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    coll_detail: Dict
    points: Dict[int, Dict[str, float]]   # raw per-calibration-point values

    def as_dict(self):
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "coll_bytes_per_device": self.coll_bytes_per_device,
            "coll_detail": self.coll_detail,
            "points": {str(k): v for k, v in self.points.items()},
        }


def _costs_of(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
    }


def calibrated_costs(
    compile_at_groups: Callable[[int], object],
    n_groups_true: int,
    *,
    scanned: bool = True,
) -> CellCosts:
    """``compile_at_groups(g)`` must return a COMPILED executable for the
    same step with ``g`` layer groups (identical mesh/shardings).

    With ``scanned=False`` (unrolled HLO, or no group axis) a single compile
    at the true count is trusted directly.
    """
    if not scanned or n_groups_true <= 1:
        comp = compile_at_groups(n_groups_true)
        c = _costs_of(comp)
        coll = collective_bytes(comp.as_text())
        return CellCosts(c["flops"], c["bytes"], float(coll.total),
                         coll.as_dict(), {n_groups_true: c})

    points = {}
    colls = {}
    for g in (1, 2):
        comp = compile_at_groups(g)
        points[g] = _costs_of(comp)
        colls[g] = collective_bytes(comp.as_text())

    def extrap(v1: float, v2: float) -> float:
        slope = v2 - v1
        return (v1 - slope) + n_groups_true * slope

    flops = extrap(points[1]["flops"], points[2]["flops"])
    bytes_ = extrap(points[1]["bytes"], points[2]["bytes"])
    coll = extrap(float(colls[1].total), float(colls[2].total))
    detail = {
        "per_op_g2": colls[2].per_op,
        "count_g2": colls[2].count,
        "wire_ring_extrap": extrap(colls[1].wire_ring, colls[2].wire_ring),
    }
    return CellCosts(flops, bytes_, coll, detail, points)
