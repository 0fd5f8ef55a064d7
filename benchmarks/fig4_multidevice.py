"""Paper Fig. 4: multi-device speedup from query chunking.

bufferkdtree(1) vs bufferkdtree(P) with queries distributed uniformly among
the P visible devices (paper §3.2).  Runs in the calling process over
``jax.devices()``: one process owns an accelerator, so a child process could
not reach it.  On one device the comparison degenerates to P = 1.  Any
failure propagates, so the benchmark run exits non-zero.
"""

from __future__ import annotations

import time

import jax

from benchmarks.common import row
from repro.api import IndexSpec, KNNIndex
from repro.data.pipeline import PointCloud


def _timed_query(idx: KNNIndex, q, k: int = 10) -> float:
    idx.query(q[:256], k=k)  # warm
    t0 = time.perf_counter()
    idx.query(q, k=k)
    return time.perf_counter() - t0


def run(scale: float = 1.0):
    devs = tuple(jax.devices())
    p = len(devs)
    n = int(50_000 * scale)
    pc = PointCloud(n, 10, seed=0)
    pts = pc.points()
    one = KNNIndex.build(pts, spec=IndexSpec(
        engine="chunked", height=6, tile_q=128, devices=devs[:1]))
    many = KNNIndex.build(pts, spec=IndexSpec(
        engine="sharded", height=6, tile_q=128, devices=devs))
    for m in (int(10_000 * scale), int(40_000 * scale)):
        q = pc.queries(m)
        t1 = _timed_query(one, q)
        tp = _timed_query(many, q)
        row(f"fig4/bufferkdtree1_m{m}", t1, "")
        row(f"fig4/bufferkdtree{p}_m{m}", tp,
            f"speedup={t1 / max(tp, 1e-9):.2f} on {p} {devs[0].platform} "
            "device(s)")
