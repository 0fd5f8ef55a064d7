"""Operations and bytes of the program's kernels, computed from the shapes
and dtypes a call actually used.  A change of layout or dtype is therefore
counted as it is, and a roofline share is that work at the chip's peak over
the kernel's measured device time.

Shared arithmetic: later benchmarks add functions and never edit these.
"""

from __future__ import annotations

__all__ = ["leaf_scan_work", "roofline_share"]


def leaf_scan_work(*, units: int, tq: int, l_pad: int, d_pad: int, k: int,
                   tx: int = 512, slab_itemsize: int = 4,
                   query_itemsize: int = 4) -> tuple:
    """(operations, bytes) of ``units`` work units of the leaf-scan kernel
    (``kernels/knn_scan.py``): each unit scores one [tq, d_pad] query tile
    against one [l_pad, d_pad] leaf slab streamed in tiles of ``tx`` rows
    and keeps the k best per query row.

    Operations are those of the distance expansion |q|^2 - 2 q.x + |x|^2
    and its clamp, counting a multiply-add as two:

    - cross term: 2 * tq * l_pad * d_pad;
    - query norms, recomputed per slab tile: 2 * tq * d_pad * (l_pad / tx);
    - slab norms: 2 * l_pad * d_pad;
    - combine and clamp: 4 * tq * l_pad.

    The k-selection's compares are left out: they are not distance work,
    and counting them would credit the kernel for its own selection cost.
    Bytes are one read of the query tile and of the slab, and one write of
    the k distances (f32) and ids (i32) per query row.
    """
    tx = min(tx, l_pad)
    n_tx = -(-l_pad // tx)
    ops_unit = (2 * tq * l_pad * d_pad + 2 * tq * d_pad * n_tx
                + 2 * l_pad * d_pad + 4 * tq * l_pad)
    bytes_unit = (tq * d_pad * query_itemsize + l_pad * d_pad * slab_itemsize
                  + tq * k * 8)
    return units * ops_unit, units * bytes_unit


def roofline_share(ops: float, nbytes: float, seconds: float, *,
                   peak_ops: float, peak_bytes_per_s: float):
    """(share of the roofline in %, the bound that sets it): the least time
    the chip could take, max(ops / peak_ops, bytes / peak_bw), over the
    measured ``seconds``.  None where no time was measured."""
    if not seconds or seconds <= 0:
        return None
    t_ops = ops / peak_ops
    t_bytes = nbytes / peak_bytes_per_s
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
