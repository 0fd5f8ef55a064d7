"""leaf_scan_roofline.batch: the leaf-scan kernel's share of its roofline.

The work of the traced calls is ``units_scanned`` work units at the shapes
the rounds used (``bench.lib.work.leaf_scan_work``); the bound is
max(ops / bf16 peak, bytes / HBM peak) over the kernel's summed device
time in the trace.  The kernel computes in f32 with its cross term at
Precision.HIGHEST (several bf16 passes), so the bf16 peak is not reachable
and the share stays well under 100%.
"""

from bench.lib import trace as tr
from bench.lib.peaks import peaks_for
from bench.lib.readers import device_seconds, traced_sum
from bench.lib.work import leaf_scan_work, roofline_share

# the Pallas kernel of kernels/knn_scan.py, as the trace names its op
PATTERNS = ("%leaf_scan_pallas",)


def read(run):
    units = traced_sum(run, "units_scanned")
    secs = device_seconds(run, line=tr.OPS_LINE, patterns=PATTERNS)
    if not units or secs is None:
        return None
    sh = run.driver.shapes
    ops, nbytes = leaf_scan_work(
        units=units, tq=sh["tq"], l_pad=sh["l_pad"], d_pad=sh["d_pad"],
        k=sh["k"], slab_itemsize=sh["slab_itemsize"])
    peaks = peaks_for(run.device_kind)
    got = roofline_share(ops, nbytes, secs, peak_ops=peaks["bf16_flops"],
                         peak_bytes_per_s=peaks["hbm_bytes_per_s"])
    return None if got is None else got[0]
