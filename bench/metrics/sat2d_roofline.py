"""The 2D SAT kernels' share of the HBM roofline (``kernels/sat/sat.py``,
``_row_scan_kernel`` and ``_col_scan_kernel``): each frame read once and
its Gamma written once, over the peak bandwidth, against their summed
device time."""
from benchlib import readers


def read(run):
    f = run.cfg["frame"]
    return readers.sat_roofline(run, readers.SAT2D,
                                readers.sat_bytes((f["n1"], f["n2"])))
