"""Arithmetic shared by the per-layer metric readers (``metrics/*.py``).

A reader takes the traced run (:class:`benchlib.window.Traced`) and
returns a number, or ``None`` when the run holds nothing to read.
"""
from __future__ import annotations

# the SAT kernels (kernels/sat/sat.py): their pallas_calls by kernel
# name, or any op under the jitted wrapper ``sat_pallas`` (at 4096^2 it
# holds nothing but the two kernels: no padding, no slicing)
SAT2D = r"_row_scan_kernel|_col_scan_kernel|\bsat_pallas\b"


def partition_ms(run) -> float | None:
    """Device-busy milliseconds per frame outside the SAT kernels, summed
    over the chips."""
    tr = run.trace
    if tr is None or not tr.ops or not run.frames:
        return None
    busy = sum(tr.busy_s(c) - tr.kernel_s(c, SAT2D) for c in tr.chips())
    return 1e3 * busy / run.frames


def idle_pct(run) -> float | None:
    """100 * (1 - busy / window), busy averaged over the chips."""
    tr = run.trace
    if tr is None or not tr.ops or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.mean_busy_s() / tr.window_s)


def sat_roofline(run, pattern: str, frame_bytes: float) -> float | None:
    """Share of the HBM roofline the SAT kernels reach: the algorithm's
    least traffic (``frame_bytes`` per frame) over the chip's peak
    bandwidth, divided by the kernels' summed device time."""
    tr = run.trace
    if tr is None or not tr.ops or not run.frames:
        return None
    secs = sum(tr.kernel_s(c, pattern) for c in tr.chips())
    if secs <= 0:
        return None
    least = run.frames * frame_bytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / secs


def sat_bytes(shape, itemsize: int = 4) -> float:
    """Read each frame once, write its Gamma (each extent + 1) once."""
    cells, gamma = 1, 1
    for n in shape:
        cells *= n
        gamma *= n + 1
    return float(itemsize * (cells + gamma))
