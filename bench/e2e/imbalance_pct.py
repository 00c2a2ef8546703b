"""The paper's load imbalance over every frame planned in the window:
100 * (sum Lmax / sum (total / m) - 1), each Lmax exact (int64, from the
frame on the host) by the mix's check module."""
from benchlib import checks


def read(run):
    return checks.imbalance_pct(
        run.window.records, run.frame, run.cfg["m"],
        lambda f, plan: run.check.lmax(run.ref, f, plan))
