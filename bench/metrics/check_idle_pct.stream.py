"""Share of the traced window in which the chip ran no operation while a
``planner.check`` span was open on the host (the host finiteness check
of ``rebalance/planner.py``), the mean over the chips: the idle share of
the checks the trace holds in full, times the checks' share of the
window."""
from benchlib import stages


def read(run):
    return stages.idle_under_span_pct(run, "planner.check")
