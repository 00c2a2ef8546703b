"""Host milliseconds per replan inside the planner's ``planner.check``
spans (``rebalance/planner.py`` ``_check_finite``: the frame's copy to
the host, the dtype test and the NaN scan; twice per ``plan_iter``
replan), read from the program's own tracer."""
from benchlib import stages


def read(run):
    return stages.span_ms(run, "planner.check")
