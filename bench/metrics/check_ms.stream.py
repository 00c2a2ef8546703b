"""Host milliseconds per frame inside the planner's ``planner.check``
spans (``rebalance/planner.py`` ``_check_finite``: the batch's copy to
the host, the dtype test and the NaN scan; once per ``plan_stream``
call), read from the program's own tracer."""
from benchlib import stages


def read(run):
    return stages.span_ms(run, "planner.check")
