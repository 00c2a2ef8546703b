"""Host milliseconds per replan inside the planner's ``planner.dispatch``
spans (``rebalance/planner.py``: slicing, the host finiteness check and
the asynchronous dispatch), read from the program's own tracer."""


def read(run):
    spans = [e for e in run.spans if e.get("name") == "planner.dispatch"]
    if not spans or not run.frames:
        return None
    return sum(e["dur"] for e in spans) / 1e3 / run.frames
