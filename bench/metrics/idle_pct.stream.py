"""Share of the traced window in which the chips ran no operation, the
mean over the chips."""
from benchlib import readers


def read(run):
    return readers.idle_pct(run)
