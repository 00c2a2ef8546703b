"""Share of the traced window in which the chip ran no operation."""
from benchlib import readers


def read(run):
    return readers.idle_pct(run)
