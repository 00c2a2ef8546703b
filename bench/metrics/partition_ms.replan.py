"""Device milliseconds per replan outside the SAT kernels: the JAG-M-HEUR
partition (``core/device.py``), from the profiler trace."""
from benchlib import readers


def read(run):
    return readers.partition_ms(run)
