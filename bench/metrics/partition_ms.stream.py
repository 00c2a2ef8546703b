"""Device milliseconds per frame outside the SAT kernels, summed over
the chips: the exact JAG-PQ-OPT (``core/device.py`` with the
``kernels/probe`` kernel) or the SGORP loop (``core/sgorp.py``), from
the profiler trace."""
from benchlib import readers


def read(run):
    return readers.partition_ms(run)
