"""Device milliseconds per replan under the ``heur.stripes`` scope: the
vmapped per-stripe bisections of JAG-M-HEUR and their cuts
(``core/device.py`` ``jag_m_heur_device_impl``), the union of their ops'
intervals in the profiler trace, over the replans it holds in full."""
from benchlib import stages


def read(run):
    return stages.scope_ms(run, "heur.stripes")
