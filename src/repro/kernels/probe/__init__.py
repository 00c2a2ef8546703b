"""Greedy feasibility-probe kernel (counts per candidate bottleneck).

The inner loop of every exact bisection is the Han-et-al greedy probe:
walk a prefix array in maximal steps of load <= L and count the
intervals.  ``kernels.probe`` runs that walk for a whole (stripe,
candidate) grid in one Pallas launch, so the fused SAT -> probe -> cut
path of ``jag_pq_opt_device`` never leaves the device between the
integral image and the realized cuts.
"""
from .ops import probe_counts, probe_counts_impl
from .ref import probe_counts_ref

__all__ = ["probe_counts", "probe_counts_impl", "probe_counts_ref"]
