"""The benchmark's harness: spec lookup, device pool, timed window,
profiler reduction and the comparison that decides ``correct``.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own under ``bench/`` and is found by the
name ``BENCHMARK.json`` gives it (see :mod:`benchlib.spec`).
"""
