"""The measured window: a closed loop over the pool, timed on the host
clock, optionally under the profiler and the program's span tracer."""
from __future__ import annotations

import shutil
import tempfile
import time
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
CALL_SPAN = "bench.call"
# a traced run profiles the calls of its first TRACE_SECONDS only (at
# least one call), and runs the rest of its window untraced: per-op
# device traces of the solvers' while-loops grow by the second
TRACE_SECONDS = 5.0


@dataclass
class Window:
    records: list = field(default_factory=list)   # (t, plan) per frame
    calls: int = 0
    frames: int = 0
    seconds: float = 0.0
    compiles: int = 0


@dataclass
class Measured:
    """What an end-to-end reader (``e2e/<name>.py``) sees: the whole
    window, the set-up time, the frames on the host, and the cell's
    reference, configuration and check module."""
    window: Window
    setup_s: float
    frame: object
    ref: object
    cfg: dict
    check: object


@dataclass
class Traced:
    """What a per-layer reader sees: the traced window's records, the
    reduced profiler trace and the program's spans."""
    records: list
    calls: int
    frames: int
    trace: object
    spans: list
    cfg: dict
    traffic: dict
    peaks: dict


def run(entry, pool: list, order: list[int], per_call: int,
        seconds: float, clock, annotate=None) -> Window:
    """Call ``entry`` on pool arrays in ``order`` (cycling) until
    ``seconds`` have passed; the window ends when the call in flight
    returns its host results.  ``annotate(name)`` opens a host span."""
    w = Window()
    compiles0 = clock.compiles
    i = 0
    t0 = time.perf_counter()
    while True:
        c = order[i % len(order)]
        if annotate is None:
            plans = entry(pool[c])
        else:
            with annotate(CALL_SPAN):
                plans = entry(pool[c])
        w.records += [(c * per_call + j, p) for j, p in enumerate(plans)]
        w.calls += 1
        w.frames += len(plans)
        i += 1
        if time.perf_counter() - t0 >= seconds:
            break
    w.seconds = time.perf_counter() - t0
    w.compiles = clock.compiles - compiles0
    return w


def traced(entry, pool, order, per_call, seconds, clock):
    """:func:`run` whose first :data:`TRACE_SECONDS` go under
    ``jax.profiler`` and the program's tracer (whose spans also go into
    the profile).  Returns the traced part, the reduced trace, the
    program's span events, and the whole window."""
    import jax
    from repro.obs import trace as obs_trace

    from benchlib import profile

    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            with obs_trace.tracing(jax_annotations=True) as tracer:
                with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                    part = run(entry, pool, order, per_call,
                               min(seconds, TRACE_SECONDS), clock,
                               annotate=jax.profiler.TraceAnnotation)
                spans = tracer.events()
        finally:
            jax.profiler.stop_trace()
        tr = profile.load(tmp, WINDOW_SPAN)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    whole = Window(list(part.records), part.calls, part.frames,
                   part.seconds, part.compiles)
    if seconds > part.seconds:
        k = part.calls % len(order)
        rest = run(entry, pool, order[k:] + order[:k], per_call,
                   seconds - part.seconds, clock)
        whole.records += rest.records
        whole.calls += rest.calls
        whole.frames += rest.frames
        whole.seconds += rest.seconds
        whole.compiles += rest.compiles
    return part, tr, spans, whole
