"""Stage times read from a traced window: device time under a named
scope, host time inside a program span, and device idle time under it.

A scope (``jax.named_scope`` in the program) reaches each HLO
instruction's ``op_name`` metadata, for example
``jit(plan_stream)/planner.partition/vmap(heur.stripes)/while/...``:
the name sits between ``/`` or ``(`` and ``/`` or ``)``.  A TPU trace's
XLA Ops events are named by the instruction's text (``%while.370 = ...``)
and carry no metadata, so the scope of an op is looked up in the
compiled HLO of the program the cell runs (:func:`program_op_names`),
by the op's name, result shape, opcode and the names it refers to: an
op of another program, or of the same program compiled otherwise, has
no scope.  The XLA Ops line nests ops (a while op's event covers the
events of its body), so a scope's time is the union of its ops'
intervals, never the sum of their durations.

A TPU trace stops recording ops after a cap of events, and the rest of
the window then looks idle.  So the device readers judge only what the
trace holds in full: a call, or a span, that a recorded op of the chip
starts after (:func:`recorded`).

Every function returns ``None`` where the run holds nothing to read: no
trace, no device ops, no op or span of the name, or none recorded in
full.
"""
from __future__ import annotations

import functools
import inspect
import re

from benchlib import window

_METADATA = re.compile(r",?\s*metadata=\{[^{}]*\}")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_REF = re.compile(r"%([\w.\-]+)")


def scope_pattern(scope: str) -> re.Pattern:
    """Matches ``scope`` as one whole component of an ``op_name`` path."""
    return re.compile(r"(?:^|[/(\s])" + re.escape(scope) + r"(?:[/)\s]|$)")


def union(intervals) -> list[tuple[float, float]]:
    """The union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def overlap(xs, ys) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        a = max(xs[i][0], ys[j][0])
        b = min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def _clipped(tr, intervals):
    return union((max(a, tr.t0), min(b, tr.t1)) for a, b in intervals)


def op_key(text: str) -> tuple | None:
    """What an HLO listing's line and a trace event named by the same
    instruction share: its name, result shape, opcode and the names it
    refers to (operands, called computations).  The trace prints each
    operand's shape too, the listing the metadata."""
    m = _INSTR.match(_METADATA.sub("", text))
    if m is None:
        return None
    name, rest = m.groups()
    op = _OPCODE.search(rest)
    if op is None:
        return None
    shape = rest[:op.start()].strip()
    refs = tuple(_REF.findall(rest[op.end() - 1:]))
    return name, shape, op.group(1), refs


def hlo_op_names(hlo: str) -> dict[tuple, str]:
    """:func:`op_key` -> ``op_name`` metadata, for each instruction of
    HLO text that has one."""
    out = {}
    for line in hlo.splitlines():
        name = _OP_NAME.search(line)
        key = op_key(line) if name else None
        if key is not None:
            out[key] = name.group(1)
    return out


@functools.lru_cache(maxsize=None)
def _compiled_hlo(shape, dtype, P, m, exact) -> str:
    """The HLO that ``planner.plan_stream`` without a mesh compiles: its
    own defaults, passed on to ``batch_device.plan_stream``."""
    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.rebalance import batch_device, planner
    x = jax.ShapeDtypeStruct(shape, jax.numpy.dtype(dtype),
                             sharding=SingleDeviceSharding(jax.devices()[0]))
    kw = {k: p.default for k, p in
          inspect.signature(planner.plan_stream).parameters.items()
          if k in ("k", "rounds", "gamma_dtype", "use_pallas", "interpret")}
    kw["gamma_dtype"] = planner.resolve_gamma_dtype(kw["gamma_dtype"],
                                                    exact=exact)
    return batch_device.plan_stream.lower(
        x, P=P, m=m, exact=exact, **kw).compile().as_text()


def program_op_names(run) -> dict[tuple, str]:
    """:func:`op_key` -> ``op_name`` of the one program both entries
    dispatch per call: ``planner.plan_stream`` on one call's frames with
    its default arguments, as the entries call it."""
    f = run.cfg["frame"]
    shape = (run.traffic["frames_per_call"], f["n1"], f["n2"])
    return hlo_op_names(_compiled_hlo(
        shape, f.get("dtype", "int32"), run.cfg["P"], run.cfg["m"],
        bool(run.traffic.get("exact", False))))


def scope_ops(tr, scope: str, op_names: dict[tuple, str]) -> set[str]:
    """Names of the trace's ops whose instruction lies under ``scope``."""
    rx = scope_pattern(scope)
    return {name for name in tr.text
            if rx.search(op_names.get(op_key(name), ""))}


def scope_intervals(tr, chip: int, names) -> list[tuple[float, float]]:
    """Union of the intervals of ``chip``'s ops named in ``names``,
    clipped to the window (ns on the trace clock)."""
    return _clipped(tr, ((o.start, o.start + o.dur)
                         for o in tr.ops[chip] if o.name in names))


def recorded(tr, chip: int, intervals) -> list[tuple[float, float]]:
    """The ``intervals`` (sorted, disjoint) that end before the last
    recorded op of ``chip`` starts: the trace holds the chip's work
    inside them in full, even where it stopped recording later."""
    if not tr.ops.get(chip):
        return []
    last = max(o.start for o in tr.ops[chip])
    return [(a, b) for a, b in intervals if b <= last]


def scope_ms(run, scope: str) -> float | None:
    """Device milliseconds per frame under ``scope``, summed over chips,
    over the traced calls whose device work the trace holds in full (a
    call's device work lies inside its ``bench.call`` span: it starts
    after the dispatch and ends before the call's results reach the
    host)."""
    tr = run.trace
    if tr is None or not tr.ops or not run.calls:
        return None
    names = scope_ops(tr, scope, program_op_names(run))
    if not names:
        return None
    calls = sorted((max(s, tr.t0), min(s + d, tr.t1)) for n, s, d in tr.spans
                   if n == window.CALL_SPAN and s < tr.t1 and s + d > tr.t0)
    per_call = run.frames / run.calls
    total, seen = 0.0, False
    for c in tr.chips():
        done = recorded(tr, c, calls)
        ns = overlap(scope_intervals(tr, c, names), done)
        if ns > 0:
            total += ns / 1e6 / (per_call * len(done))
            seen = True
    return total if seen else None


def span_ms(run, name: str) -> float | None:
    """Host milliseconds per frame inside the program's ``name`` spans,
    read from its own tracer (``dur`` in microseconds)."""
    spans = [e for e in run.spans if e.get("name") == name]
    if not spans or not run.frames:
        return None
    return sum(e["dur"] for e in spans) / 1e3 / run.frames


def idle_under_span_pct(run, name: str) -> float | None:
    """100 * (device idle time while a ``name`` span is open) / window,
    averaged over chips.  Idle is the time of the spans less the union
    of the chip's op intervals, judged on the spans the trace holds in
    full and carried over to every span of the window, whose length the
    host plane holds; the spans are on the device trace's clock."""
    tr = run.trace
    if tr is None or not tr.ops or tr.window_s <= 0:
        return None
    spans = _clipped(tr, ((s, s + d) for n, s, d in tr.spans if n == name))
    if not spans:
        return None
    shares = []
    for c in tr.chips():
        done = recorded(tr, c, spans)
        if done:
            shares.append(1.0 - overlap(done, tr.busy_intervals(c))
                          / length(done))
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares) * length(spans) / (tr.t1 - tr.t0)
