"""The profiler trace of a traced window, reduced to what metrics read.

``jax.profiler`` writes an ``.xplane.pb``; ``ProfileData`` reads it with
nothing but JAX.  Device planes are named ``/device:TPU:<n>``; their op
line holds one event per HLO operation run (a Pallas kernel is its
``custom-call``, named after the jitted function that wraps the
``pallas_call``).  Host planes hold ``TraceAnnotation`` spans, the
harness's and the program's, on the same clock.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
_OP_LINES = ("XLA Ops",)
_HOST_PLANE = re.compile(r"^/host:")


@dataclass
class Op:
    name: str
    start: float          # ns, on the trace clock
    dur: float            # ns


@dataclass
class Trace:
    """Device ops per chip, host spans, and the traced window [t0, t1]."""
    ops: dict = field(default_factory=dict)      # chip -> [Op]
    text: dict = field(default_factory=dict)     # op name -> name + stats
    spans: list = field(default_factory=list)    # [(name, start, dur)]
    t0: float = 0.0
    t1: float = 0.0

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def chips(self) -> list[int]:
        return sorted(self.ops)

    def busy_intervals(self, chip: int) -> list[tuple[float, float]]:
        """Union of the op intervals of ``chip``, clipped to the window."""
        iv = sorted((max(o.start, self.t0), min(o.start + o.dur, self.t1))
                    for o in self.ops[chip])
        out: list[list[float]] = []
        for a, b in iv:
            if b <= a:
                continue
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return [(a, b) for a, b in out]

    def busy_s(self, chip: int) -> float:
        return sum(b - a for a, b in self.busy_intervals(chip)) / 1e9

    def mean_busy_s(self) -> float:
        chips = self.chips()
        return sum(self.busy_s(c) for c in chips) / max(len(chips), 1)

    def kernel_s(self, chip: int, pattern: str) -> float:
        """Summed device time of the ops whose name or stats match."""
        rx = re.compile(pattern)
        hit = {name for name, text in self.text.items() if rx.search(text)}
        return sum(o.dur for o in self.ops[chip] if o.name in hit) / 1e9

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` op names that took most device time, in seconds per
        chip (summed over chips, divided by the chip count)."""
        tot: dict = {}
        for chip in self.chips():
            for o in self.ops[chip]:
                tot[o.name] = tot.get(o.name, 0.0) + o.dur
        k = max(len(self.chips()), 1)
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9 / k] for name, ns in best]

    def idle_gaps(self, n: int = 10, short_ns: float = 10e3) -> list:
        """Idle time of the first chip, summed by the innermost host span
        open at each gap's midpoint ("(no span)" where none is); gaps
        under ``short_ns`` are summed as "(between ops)"."""
        if not self.ops:
            return []
        busy = self.busy_intervals(self.chips()[0])
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        starts = np.array([s[1] for s in self.spans])
        durs = np.array([s[2] for s in self.spans])
        by: dict = {}
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            if b - a < short_ns:
                label = "(between ops)"
            else:
                mid = (a + b) / 2
                inside = (starts <= mid) & (starts + durs >= mid)
                label = "(no span)"
                if inside.any():
                    idx = np.flatnonzero(inside)
                    label = self.spans[idx[np.argmin(durs[idx])]][0]
            by[label] = by.get(label, 0.0) + (b - a)
        best = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in best]


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def _text(ev) -> str:
    try:
        stats = " ".join(str(v) for _, v in ev.stats)
    except (TypeError, ValueError):
        stats = ""
    return f"{ev.name} {stats}"


def reduce(xspace, window_span: str) -> Trace:
    """Reduce a ``ProfileData`` to a :class:`Trace`.  The window is the
    host span named ``window_span``: the harness opens it around the
    traced calls."""
    tr = Trace()
    for plane in xspace.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name not in _OP_LINES:
                    continue
                for ev in line.events:
                    if ev.name not in tr.text:
                        tr.text[ev.name] = _text(ev)
                    ops.append(Op(ev.name, ev.start_ns, ev.duration_ns))
            tr.ops[int(m.group(2))] = ops
        elif _HOST_PLANE.match(plane.name):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        tr.spans.append((ev.name, ev.start_ns,
                                         ev.duration_ns))
    win = [s for s in tr.spans if s[0] == window_span]
    if not win:
        raise ValueError(f"the trace holds no {window_span!r} span")
    tr.t0 = min(s[1] for s in win)
    tr.t1 = max(s[1] + s[2] for s in win)
    return tr


def load(directory: str, window_span: str) -> Trace:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(directory)), window_span)
