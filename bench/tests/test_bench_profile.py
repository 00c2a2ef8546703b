"""The reduction from a profiler trace to per-layer metrics, and the
byte counts the rooflines rest on."""
from __future__ import annotations

import types

import benchtiny  # noqa: F401  (puts bench/ on the path)
import pytest

from benchlib import profile, readers, window


def _ev(name, start, dur, stats=()):
    return types.SimpleNamespace(name=name, start_ns=float(start),
                                 duration_ns=float(dur),
                                 end_ns=float(start + dur), stats=stats)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=evs) for ln, evs in lines])


@pytest.fixture
def xspace():
    """Two chips, a window span [1000, 11000] ns on the host, SAT
    kernels, a partition loop and host spans around an idle gap."""
    sat_stats = [("tf_op", "jit(plan_stream)/jit(sat_pallas)/pallas_call")]
    tpu0 = [("XLA Ops", [
        _ev("sat_pallas.2", 1000, 1000, sat_stats),
        _ev("sat_pallas.3", 2000, 1000, sat_stats),
        _ev("while.7", 3000, 3000),
        _ev("fusion.1", 5000, 2000),          # overlaps while.7
        _ev("fusion.1", 10500, 1000),         # runs past the window
    ]), ("XLA Modules", [_ev("jit_plan_stream", 1000, 10500)])]
    tpu1 = [("XLA Ops", [_ev("while.7", 1000, 5000)])]
    host = [("python", [_ev("bench.window", 1000, 10000),
                        _ev("bench.call", 1000, 9000),
                        _ev("planner.dispatch", 7100, 1000),
                        _ev("instant", 500, 0)])]
    return types.SimpleNamespace(planes=[
        _plane("/device:TPU:0", tpu0), _plane("/device:TPU:1", tpu1),
        _plane("/host:CPU", host), _plane("Task Environment", [])])


def test_reduce_busy_kernels_and_window(xspace):
    tr = profile.reduce(xspace, window.WINDOW_SPAN)
    assert tr.chips() == [0, 1]
    assert tr.window_s == pytest.approx(10e-6)
    # chip 0: [1000, 7000] and [10500, 11000] inside the window
    assert tr.busy_intervals(0) == [(1000, 7000), (10500, 11000)]
    assert tr.busy_s(0) == pytest.approx(6.5e-6)
    assert tr.busy_s(1) == pytest.approx(5e-6)
    assert tr.mean_busy_s() == pytest.approx(5.75e-6)
    assert tr.kernel_s(0, readers.SAT2D) == pytest.approx(2e-6)
    assert tr.kernel_s(1, readers.SAT2D) == 0


def test_breakdown(xspace):
    tr = profile.reduce(xspace, window.WINDOW_SPAN)
    top = dict(tr.top_ops(3))
    assert top["while.7"] == pytest.approx((3000 + 5000) / 2 / 1e9)
    gaps = dict(tr.idle_gaps(short_ns=1000))
    # chip 0 idles over [7000, 10500]: its midpoint 8750 lies in
    # bench.call only (planner.dispatch ends at 8100)
    assert gaps == {"bench.call": pytest.approx(3.5e-6)}
    assert len(tr.top_ops(1)) == 1


def test_readers_on_the_reduced_trace(xspace):
    tr = profile.reduce(xspace, window.WINDOW_SPAN)
    cfg = {"frame": {"n1": 4, "n2": 8}}
    run = window.Traced(records=[(0, {}), (1, {})],
                        calls=1, frames=2, trace=tr,
                        spans=[{"name": "planner.dispatch", "dur": 1500.0},
                               {"name": "planner.collect", "dur": 9.0}],
                        cfg=cfg, traffic={}, peaks={"hbm_bytes_per_s": 1e9})
    # (6.5 - 2 + 5) us of non-SAT busy time over 2 frames
    assert readers.partition_ms(run) == pytest.approx(9.5e-3 / 2)
    assert readers.idle_pct(run) == pytest.approx(100 * (1 - 0.575))
    b = readers.sat_bytes((4, 8))
    assert b == 4 * (4 * 8 + 5 * 9)
    want = 100 * (2 * b / 1e9) / 2e-6
    assert readers.sat_roofline(run, readers.SAT2D, b) == pytest.approx(want)
    assert readers.sat_roofline(run, r"\bno_such_kernel\b", b) is None
    from benchlib import spec as benchspec
    cell = benchspec.Cell(benchtiny.spec(), "pic2d.replan-heur")
    assert cell.reader("dispatch_ms.replan").read(run) == pytest.approx(0.75)


def test_readers_return_nothing_without_a_trace():
    run = window.Traced(records=[], calls=0, frames=0, trace=None, spans=[],
                        cfg={}, traffic={}, peaks={})
    assert readers.partition_ms(run) is None
    assert readers.idle_pct(run) is None
    assert readers.sat_roofline(run, readers.SAT2D, 1.0) is None


def test_window_span_is_required(xspace):
    xspace.planes[2].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        profile.reduce(xspace, window.WINDOW_SPAN)


def test_sat_bytes_at_cell_sizes():
    assert readers.sat_bytes((4096, 4096)) == 4 * (4096 ** 2 + 4097 ** 2)
    assert readers.sat_bytes((256, 256, 256)) == 4 * (256 ** 3 + 257 ** 3)


def test_a_recorded_trace_reduces(tmp_path):
    """A trace recorded by jax.profiler on this host reads back with its
    window span (no device plane off the chip, so no ops)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.cumsum(x, axis=0))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(window.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation(window.CALL_SPAN):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = profile.load(str(tmp_path), window.WINDOW_SPAN)
    assert tr.window_s > 0
    assert any(s[0] == window.CALL_SPAN for s in tr.spans)


@pytest.mark.parametrize("text,hit", [
    ("custom-call.3 _row_scan_kernel", True),
    ("custom-call.4 jit(plan_stream)/_col_scan_kernel", True),
    ("custom-call.5 jit(plan_stream)/jit(sat_pallas)/pallas_call", True),
    ("while.7 jit(plan_stream)/_row_scan/while", False),
    ("fusion.2 jit(plan_stream)/sat_pallas_like", False),
])
def test_sat_pattern_names_only_the_sat_kernels(text, hit):
    import re
    assert bool(re.search(readers.SAT2D, text)) is hit
