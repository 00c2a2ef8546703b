"""Stage readers (``benchlib/stages.py``): device time under a named
scope as a union of nested op intervals over the calls a trace holds in
full, host time in a program span, and device idle time charged to a
span only where the two overlap."""
from __future__ import annotations

import types

import benchtiny
import pytest

from benchlib import profile, stages, window
from benchlib import spec as benchspec

PREFIX = "jit(plan_stream)/planner.partition/"
# instruction -> op_name, as the compiled program states them
OP_NAMES = {
    "while.3": PREFIX + "vmap(exact.row_bisect)/while",
    "fusion.4": PREFIX + "vmap(exact.row_bisect)/while/body/fusion",
    "fusion.5": PREFIX + "vmap(exact.row_bisect)/while/body/add",
    "custom-call.6": PREFIX + "vmap(exact.col_bisect)/while/body/jit(probe)"
                              "/pallas_call",
    "fusion.7": PREFIX + "vmap(exact.row_bisect2)/add",
    "while.8": PREFIX + "vmap(heur.stripes)/while",
    "fusion.9": PREFIX + "exact.col_bisect/select",
}
FRAMES_PER_CALL = 2


def _ev(name, start, dur):
    """An event named as a TPU trace names an op: by its HLO text, the
    operands' shapes included."""
    if name in OP_NAMES:
        name = f"%{name} = s32[8]{{0}} op(s32[8]{{0}} %p)"
    return types.SimpleNamespace(name=name, start_ns=float(start),
                                 duration_ns=float(dur),
                                 end_ns=float(start + dur), stats=())


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=evs) for ln, evs in lines])


@pytest.fixture(autouse=True)
def program(monkeypatch):
    """The compiled program's op names, without compiling one: keyed as
    an HLO listing states each instruction (no operand shapes)."""
    names = {stages.op_key(f"%{k} = s32[8]{{0}} op(%p)"): v
             for k, v in OP_NAMES.items()}
    monkeypatch.setattr(stages, "program_op_names", lambda run: names)
    return names


CALLS = [(1000, 6000), (6000, 9000), (9000, 13000)]


def _xspace(checks: bool = True, tpu0=None):
    """Two chips, a window [1000, 13000] ns and three calls.  On chip 0 a
    while op [1000, 5000) under ``exact.row_bisect`` nests two body ops;
    a probe kernel under ``exact.col_bisect``, a look-alike scope, a
    heuristic stripe loop and a second row bisection follow; its last
    recorded op starts at 9500, inside the third call, so only the first
    two are held in full.  Chip 1 records into the third call's tail.
    Host ``planner.check`` spans cover [500, 1500), [5800, 6500),
    [9000, 9400) and [12500, 13500)."""
    if tpu0 is None:
        tpu0 = [_ev("while.3", 1000, 4000), _ev("fusion.4", 1500, 1000),
                _ev("fusion.5", 3000, 1000), _ev("custom-call.6", 5000, 500),
                _ev("fusion.7", 5500, 300), _ev("while.8", 6500, 1000),
                _ev("while.3", 7500, 1000), _ev("fusion.9", 9500, 500)]
    tpu1 = [_ev("while.3", 1000, 2000), _ev("while.3", 6000, 1000),
            _ev("fusion.7", 12000, 500)]
    host = [_ev(window.WINDOW_SPAN, 1000, 12000),
            _ev("planner.py:72 _check_finite", 12500, 1000)]
    host += [_ev(window.CALL_SPAN, a, b - a) for a, b in CALLS]
    if checks:
        host += [_ev("planner.check", 500, 1000),
                 _ev("planner.check", 5800, 700),
                 _ev("planner.check", 9000, 400),
                 _ev("planner.check", 12500, 1000)]
    return types.SimpleNamespace(planes=[
        _plane("/device:TPU:0", [("XLA Ops", tpu0)]),
        _plane("/device:TPU:1", [("XLA Ops", tpu1)]),
        _plane("/host:CPU", [("python", host)])])


def _run(xspace, spans=()):
    calls = len(CALLS)
    return window.Traced(
        records=[(i, {}) for i in range(calls * FRAMES_PER_CALL)],
        calls=calls, frames=calls * FRAMES_PER_CALL,
        trace=profile.reduce(xspace, window.WINDOW_SPAN),
        spans=list(spans), cfg={}, traffic={}, peaks={})


CHECK_SPANS = [{"name": "planner.check", "dur": 1500.0},
               {"name": "planner.dispatch", "dur": 700.0},
               {"name": "planner.check", "dur": 500.0}]

# chip 0 idles 0 + 700 + 400 and chip 1 0 + 200 + 400 of the 1600 ns of
# the three checks it holds in full; that share of the 2100 ns of checks
# in the window, over its 12000 ns
CHECK_IDLE = 100 * (1100 + 600) / 2 / 1600 * 2100 / 12000

# metric -> its value on the fixture above
WANT = {
    # (1500 + 500) us over 6 frames
    "check_ms.replan": 2.0 / 6,
    "check_ms.stream": 2.0 / 6,
    "check_idle_pct.replan": CHECK_IDLE,
    "check_idle_pct.stream": CHECK_IDLE,
    # [6500, 7500) on chip 0, over the 4 frames of its two held calls
    "heur_stripes_ms.replan": 1000 / 1e6 / 4,
}


def _reader(metric):
    spec = benchtiny.spec()
    cell = next(w["name"] for w in spec["workloads"]
                if any(m["name"] == metric and w["name"] in m["workloads"]
                       for m in spec["per_layer"]))
    return benchspec.Cell(spec, cell).reader(metric)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_stage_readers_on_a_synthetic_trace(metric):
    got = _reader(metric).read(_run(_xspace(), CHECK_SPANS))
    assert got == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", sorted(WANT))
def test_stage_readers_return_nothing_without_their_name(metric, program):
    """A program without scopes (as before they were put in), no
    ``planner.check`` span: nothing to read; and no trace at all: nothing
    either."""
    for k, v in program.items():
        program[k] = v.replace("exact.", "").replace("heur.", "")
    bare = _run(_xspace(checks=False),
                [s for s in CHECK_SPANS if s["name"] != "planner.check"])
    assert _reader(metric).read(bare) is None
    empty = window.Traced(records=[], calls=0, frames=0, trace=None,
                          spans=[], cfg={}, traffic={}, peaks={})
    assert _reader(metric).read(empty) is None


def test_nested_ops_count_once():
    tr = profile.reduce(_xspace(), window.WINDOW_SPAN)
    names = stages.scope_ops(tr, "exact.row_bisect",
                             stages.program_op_names(None))
    assert {n.split(" ")[0] for n in names} == {
        "%while.3", "%fusion.4", "%fusion.5"}
    assert stages.scope_intervals(tr, 0, names) == [(1000, 5000),
                                                    (7500, 8500)]
    assert sum(o.dur for o in tr.ops[0] if o.name in names) == 7000
    # chip 0: [1000, 5000) and [7500, 8500); chip 1: [1000, 3000) and
    # [6000, 7000); each over the 4 frames of the two calls it holds
    assert stages.scope_ms(_run(_xspace()), "exact.row_bisect") == \
        pytest.approx((5000 + 3000) / 1e6 / 4)


def test_scope_time_counts_only_calls_held_in_full():
    """The probe kernel [5000, 5500) counts; the column op [9500, 10000)
    lies in the third call, which chip 0's trace does not hold in full."""
    assert stages.scope_ms(_run(_xspace()), "exact.col_bisect") == \
        pytest.approx(500 / 1e6 / 4)


@pytest.mark.parametrize("cap", [6000, 6500, 7500, 8800])
def test_scope_time_does_not_move_with_where_the_trace_stops(cap):
    """A trace that stops recording inside the second call reads the
    first call alone, wherever in the second call it stops."""
    ops = [_ev("while.3", 1000, 4000), _ev("fusion.4", 1500, 1000),
           _ev("while.3", 6000, 3000)]
    ops += [_ev("fusion.4", t, 100) for t in range(6000, cap, 200)]
    run = _run(_xspace(tpu0=ops))
    run.trace.ops.pop(1)
    assert stages.scope_ms(run, "exact.row_bisect") == \
        pytest.approx(4000 / 1e6 / FRAMES_PER_CALL)


def test_a_trace_holding_no_whole_call_reads_nothing():
    run = _run(_xspace(tpu0=[_ev("while.3", 1000, 4000)]))
    run.trace.ops.pop(1)
    assert stages.scope_ms(run, "exact.row_bisect") is None
    # nor any check: none ends before the last recorded op starts; one
    # op recorded later makes the first check held, over busy time
    assert stages.idle_under_span_pct(run, "planner.check") is None
    run.trace.ops[0].append(profile.Op("x", 5000.0, 1.0))
    assert stages.idle_under_span_pct(run, "planner.check") == \
        pytest.approx(0.0)


def test_idle_is_charged_only_where_the_gap_overlaps_the_span():
    """Chip 0 idles over [5800, 6500), [8500, 9500) and from 10000 on;
    only the part inside a ``planner.check`` span that the trace holds
    counts, and a Python-tracer frame of the same extent as the last
    span is not the span."""
    run = _run(_xspace())
    assert run.trace.busy_intervals(0) == [(1000, 5800), (6500, 8500),
                                           (9500, 10000)]
    assert stages.idle_under_span_pct(run, "planner.check") == \
        pytest.approx(CHECK_IDLE)
    assert stages.idle_under_span_pct(run, "planner.dispatch") is None


@pytest.mark.parametrize("text,hit", [
    ("while.3 jit(f)/planner.partition/vmap(exact.row_bisect)/while", True),
    ("fusion.1 jit(f)/exact.row_bisect/while/body/add", True),
    ("fusion.2 jit(f)/vmap(vmap(exact.row_bisect))/while", True),
    ("fusion.3 jit(f)/vmap(exact.row_bisect2)/add", False),
    ("fusion.4 jit(f)/my_exact.row_bisect/add", False),
    ("fusion.5 jit(f)/exact.row_realize/add", False),
])
def test_scope_pattern_matches_whole_components(text, hit):
    assert bool(stages.scope_pattern("exact.row_bisect").search(text)) is hit


def test_union_and_overlap():
    assert stages.union([(5, 7), (1, 3), (2, 4), (7, 9), (6, 6)]) == [
        (1, 4), (5, 9)]
    assert stages.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert stages.overlap([], [(0, 1)]) == 0


@pytest.mark.parametrize("exact", [False, True])
def test_program_op_names_hold_the_scopes(exact, monkeypatch):
    """The reader's own compile of a cell's program (the tiny
    configuration, on the CPU) names its instructions' scopes."""
    monkeypatch.undo()
    cfg = dict(benchtiny.TINY_CONFIG["pic2d-hotspot-4096"])
    run = types.SimpleNamespace(cfg=cfg, traffic={"frames_per_call": 2,
                                                  "exact": exact})
    names = stages.program_op_names(run)
    scope = "exact.col_bisect" if exact else "heur.stripes"
    rx = stages.scope_pattern(scope)
    assert any(rx.search(v) for v in names.values())
    assert all(not k[0].startswith("%") and " " not in k[0] for k in names)


# a TPU trace's event and the compiled listing's line of one instruction
TRACE_OP = ("%while.370 = (s32[]{:T(128)}, /*index=1*/s32[16,8]{0,1:T(8,128)"
            "S(1)}) while((s32[]{:T(128)}, /*index=1*/s32[16,8]{0,1:T(8,128)"
            "S(1)}) %tuple.495), condition=%region_12.31.clone, "
            "body=%region_5.30.sunk")
LISTED_OP = ("  %while.370 = (s32[]{:T(128)}, /*index=1*/s32[16,8]"
             "{0,1:T(8,128)S(1)}) while(%tuple.495), "
             "condition=%region_12.31.clone, body=%region_5.30.sunk, "
             "metadata={op_name=\"jit(f)/vmap(exact.row_bisect)/while\" "
             "source_file=\"d.py\"}, "
             "backend_config={\"flag\":\"1\"}")


@pytest.mark.parametrize("other,same", [
    (LISTED_OP, True),
    (LISTED_OP.replace("%tuple.495", "%tuple.496"), False),
    (LISTED_OP.replace("s32[16,8]", "s32[32,8]"), False),
    (LISTED_OP.replace("while.370", "while.371"), False),
    (LISTED_OP.replace("region_5.30", "region_5.31"), False),
])
def test_op_key_matches_only_the_same_instruction(other, same):
    """The trace prints operand shapes, the listing metadata and backend
    config; a program compiled otherwise differs in a name, a shape or a
    reference."""
    assert (stages.op_key(TRACE_OP) == stages.op_key(other)) is same
    assert stages.op_key(TRACE_OP) == (
        "while.370",
        "(s32[]{:T(128)}, /*index=1*/s32[16,8]{0,1:T(8,128)S(1)})", "while",
        ("tuple.495", "region_12.31.clone", "region_5.30.sunk"))


def test_hlo_op_names():
    hlo = """HloModule m
%fused_computation.1 (p: s32[8]) -> s32[8] {
  ROOT %add.1 = s32[8]{0} add(s32[8]{0} %p, s32[8]{0} %p), metadata={op_name="jit(f)/a.b/add" source_file="x.py"}
}
ENTRY %main (x: s32[8]) -> s32[8] {
  %x = s32[8]{0} parameter(0)
  ROOT %fusion.2 = s32[8]{0} fusion(s32[8]{0} %x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/vmap(a.b)/add"}
}
"""
    assert stages.hlo_op_names(hlo) == {
        ("add.1", "s32[8]{0}", "add", ("p", "p")): "jit(f)/a.b/add",
        ("fusion.2", "s32[8]{0}", "fusion", ("x", "fused_computation.1")):
            "jit(f)/vmap(a.b)/add"}
