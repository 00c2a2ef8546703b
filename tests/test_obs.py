"""Observability layer: tracer, counters, explain(), ledger, profiling.

Covers the tentpole invariants: explain() is bit-identical to the plain
partition call; counters are per-call and internally consistent
(hits + misses == lookups); the tracer composes with enclosing tracing
blocks and always restores global state; emitted traces validate against
the Chrome trace_event structure end-to-end (including the demo script's
file on disk).
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import obs
from repro.core import prefix, registry
from repro.obs.counters import C
from repro.rebalance import migrate, planner, runtime, stream
from repro.rebalance import faults as faults_mod
from repro.rebalance.policy import AlwaysRebalance, HysteresisPolicy
from repro.serve import batcher


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small_gamma(n=48, seed=0):
    return prefix.prefix_sum_2d(prefix.uniform_instance(n, n, delta=1.3,
                                                        seed=seed))


# ---------------------------------------------------------------------------
# tracer


def test_tracing_disabled_by_default_and_noop():
    assert not obs.enabled()
    sp = obs.span("anything", x=1)
    with sp as s:
        s.args["later"] = 2  # the no-op span must absorb arg writes
    assert obs.TRACER.events() == []


def test_tracing_records_and_restores():
    with obs.tracing() as tr:
        assert obs.enabled()
        with obs.span("work", k=3):
            pass
        obs.instant("marker", v=1)
        ev = tr.events()
    assert not obs.enabled()
    names = [e["name"] for e in ev]
    assert names == ["work", "marker"]
    x = next(e for e in ev if e["name"] == "work")
    assert x["ph"] == "X" and x["dur"] >= 0 and x["args"] == {"k": 3}
    i = next(e for e in ev if e["name"] == "marker")
    assert i["ph"] == "i"


def test_tracing_nested_blocks_compose():
    with obs.tracing() as outer:
        obs.instant("outer")
        with obs.tracing(clear=False):
            obs.instant("inner")
        # inner block must not have cleared the outer's events
        names = [e["name"] for e in outer.events()]
    assert names == ["outer", "inner"]
    assert not obs.enabled()


def test_tracing_restores_on_exception():
    with pytest.raises(RuntimeError):
        with obs.tracing():
            raise RuntimeError("boom")
    assert not obs.enabled()


def test_chrome_trace_structure_and_validation():
    with obs.tracing() as tr:
        with obs.span("a", n=1):
            obs.instant("b")
        ev = tr.events()
    doc = obs.chrome_trace(ev, source="test")
    assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
    assert doc["otherData"]["source"] == "test"
    assert obs.validate_chrome_trace(doc) == ev
    assert obs.validate_chrome_trace(ev) == ev  # bare array form is legal


@pytest.mark.parametrize("bad", [
    [{"ph": "X", "pid": 0, "tid": 0, "ts": 0, "dur": 1}],   # no name
    [{"name": "x", "ph": "Z", "pid": 0, "tid": 0, "ts": 0}],  # bad phase
    [{"name": "x", "ph": "X", "tid": 0, "ts": 0, "dur": 1}],  # no pid
    [{"name": "x", "ph": "X", "pid": 0, "tid": 0, "ts": 0}],  # X needs dur
    [{"name": "x", "ph": "i", "pid": 0, "tid": 0}],           # i needs ts
    ["not an event"],
])
def test_validate_rejects_malformed(bad):
    with pytest.raises(ValueError):
        obs.validate_chrome_trace(bad)


def test_write_chrome_trace_roundtrip(tmp_path):
    path = tmp_path / "t.json"
    with obs.tracing() as tr:
        obs.instant("m", count=np.int64(3))  # numpy scalars must coerce
        ev = tr.events()
    obs.write_chrome_trace(str(path), ev, note="demo")
    doc = json.loads(path.read_text())
    obs.validate_chrome_trace(doc)
    assert doc["otherData"]["note"] == "demo"


# ---------------------------------------------------------------------------
# explain(): bit-identity + counters


EXPLAIN_CASES = [("jag-pq-opt", 16, {}), ("jag-m-heur-probe", 20, {}),
                 ("hybrid_auto", 24, {})]


@pytest.mark.parametrize("name,m,kw", EXPLAIN_CASES)
def test_explain_bit_identical_to_partition(name, m, kw):
    g = small_gamma()
    plain = registry.partition(name, g, m, **kw)
    rep = registry.explain(name, g, m, **kw)
    assert rep.bottleneck == float(plain.max_load(g))
    assert [(r.r0, r.r1, r.c0, r.c1) for r in rep.partition.rects] == \
        [(r.r0, r.r1, r.c0, r.c1) for r in plain.rects]
    assert rep.algo == name and rep.m == m
    assert rep.spans, "explain() must carry per-phase spans"
    assert rep.counters["probe_calls"] > 0
    assert rep.wall_time > 0
    totals = rep.span_totals()
    assert f"partition.{name}" in totals


@pytest.mark.parametrize("name,m,kw", EXPLAIN_CASES)
def test_counter_consistency_hits_plus_misses(name, m, kw):
    rep = registry.explain(name, small_gamma(), m, **kw)
    c = rep.counters
    assert c["stripe_hits"] + c["stripe_misses"] == c["stripe_lookups"]
    assert c["subgrid_hits"] + c["subgrid_misses"] == c["subgrid_lookups"]


def test_counters_reset_between_registry_calls():
    g = small_gamma()
    snap1 = registry.explain("jag-pq-opt", g, 16).counters
    registry.partition("hybrid_auto", g, 24)  # pollute
    snap2 = registry.explain("jag-pq-opt", g, 16).counters
    assert snap1 == snap2


def test_subgrid_memo_peak_bounded():
    rep = registry.explain("hybrid_auto", small_gamma(), 24)
    c = rep.counters
    # the memo only grows on misses, so its peak can never exceed them
    assert 0 < c["subgrid_memo_peak"] <= c["subgrid_misses"]


def test_explain_composes_with_enclosing_tracing():
    g = small_gamma()
    with obs.tracing() as tr:
        obs.instant("before")
        rep = registry.explain("jag-pq-opt", g, 16)
        names = [e["name"] for e in tr.events()]
    assert "before" in names          # outer events survived explain()
    assert "partition.jag-pq-opt" in names
    assert rep.spans
    assert not obs.enabled()


def test_report_to_dict_and_summary():
    rep = registry.explain("jag-pq-opt", small_gamma(), 16)
    d = rep.to_dict()
    assert d["algo"] == "jag-pq-opt" and d["bottleneck"] == rep.bottleneck
    assert json.dumps(d)  # must be JSON-serializable
    assert "Lmax" in rep.summary()


@settings(max_examples=10)
@given(st.integers(min_value=8, max_value=40),
       st.integers(min_value=2, max_value=12))
def test_counter_consistency_property(n, m):
    g = prefix.prefix_sum_2d(prefix.uniform_instance(n, n, delta=1.4,
                                                     seed=n * 31 + m))
    c = registry.explain("jag-m-heur-probe", g, m).counters
    assert c["stripe_hits"] + c["stripe_misses"] == c["stripe_lookups"]
    assert c["subgrid_hits"] + c["subgrid_misses"] == c["subgrid_lookups"]
    assert all(v >= 0 for v in c.values())


# ---------------------------------------------------------------------------
# runtime ledger


def test_runtime_ledger_modes_walltime_churn():
    frames = stream.drifting_hotspot(T=8, n1=24, n2=24, seed=0)
    res = runtime.run_stream(frames, HysteresisPolicy(), P=4, m=8,
                             alpha=0.1, replan_overhead=5.0)
    assert res.records[0].mode == "init"
    assert all(r.wall_time > 0 for r in res.records)
    saw_replan = False
    for r in res.records[1:]:
        if r.replanned:
            saw_replan = True
            assert r.mode in ("fast", "slow")
            assert r.churn is not None
            assert r.churn["volume"] == pytest.approx(r.migration_volume)
            assert r.churn["outflow"].sum() == \
                pytest.approx(r.churn["inflow"].sum())
        else:
            assert r.mode == "keep" and r.churn is None
    assert saw_replan


def test_runtime_forced_evacuation_churn():
    frames = stream.drifting_hotspot(T=8, n1=24, n2=24, seed=0)
    fs = faults_mod.FaultSchedule(8, [faults_mod.FaultEvent(3, 2, "fail")])
    res = runtime.run_stream(frames, HysteresisPolicy(), P=4, m=8,
                             alpha=0.1, faults=fs)
    forced = [r for r in res.records if r.forced]
    assert forced
    for r in forced:
        assert r.mode == "slow" and r.churn is not None
        # everything leaving the dead processor is the evacuation
        assert r.churn["outflow"][2] == pytest.approx(r.evacuation_volume)


def test_runresult_trace_events_validate():
    frames = stream.drifting_hotspot(T=6, n1=24, n2=24, seed=1)
    res = runtime.run_stream(frames, AlwaysRebalance(), P=4, m=8)
    ev = res.trace_events(pid=2)
    obs.validate_chrome_trace(obs.chrome_trace(ev))
    assert all(e["pid"] == 2 for e in ev)
    replans = [e for e in ev if e["name"] == "replan"]
    assert len(replans) == sum(r.replanned for r in res.records)


def test_per_processor_churn_flow_kwarg():
    frames = stream.drifting_hotspot(T=2, n1=24, n2=24, seed=0)
    plans = planner.plan_host(frames, P=4, m=8)
    flow = migrate.migration_matrix(plans[0], plans[1], weights=frames[1])
    via_flow = migrate.per_processor_churn(flow=flow)
    direct = migrate.per_processor_churn(plans[0], plans[1],
                                         weights=frames[1])
    np.testing.assert_allclose(via_flow["outflow"], direct["outflow"])
    assert via_flow["volume"] == pytest.approx(direct["volume"])
    assert via_flow["volume"] == pytest.approx(float(flow.sum()))


# ---------------------------------------------------------------------------
# planner + policy + serve instrumentation


@pytest.mark.parametrize("traced", [True, False], ids=["on", "off"])
@pytest.mark.parametrize("entry", ["plan_iter", "plan_stream"])
def test_planner_check_span(entry, traced):
    """The host finiteness check runs under ``planner.check``: twice per
    ``plan_iter`` slice (the slice loop's check and the one inside
    ``plan_stream``), once per ``plan_stream`` call; nothing is recorded
    when tracing is off."""
    frames = stream.drifting_hotspot(T=4, n1=24, n2=24, seed=0)

    def run():
        if entry == "plan_iter":
            return list(planner.plan_iter(frames, P=4, m=8, slice_size=2))
        return planner.plan_stream(frames, P=4, m=8)

    if not traced:
        before = obs.TRACER.events()
        assert obs.span("planner.check") is obs.trace._NOOP
        run()
        assert obs.TRACER.events() == before
        return
    with obs.tracing() as tr:
        run()
        checks = [e for e in tr.events() if e["name"] == "planner.check"]
    if entry == "plan_iter":
        assert [e["args"]["frames"] for e in checks] == [2, 2, 2, 2]
    else:
        assert [e["args"]["frames"] for e in checks] == [4]
    assert all(e["dur"] >= 0 for e in checks)


_SCOPES = {
    False: {"planner.ingest", "planner.sat", "planner.partition",
            "heur.rows", "heur.counts", "heur.stripes"},
    # int32 frames already hold the exact path's int32 accumulator, so
    # its ingest is the identity and emits no op
    True: {"planner.sat", "planner.partition", "exact.row_bisect",
           "exact.row_realize", "exact.col_bisect", "exact.col_realize"},
}
_FRAME_TABLE = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n.*?(?:\n\n|\Z)",
    re.S | re.M)


def _plan_frames_hlo(exact: bool) -> str:
    """Compiled HLO of a fresh ``jit(plan_frames)`` on a small int32
    stream (a new partial, so nothing is reused from an earlier trace)."""
    import functools
    import jax
    import jax.numpy as jnp
    x = jnp.asarray(np.random.default_rng(0).integers(0, 50, (2, 24, 20)),
                    jnp.int32)
    fn = jax.jit(functools.partial(planner.plan_frames, P=4, m=8,
                                   exact=exact))
    return fn.lower(x).compile().as_text()


def _without_metadata(hlo: str) -> str:
    hlo = _FRAME_TABLE.sub("", hlo)
    return re.sub(r",?\s*metadata=\{[^{}]*\}", "", hlo)


@pytest.mark.parametrize("exact", [False, True])
def test_plan_frames_hlo_carries_the_stage_scopes(exact):
    names = set(re.findall(r'op_name="([^"]*)"', _plan_frames_hlo(exact)))
    found = {part for name in names for part in re.split(r"[/()]", name)}
    assert _SCOPES[exact] <= found, _SCOPES[exact] - found
    # under vmap a scope is wrapped, not renamed
    solver = "exact.row_bisect" if exact else "heur.stripes"
    assert any(f"vmap({solver})/" in n for n in names)


@pytest.mark.parametrize("exact", [False, True])
def test_plan_frames_scopes_and_spans_change_only_metadata(exact,
                                                           monkeypatch):
    """The scopes and the tracer leave the compiled program as it is: the
    HLO with its metadata stripped is the same with tracing on and off,
    and the same as with every named scope taken out."""
    import contextlib
    import jax
    off = _without_metadata(_plan_frames_hlo(exact))
    with obs.tracing():
        on = _without_metadata(_plan_frames_hlo(exact))
    assert on == off
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert _without_metadata(_plan_frames_hlo(exact)) == off


def test_runtime_emits_spans_under_tracing():
    frames = stream.drifting_hotspot(T=6, n1=24, n2=24, seed=0)
    with obs.tracing() as tr:
        runtime.run_stream(frames, HysteresisPolicy(), P=4, m=8, alpha=0.1)
        names = {e["name"] for e in tr.events()}
    assert "runtime.step" in names
    assert "planner.dispatch" in names
    assert "planner.collect" in names
    assert "policy.replan_mode" in names


def test_serve_replan_span_and_histogram():
    rng = np.random.default_rng(0)
    reqs = [batcher.Request(i, int(v))
            for i, v in enumerate(rng.integers(10, 500, 40))]
    newr = [batcher.Request(100 + i, int(v))
            for i, v in enumerate(rng.integers(10, 500, 8))]
    with obs.tracing() as tr:
        asg = batcher.plan(reqs, 4)
        asg2, mode = batcher.replan(asg, newr, policy=HysteresisPolicy(),
                                    alpha=0.01)
        ev = tr.events()
    plans = [e for e in ev if e["name"] == "serve.plan"]
    assert plans and plans[0]["args"]["queue_depth"] == 40
    replans = [e for e in ev if e["name"] == "serve.replan"]
    assert replans and replans[0]["args"]["mode"] == mode
    hist, edges = batcher.load_histogram(asg2, bins=5)
    assert hist.sum() == len(asg2) and len(edges) == 6
    total = sum(r.prompt_tokens for r in reqs + newr)
    assert batcher.replica_loads(asg2).sum() == total


def test_serve_counters_tick():
    C.reset()
    reqs = [batcher.Request(i, 10 + i) for i in range(12)]
    asg = batcher.plan(reqs, 3)
    batcher.replan(asg, [batcher.Request(99, 500)])
    assert C.serve_plans >= 1 and C.serve_replans == 1
    assert C.serve_queue_peak >= 13


def test_heur_probe_step_counters():
    """``heur_probe_steps`` reads each frame's max(counts), summed;
    ``heur_probe_steps_static`` reads T * (m - P + 1), from plan_iter and
    plan_host alike; exact calls add nothing."""
    rng = np.random.default_rng(7)
    frames = rng.integers(1, 50, (3, 24, 32)).astype(np.float32)
    frames[0, 5] *= 2000   # one stripe takes m - P + 1 processors
    frames[1, [2, 17]] *= 40
    P, m, T = 4, 16, frames.shape[0]
    C.reset()
    plans = list(planner.plan_iter(frames, P=P, m=m, slice_size=T))
    maxes = [int(pl.counts.max()) for pl in plans]
    assert len(set(maxes)) == T
    assert C.heur_probe_steps == sum(maxes)
    assert C.heur_probe_steps_static == T * (m - P + 1)
    planner.plan_host(frames, P=P, m=m)
    assert C.heur_probe_steps == 2 * sum(maxes)
    assert C.heur_probe_steps_static == 2 * T * (m - P + 1)
    C.reset()
    list(planner.plan_iter(frames.astype(np.int32), P=P, m=m, exact=True))
    assert C.heur_probe_steps == C.heur_probe_steps_static == 0


def test_exact_row_onepass_frame_counter():
    """``exact_row_onepass_frames`` reads the frames planned by exact
    plan_stream, plan_iter (every slice) and plan_host calls; heuristic
    calls add nothing."""
    rng = np.random.default_rng(17)
    frames = rng.integers(0, 50, (5, 16, 24)).astype(np.int32)
    P, m, T = 4, 12, frames.shape[0]
    C.reset()
    planner.plan_stream(frames, P=P, m=m, exact=True)
    assert C.exact_row_onepass_frames == T
    plans = list(planner.plan_iter(frames, P=P, m=m, slice_size=2,
                                   exact=True))
    assert len(plans) == T and C.exact_row_onepass_frames == 2 * T
    planner.plan_host(frames[:3], P=P, m=m, exact=True)
    assert C.exact_row_onepass_frames == 2 * T + 3
    C.reset()
    planner.plan_stream(frames, P=P, m=m)
    list(planner.plan_iter(frames, P=P, m=m))
    planner.plan_host(frames, P=P, m=m)
    assert C.exact_row_onepass_frames == 0


# ---------------------------------------------------------------------------
# demo script


def test_trace_demo_writes_valid_chrome_trace(tmp_path):
    out = tmp_path / "trace.json"
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "examples/trace_demo.py", "--out", str(out),
         "--steps", "6", "--size", "24", "--m", "8"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr
    doc = json.loads(out.read_text())
    ev = obs.validate_chrome_trace(doc)
    names = {e["name"] for e in ev}
    assert "runtime.step" in names          # live host spans
    assert any(n.startswith("step[") for n in names)  # ledger timeline
    assert any(e["ph"] == "M" for e in ev)  # process metadata
