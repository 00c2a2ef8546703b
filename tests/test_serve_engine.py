"""Continuous-batching serve engine: incremental prefix + queue + loop.

The load-bearing contract is **bit-identity**: the incremental solvers
(``serve.queue``) replicate ``core.oned`` decision-for-decision over the
descending-length order, so a replan off the O(K)-updated structure must
produce exactly the cuts a scratch ``batcher.plan(sort=True)`` computes
over the same multiset.  Everything else (queue invariants, the
simulator's conservation laws, the histogram) guards the machinery
around that contract.
"""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import oned
from repro.obs.hist import LogHistogram
from repro.serve import batcher, simulate
from repro.serve import queue as squeue


def _dense(lengths):
    """The dense descending prefix array the incremental structure models."""
    ls = np.sort(np.asarray(lengths, dtype=np.int64))[::-1]
    return np.concatenate([[0], np.cumsum(ls)])


def _filled(lengths, cap=4096, block=64):
    pf = squeue.LengthPrefix(cap=cap, block=block)
    pf.add(lengths)
    return pf


# ---------------------------------------------------------------------------
# LengthPrefix: query identity with the dense array


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(1, 4096), min_size=0, max_size=80))
def test_prefix_tokens_matches_dense(lens):
    pf = _filled(lens)
    p = _dense(lens)
    for c in range(len(lens) + 1):
        assert pf.prefix_tokens(c) == int(p[c])
    assert pf.max_element() == (max(lens) if lens else 0)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(1, 4096), min_size=1, max_size=60),
       st.integers(0, 10 ** 6))
def test_cut_below_matches_searchsorted(lens, x):
    pf = _filled(lens)
    p = _dense(lens)
    e, pe = pf.cut_below(x)
    want = int(np.searchsorted(p, x, side="right")) - 1
    assert e == want and pe == int(p[e])
    es, _ = pf.cut_below(x, strict=True)
    assert es == int(np.searchsorted(p, x, side="left")) - 1
    assert pf.first_at_least(x) == int(np.searchsorted(p, x, side="left"))


def test_prefix_add_remove_roundtrip():
    pf = squeue.LengthPrefix(cap=1024, block=32)
    rng = np.random.default_rng(0)
    live = []
    for _ in range(30):
        add = rng.integers(1, 1024, size=rng.integers(0, 20)).tolist()
        pf.add(add)
        live += add
        if live and rng.random() < 0.6:
            k = int(rng.integers(1, len(live) + 1))
            rng.shuffle(live)
            gone, live = live[:k], live[k:]
            pf.remove(gone)
        p = _dense(live)
        assert pf.n == len(live) and pf.total == int(p[-1])
        for c in (0, len(live) // 2, len(live)):
            assert pf.prefix_tokens(c) == int(p[c])


def test_prefix_remove_missing_raises_and_preserves_state():
    pf = _filled([5, 5, 9])
    with pytest.raises(ValueError, match="not present"):
        pf.remove([5, 7])  # 7 was never added; the 5 must be rolled back
    assert pf.n == 3 and pf.total == 19
    assert pf.prefix_tokens(3) == 19


def test_prefix_validates_inputs():
    pf = squeue.LengthPrefix(cap=64, block=8)
    with pytest.raises(ValueError, match=r"\[1, 64\]"):
        pf.add([0])
    with pytest.raises(ValueError, match=r"\[1, 64\]"):
        pf.add([65])
    with pytest.raises(TypeError, match="integers"):
        pf.add([1.5])
    with pytest.raises(ValueError, match="multiple"):
        squeue.LengthPrefix(cap=65, block=8)


# ---------------------------------------------------------------------------
# incremental solvers == dense oned solvers (bit-identical cuts)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(1, 2048), min_size=1, max_size=80),
       st.integers(1, 12))
def test_direct_cut_bit_identical(lens, m):
    pf = _filled(lens)
    np.testing.assert_array_equal(
        squeue.direct_cut(pf, m), oned.direct_cut(_dense(lens), m))


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(1, 2048), min_size=1, max_size=80),
       st.integers(1, 12))
def test_optimal_cuts_bit_identical(lens, m):
    pf = _filled(lens)
    p = _dense(lens)
    got = squeue.optimal_cuts(pf, m)
    want = oned.optimal_1d(p, m)
    np.testing.assert_array_equal(got, want)
    # warm starts never change the optimum (feasible and infeasible seeds)
    L = float(np.max(np.diff(p[got])))
    for warm in (L, L + 1.0, max(L - 1.0, float(p[-1]) / m)):
        np.testing.assert_array_equal(
            squeue.optimal_cuts(pf, m, warm=warm), want)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(1, 512), min_size=1, max_size=40),
       st.integers(2, 6), st.integers(0, 10 ** 6))
def test_optimal_cuts_speeds_matches_dense(lens, m, seed):
    """Capacity-aware path (n * m small enough that the dense engine takes
    its scalar branch — bit-identical there)."""
    rng = np.random.default_rng(seed)
    sp = rng.choice([0.5, 1.0, 2.0], size=m)
    pf = _filled(lens)
    got = squeue.optimal_cuts(pf, m, speeds=sp)
    want = oned.optimal_1d(_dense(lens), m, speeds=sp)
    np.testing.assert_array_equal(got, want)


def test_incremental_replan_equals_scratch_plan():
    """The engine's core claim: admit K, replan warm off the incremental
    structure -> exactly the cuts of a scratch batcher.plan(sort=True)."""
    rng = np.random.default_rng(7)
    q = squeue.RequestQueue(cap=4096, block=64)
    R = 8
    q.admit(rng.integers(1, 4096, size=2000))
    cuts = q.plan_cuts(R)
    q.assign_contiguous(cuts)
    for _ in range(5):
        q.admit(rng.integers(1, 4096, size=200))
        warm = float(np.max(np.diff(
            [q.prefix.prefix_tokens(int(c)) for c in cuts])))
        cuts = q.plan_cuts(R, warm=warm)
        scratch = batcher.plan(q.as_requests(), R, algo="optimal")
        sizes = np.array([len(a.requests) for a in scratch])
        np.testing.assert_array_equal(np.diff(cuts), sizes)
        loads = np.array([a.load for a in scratch])
        got_loads = np.diff([q.prefix.prefix_tokens(int(c)) for c in cuts])
        np.testing.assert_array_equal(got_loads, loads)


# ---------------------------------------------------------------------------
# RequestQueue mechanics


def test_queue_admit_keeps_descending_order():
    q = squeue.RequestQueue(cap=1024, block=32)
    rng = np.random.default_rng(1)
    for _ in range(10):
        q.admit(rng.integers(1, 1024, size=rng.integers(1, 50)),
                arrival_times=float(rng.random()))
        q.check()
    assert q.n == q.prefix.n


def test_queue_serve_conserves_tokens_and_interpolates():
    q = squeue.RequestQueue(cap=256, block=32)
    q.admit([100, 50, 10], arrival_times=0.0)
    q.assign_contiguous(np.array([0, 3]))  # one replica owns everything
    rids, lats = q.serve([60], now=0.0, dt=1.0)
    # shortest-first: the 10 and the 50 complete, the 100 is untouched
    assert sorted(rids.tolist()) == [1, 2]
    np.testing.assert_allclose(np.sort(lats), [10 / 60, 1.0])
    assert q.total_remaining == 100
    q.check()


def test_queue_serve_partial_repositions():
    q = squeue.RequestQueue(cap=256, block=32)
    q.admit([100, 90], arrival_times=0.0)
    q.assign_contiguous(np.array([0, 2]))
    rids, _ = q.serve([95], now=0.0, dt=1.0)
    assert rids.tolist() == [1]          # the 90 finishes
    assert q.rem.tolist() == [95]        # 100 partially served, re-sorted
    q.check()


def test_queue_evict_indices():
    q = squeue.RequestQueue(cap=256, block=32)
    q.admit([10, 20, 30], arrival_times=[0.0, 1.0, 2.0])
    gone = q.evict_indices(np.flatnonzero(q.arrival < 1.5))
    assert sorted(gone.tolist()) == [0, 1]
    assert q.n == 1 and q.total_remaining == 30  # the t=2.0 arrival stays
    q.check()


def test_extend_greedy_dead_replica_gets_nothing():
    q = squeue.RequestQueue(cap=256, block=32)
    q.admit([50, 40, 30, 20, 10])
    q.extend_greedy(3, speeds=[1.0, 0.0, 1.0])
    assert not (q.replica == 1).any()
    assert (q.replica >= 0).all()
    q2 = squeue.RequestQueue(cap=64, block=8)
    q2.admit([1])
    with pytest.raises(ValueError, match="positive"):
        q2.extend_greedy(2, speeds=[0.0, 0.0])


# ---------------------------------------------------------------------------
# simulator invariants


def test_simulate_conserves_requests_and_orders_time():
    res = simulate.simulate(
        simulate.poisson_arrivals(3000, rate=300.0, seed=4),
        n_replicas=4, service_rate=40000.0, tick=0.05)
    assert res.admitted == 3000
    assert res.completed + res.evicted == res.admitted
    assert res.evicted == 0
    lat = res.latencies()
    assert lat.size == res.completed and (lat > 0).all()
    assert res.throughput > 0
    assert sum(res.replans.values()) == res.ticks
    # exact and histogram percentiles agree to the bucket resolution (~7%)
    p99 = float(res.percentile(99))
    assert res.hist.percentile(99) == pytest.approx(p99, rel=0.08)


def test_simulate_overload_evicts_by_deadline():
    times = np.linspace(0, 1.0, 2000)
    toks = np.full(2000, 512)
    res = simulate.simulate(
        simulate.trace_arrivals(times, toks), n_replicas=2,
        service_rate=2000.0, tick=0.5, deadline=2.0, max_ticks=400)
    assert res.evicted > 0
    assert res.completed + res.evicted == res.admitted
    assert res.completed > 0  # shortest-first keeps completions flowing


def test_simulate_graded_policy_modes_and_tick_records():
    from repro.rebalance.policy import TwoPhaseHysteresis
    res = simulate.simulate(
        simulate.poisson_arrivals(4000, rate=400.0, seed=0,
                                  mean_tokens=256.0),
        n_replicas=8, service_rate=16000.0, tick=0.1,
        policy=TwoPhaseHysteresis(), record_ticks=True)
    assert res.completed == res.admitted == 4000
    assert res.replans["keep"] > 0  # the hysteresis band holds most ticks
    assert res.tick_records is not None
    assert len(res.tick_records) == res.ticks
    assert sum(t.admitted for t in res.tick_records) == res.admitted
    assert sum(t.completed for t in res.tick_records) == res.completed
    assert sum(t.migrated_tokens
               for t in res.tick_records) == res.migrated_tokens
    modes = {t.mode for t in res.tick_records}
    assert modes <= {"keep", "fast", "slow", "idle"}


def test_simulate_speeds_respects_dead_replica():
    res = simulate.simulate(
        simulate.poisson_arrivals(500, rate=100.0, seed=2),
        n_replicas=4, speeds=[2.0, 1.0, 0.0, 1.0],
        service_rate=8000.0, tick=0.1)
    assert res.completed == 500


def test_arrival_generators_validate():
    with pytest.raises(ValueError, match="rate > 0"):
        list(simulate.poisson_arrivals(10, rate=0.0))
    with pytest.raises(ValueError, match="non-decreasing"):
        list(simulate.trace_arrivals([1.0, 0.5], [4, 4]))
    with pytest.raises(ValueError, match="equal length"):
        list(simulate.trace_arrivals([1.0], [4, 4]))
    with pytest.raises(ValueError, match="budgets"):
        simulate.simulate(simulate.poisson_arrivals(1, rate=1.0),
                          n_replicas=2, service_rate=0.0)


# ---------------------------------------------------------------------------
# LogHistogram


def test_log_histogram_percentiles_and_merge():
    rng = np.random.default_rng(3)
    vals = rng.lognormal(0.0, 1.5, size=20000)
    h = LogHistogram(1e-4, 1e4)
    h.add(vals)
    for q in (50, 90, 99):
        exact = float(np.percentile(vals, q))
        assert h.percentile(q) == pytest.approx(exact, rel=0.08)
    assert h.mean == pytest.approx(float(vals.mean()), rel=1e-12)
    a, b = LogHistogram(1e-4, 1e4), LogHistogram(1e-4, 1e4)
    a.add(vals[:9000])
    b.add(vals[9000:])
    a.merge(b)
    np.testing.assert_array_equal(a.counts, h.counts)
    with pytest.raises(ValueError, match="bucketing"):
        a.merge(LogHistogram(1e-3, 1e4))


def test_log_histogram_overflow_underflow_and_guards():
    h = LogHistogram(1e-2, 1e2)
    h.add([1e-5, 1e5, 1.0])
    assert h.count == 3
    assert h.percentile(0.1) == 1e-2   # underflow reports lo
    assert h.percentile(99.9) == 1e2   # overflow reports hi
    with pytest.raises(ValueError, match="finite"):
        h.add([-1.0])
    assert LogHistogram().percentile(50) == 0.0
