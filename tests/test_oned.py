"""1D partitioning: exactness, bounds, probe properties (hypothesis)."""
import functools

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import oned

arrays = st.lists(st.integers(0, 60), min_size=1, max_size=18)
procs = st.integers(1, 7)


def brute_optimal(p, m):
    @functools.lru_cache(None)
    def f(i, j):
        if j == 1:
            return float(p[i])
        return min(max(f(k, j - 1), float(p[i] - p[k]))
                   for k in range(0, i + 1))
    return f(len(p) - 1, m)


def prefix(a):
    return np.concatenate([[0], np.cumsum(np.asarray(a, dtype=np.int64))])


@settings(max_examples=60, deadline=None)
@given(arrays, procs)
def test_exact_algorithms_agree_with_bruteforce(a, m):
    p = prefix(a)
    opt = brute_optimal(tuple(p), m)
    for fn in (oned.dp_optimal, oned.probe_bisect_optimal,
               oned.nicol_optimal):
        cuts = fn(p, m)
        assert cuts[0] == 0 and cuts[-1] == len(p) - 1
        assert (np.diff(cuts) >= 0).all()
        assert oned.max_interval_load(p, cuts) == pytest.approx(opt)


@settings(max_examples=60, deadline=None)
@given(arrays, procs)
def test_heuristics_meet_paper_bound(a, m):
    """DC and RB satisfy Lmax <= sum/m + max (Section 2.2)."""
    p = prefix(a)
    bound = p[-1] / m + max(a)
    for fn in (oned.direct_cut, oned.recursive_bisection):
        cuts = fn(p, m)
        assert oned.max_interval_load(p, cuts) <= bound + 1e-9


@settings(max_examples=40, deadline=None)
@given(arrays, procs, st.integers(0, 2000))
def test_probe_feasibility_matches_optimum(a, m, L):
    p = prefix(a)
    opt = oned.max_interval_load(p, oned.dp_optimal(p, m))
    cuts = oned.probe(p, m, L)
    if L >= opt:
        assert cuts is not None
        assert oned.max_interval_load(p, cuts) <= L
    else:
        assert cuts is None


@settings(max_examples=40, deadline=None)
@given(arrays, procs)
def test_lemma1_no_zero_bound(a, m):
    """Lemma 1: Lmax(DC) <= (sum/m)(1 + Delta*m/n) for strictly positive."""
    a = [x + 1 for x in a]
    p = prefix(a)
    n = len(a)
    delta = max(a) / min(a)
    cuts = oned.direct_cut(p, m)
    assert oned.max_interval_load(p, cuts) <= \
        (p[-1] / m) * (1 + delta * m / n) + 1e-9


@settings(max_examples=25, deadline=None)
@given(st.lists(arrays, min_size=1, max_size=4), st.integers(0, 8))
def test_multi_array_optimal(parts, extra):
    ps = [prefix(a) for a in parts]
    m = len(ps) + extra
    bott, counts, cuts = oned.nicol_multi(ps, m)
    assert sum(counts) == m
    # verify achieved bottleneck
    achieved = max(oned.max_interval_load(p, c) for p, c in zip(ps, cuts))
    assert achieved == pytest.approx(bott)
    # brute force over allocations
    import itertools
    best = np.inf
    for alloc in itertools.product(range(1, m + 1), repeat=len(ps)):
        if sum(alloc) != m:
            continue
        v = max(oned.max_interval_load(p, oned.dp_optimal(p, q))
                for p, q in zip(ps, alloc))
        best = min(best, v)
    assert bott == pytest.approx(best)


def test_float_loads_nicol():
    rng = np.random.default_rng(1)
    for _ in range(20):
        a = rng.uniform(0, 10, rng.integers(1, 15))
        p = np.concatenate([[0.0], np.cumsum(a)])
        m = int(rng.integers(1, 6))
        opt = oned.max_interval_load(p, oned.dp_optimal(p, m))
        got = oned.max_interval_load(p, oned.nicol_optimal(p, m))
        assert got <= opt * (1 + 1e-9) + 1e-9
