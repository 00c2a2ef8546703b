"""2D partitioning: validity, class orderings, optimality, theorems."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import hier, jagged, prefix, rect, registry
from repro.core.types import Partition, Rect

small_matrix = st.tuples(
    st.integers(2, 9), st.integers(2, 9), st.integers(0, 10**6)
).map(lambda t: np.random.default_rng(t[2]).integers(
    0, 30, (t[0], t[1])).astype(np.int64))


FAST_ALGOS = ["rect-uniform", "rect-nicol", "jag-pq-heur", "jag-pq-opt",
              "jag-m-heur", "jag-m-heur-probe", "jag-m-alloc",
              "hier-rb", "hier-rb-hor", "hier-rb-ver", "hier-rb-dist",
              "hier-relaxed", "hier-relaxed-dist"]


@settings(max_examples=25, deadline=None)
@given(small_matrix, st.integers(1, 9))
def test_all_algorithms_produce_valid_partitions(A, m):
    g = prefix.prefix_sum_2d(A)
    sq = int(round(np.sqrt(m)))
    for name in FAST_ALGOS:
        if name.startswith(("rect", "jag-pq")) and sq * sq != m:
            continue
        p = registry.partition(name, g, m)
        assert p.is_valid(), (name, m, A.shape)
        assert len(p.rects) <= m
        assert p.max_load(g) >= g[-1, -1] / m - 1e-9  # >= average


@settings(max_examples=10, deadline=None)
@given(small_matrix)
def test_optimal_class_orderings(A):
    """Paper's hierarchy: opt m-way jagged <= opt PxQ jagged; rect-nicol
    <= rect-uniform; every heuristic >= its optimal counterpart."""
    g = prefix.prefix_sum_2d(A)
    m = 4
    li = {n: registry.partition(n, g, m).max_load(g)
          for n in ["rect-uniform", "rect-nicol", "jag-pq-heur",
                    "jag-pq-opt", "jag-m-heur", "jag-m-heur-probe",
                    "jag-m-opt"]}
    assert li["rect-nicol"] <= li["rect-uniform"] + 1e-9
    assert li["jag-pq-opt"] <= li["jag-pq-heur"] + 1e-9
    assert li["jag-m-opt"] <= li["jag-pq-opt"] + 1e-9
    assert li["jag-m-opt"] <= li["jag-m-heur-probe"] + 1e-9
    assert li["jag-m-heur-probe"] <= li["jag-m-heur"] + 1e-9


def test_hier_opt_is_best_hierarchical():
    rng = np.random.default_rng(7)
    for _ in range(5):
        A = rng.integers(0, 20, (6, 6)).astype(np.int64)
        g = prefix.prefix_sum_2d(A)
        m = 4
        opt = hier.hier_opt(g, m).max_load(g)
        for variant in ("load", "dist", "hor", "ver"):
            assert opt <= hier.hier_rb(g, m, variant).max_load(g) + 1e-9
            assert opt <= hier.hier_relaxed(g, m, variant).max_load(g) + 1e-9


def test_theorem1_bound_jag_pq_heur():
    """(1 + d P/n1)(1 + d Q/n2) approximation when no zeros (Thm 1)."""
    rng = np.random.default_rng(3)
    for _ in range(5):
        n1 = n2 = 12
        A = rng.integers(1, 50, (n1, n2)).astype(np.int64)
        g = prefix.prefix_sum_2d(A)
        m, P, Q = 9, 3, 3
        delta = A.max() / A.min()
        ratio_bound = (1 + delta * P / n1) * (1 + delta * Q / n2)
        p = jagged.jag_pq_heur(g, m, P=P, Q=Q, orient="hor")
        lavg = A.sum() / m
        assert p.max_load(g) <= ratio_bound * lavg + 1e-6


def test_theorem3_bound_jag_m_heur():
    """m/(m-P) + m d/(P n2) + d^2 m/(n1 n2) approximation (Thm 3)."""
    rng = np.random.default_rng(4)
    for _ in range(5):
        n1 = n2 = 12
        A = rng.integers(1, 50, (n1, n2)).astype(np.int64)
        g = prefix.prefix_sum_2d(A)
        m, P = 9, 3
        d = A.max() / A.min()
        bound = m / (m - P) + m * d / (P * n2) + d * d * m / (n1 * n2)
        p = jagged.jag_m_heur(g, m, P=P, orient="hor")
        assert p.max_load(g) <= bound * (A.sum() / m) + 1e-6


def test_hybrid_runs_and_is_valid():
    A = prefix.multipeak_instance(24, 24, seed=5)
    g = prefix.prefix_sum_2d(A)
    p = registry.partition("hybrid", g, 16)
    assert p.is_valid()
    assert p.m == 16


def test_registry_sweep_exact_tiling_and_true_bottleneck():
    """Every algorithm in the registry, ~20 randomized instances: the
    rectangles tile the matrix exactly (no overlap, full cover) and the
    Gamma-reported loads/bottleneck equal the true rectangle sums on A."""
    rng = np.random.default_rng(1104)
    for case in range(20):
        n1, n2 = int(rng.integers(2, 10)), int(rng.integers(2, 10))
        A = rng.integers(0, 50, (n1, n2)).astype(np.int64)
        g = prefix.prefix_sum_2d(A)
        m = int(rng.integers(1, 10))
        sq = int(round(np.sqrt(m)))
        for name in registry.names():
            if (name.startswith(("rect", "jag-pq")) and sq * sq != m):
                continue  # square-only algorithms
            if name in registry.RANK3:
                continue  # raw-volume algorithms (tests/test_threed.py)
            if name.startswith("sgorp"):
                from repro.core import sgorp
                try:
                    sgorp.default_grid(m, (n1, n2))
                except ValueError:
                    continue  # no processor grid fits this tiny shape
            p = registry.partition(name, g, m)
            assert p.m == m, (name, case)
            paint = np.zeros((n1, n2), dtype=np.int32)
            for r in p.rects:
                assert 0 <= r.r0 <= r.r1 <= n1, (name, case, r)
                assert 0 <= r.c0 <= r.c1 <= n2, (name, case, r)
                paint[r.r0:r.r1, r.c0:r.c1] += 1
            assert (paint == 1).all(), (name, case, m, A.shape)
            true_loads = np.array(
                [A[r.r0:r.r1, r.c0:r.c1].sum() for r in p.rects],
                dtype=np.int64)
            np.testing.assert_array_equal(p.loads(g), true_loads,
                                          err_msg=f"{name} case {case}")
            assert p.max_load(g) == float(true_loads.max(initial=0)), \
                (name, case)


def test_rect_types():
    r = Rect(0, 2, 1, 3)
    assert r.area == 4
    assert r.intersects(Rect(1, 3, 2, 4))
    assert not r.intersects(Rect(2, 4, 0, 4))
    with pytest.raises(ValueError):
        Rect(2, 1, 0, 0)


def test_orientation_variants():
    A = prefix.diagonal_instance(16, 8, seed=0)
    g = prefix.prefix_sum_2d(A)
    h = jagged.jag_m_heur(g, 4, orient="hor")
    v = jagged.jag_m_heur(g, 4, orient="ver")
    b = jagged.jag_m_heur(g, 4, orient="best")
    assert all(p.is_valid() for p in (h, v, b))
    assert b.max_load(g) <= min(h.max_load(g), v.max_load(g)) + 1e-9


def test_instances_generators():
    for name, gen in prefix.INSTANCES.items():
        A = gen(16, 16)
        assert A.shape == (16, 16)
        assert (A >= 0).all(), name
