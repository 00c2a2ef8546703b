"""On-device partitioners match host algorithms."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import device, jagged, oned, prefix, search
from repro.rebalance import planner


def test_device_probe_matches_host(rng):
    for _ in range(20):
        n = int(rng.integers(2, 100))
        m = int(rng.integers(1, 10))
        a = rng.integers(1, 500, n).astype(np.int64)
        p = np.concatenate([[0], np.cumsum(a)])
        Ls = rng.uniform(a.max(), a.sum(), 8)
        feas_dev = np.asarray(device.probe_device(
            jnp.asarray(p, jnp.float32), m, jnp.asarray(Ls, jnp.float32)))
        for L, fd in zip(Ls, feas_dev):
            assert fd == (oned.probe(p, m, L) is not None)


def test_device_optimal_matches_host(rng):
    for _ in range(15):
        n = int(rng.integers(2, 150))
        m = int(rng.integers(1, 12))
        a = rng.integers(1, 1000, n).astype(np.int64)
        p = np.concatenate([[0], np.cumsum(a)])
        host = oned.max_interval_load(p, oned.optimal_1d(p, m))
        cuts, L = device.optimal_1d_device(jnp.asarray(p, jnp.float32), m)
        got = oned.max_interval_load(p, np.asarray(cuts))
        assert got <= host * (1 + 1e-4) + 1
        c = np.asarray(cuts)
        assert c[0] == 0 and c[-1] == n and (np.diff(c) >= 0).all()


def test_device_jag_m_heur_matches_host(rng):
    for _ in range(6):
        n1, n2 = int(rng.integers(12, 48)), int(rng.integers(12, 48))
        A = rng.integers(1, 100, (n1, n2)).astype(np.int64)
        g = prefix.prefix_sum_2d(A)
        m, P = 16, 4
        rc, counts, cc, Lmax = device.jag_m_heur_device(
            jnp.asarray(g, jnp.float32), P=P, m=m)
        assert int(np.asarray(counts).sum()) == m
        host = jagged.jag_m_heur(g, m, P=P, orient="hor").max_load(g)
        assert float(Lmax) <= host * 1.2 + 1
        # realized cuts form valid per-stripe partitions
        rc = np.asarray(rc)
        assert rc[0] == 0 and rc[-1] == n1


# ---------------------------------------------------------------------------
# the greedy step: one compare-and-reduce pass against the former
# ``searchsorted`` step, kept here as the reference


def _searchsorted_advance(p, pos, L):
    """The former ``_advance``: furthest index e with p[e] <= p[pos] + L,
    >= pos, by ``searchsorted``."""
    target = jnp.take(p, pos) + L
    nxt = jnp.searchsorted(p, target, side="right") - 1
    nxt = jnp.minimum(nxt, p.shape[0] - 1)
    return jnp.maximum(nxt, pos)  # stuck (single element > L) stays stuck


def _former_advance(p, pos, v, L):
    """``_searchsorted_advance`` in the signature of ``device._advance``."""
    nxt = _searchsorted_advance(p, pos, L)
    return nxt, jnp.take(p, nxt)


def _step_rows():
    rng = np.random.default_rng(19)
    loads = rng.integers(0, 6, 64)
    loads[[0, 1, 2, 17, 18, 40, 63]] = 0           # zero-load runs
    flat = np.full(33, 4)                           # every prefix repeats
    flat[10:20] = 0
    spiky = rng.integers(0, 3, 48)
    spiky[[5, 30]] = 200                            # steps that stick
    return {name: np.concatenate([[0], np.cumsum(a)])
            for name, a in (("zero-runs", loads), ("flat", flat),
                            ("spiky", spiky))}


_STEP_ROWS = _step_rows()


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
@pytest.mark.parametrize("row", list(_STEP_ROWS))
def test_advance_matches_searchsorted(row, dtype):
    """On a non-decreasing row the one-pass step equals the former
    ``searchsorted`` step bit for bit, from every position at every L
    from 0 past the row's total, batched as ``probe_device`` calls it."""
    p = jnp.asarray(_STEP_ROWS[row], dtype)
    n = p.shape[0] - 1
    pos = jnp.arange(n + 1, dtype=jnp.int32)
    for L in range(0, int(p[n]) + 3):
        Ls = jnp.full(pos.shape, L, dtype)
        nxt, w = jax.jit(device._advance)(p, pos, p[pos], Ls)
        want, want_w = _former_advance(p, pos, p[pos], Ls)
        np.testing.assert_array_equal(np.asarray(nxt), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(w), np.asarray(want_w))
    # a scalar position, as the loops over stripes and candidates call it
    nxt, w = device._advance(p, jnp.int32(3), p[3], dtype(7))
    assert int(nxt) == int(_searchsorted_advance(p, jnp.int32(3), dtype(7)))
    assert w == p[nxt]


def test_advance_non_monotone_row():
    """A float32 stripe prefix need not be monotone: the step returns the
    largest e >= pos with q[e] <= q[pos] + L (pos when there is none),
    and q[e]."""
    q = np.array([0.0, 3.0, 2.5, 6.0, 5.75, 5.75, 9.0, 8.5, 12.0, 7.0],
                 np.float32)
    n = len(q) - 1
    qj = jnp.asarray(q)
    pos = jnp.arange(n + 1, dtype=jnp.int32)
    for L in np.arange(0.0, 13.0, 0.25, dtype=np.float32):
        nxt, w = jax.jit(device._advance)(qj, pos, qj[pos],
                                          jnp.full(pos.shape, L))
        for s in range(n + 1):
            fits = [e for e in range(s, n + 1) if q[e] <= q[s] + L]
            want = max(fits, default=s)
            assert int(nxt[s]) == want, (s, L)
            assert float(w[s]) == q[want]
    # the searchsorted step it replaces reads another index on this row
    nxt, _ = device._advance(qj, jnp.int32(0), qj[0], jnp.float32(7.5))
    assert int(nxt) == 9
    assert int(_searchsorted_advance(qj, jnp.int32(0), 7.5)) != 9


def _loop_depth(jaxpr) -> int:
    """Deepest nest of sequential loops (``scan``/``while``) in a jaxpr."""
    depth = 0
    for eqn in jaxpr.eqns:
        inner = 0
        for sub in jax.core.jaxprs_in_params(eqn.params):
            inner = max(inner, _loop_depth(sub))
        if eqn.primitive.name in ("scan", "while"):
            inner += 1
        depth = max(depth, inner)
    return depth


@pytest.mark.parametrize("probe", ["probe_device", "probe_cuts_device",
                                   "bottleneck_masked", "cuts_masked",
                                   "greedy_cuts_exact"])
def test_heur_probe_loop_depth(probe, monkeypatch):
    """The greedy probes are one loop over the steps; nothing loops inside
    a step (the former ``searchsorted`` step nests a loop of its own)."""
    p = jnp.asarray(_STEP_ROWS["zero-runs"], jnp.float32)
    m = 6

    def depth():
        # a new function each time: make_jaxpr caches traces by function
        fn = {
            "probe_device": lambda L: device.probe_device(
                p, m, L + jnp.ones(8)),
            "probe_cuts_device": lambda L: device.probe_cuts_device(p, m, L),
            "bottleneck_masked": lambda L: device._probe_bottleneck_masked(
                p, m, jnp.int32(4), L, jnp.int32(5)),
            "cuts_masked": lambda L: device._probe_cuts_masked(
                p, m, jnp.int32(4), L, jnp.int32(5)),
            "greedy_cuts_exact": lambda L: device._greedy_cuts_exact(
                p.astype(jnp.int32), m, L.astype(jnp.int32)),
        }[probe]
        return _loop_depth(jax.make_jaxpr(fn)(jnp.float32(20)).jaxpr)

    assert depth() == 1
    monkeypatch.setattr(device, "_advance", _former_advance)
    assert depth() == 2


# ---------------------------------------------------------------------------
# JAG-M-HEUR stripe probes: a loop of max(counts) one-pass steps against
# the former static m - P + 1 scan of ``searchsorted`` steps, kept here as
# the reference


def _static_probe_cuts(p, m_max, count, L, steps=None):
    """The former probe: a scan of a static m_max steps, ``steps`` unused."""
    n = p.shape[0] - 1

    def step(pos, i):
        nxt = jnp.where(i < count, _searchsorted_advance(p, pos, L), pos)
        nxt = jnp.where(i == count - 1, n, nxt)
        return nxt, nxt

    _, cuts = jax.lax.scan(step, jnp.int32(0),
                           jnp.arange(m_max, dtype=jnp.int32))
    return jnp.concatenate([jnp.zeros(1, jnp.int32), cuts])


def _static_probe_bottleneck(p, m_max, count, L, steps=None):
    return device._stripe_bottleneck(p, _static_probe_cuts(p, m_max, count, L))


def _skewed_frame(rng, n1, n2, heavy_rows=(), scale=2000):
    """Random loads with a few rows ``scale`` times heavier."""
    A = rng.integers(1, 50, (n1, n2)).astype(np.float32)
    for r in heavy_rows:
        A[r] *= scale
    return A


def _heur_cases():
    rng = np.random.default_rng(14)
    return {
        # one row holds nearly all load: its stripe gets m_max processors,
        # every other stripe gets 1
        "one-heavy-row": (_skewed_frame(rng, 24, 40, (7,)), 4, 16),
        "two-heavy-rows": (_skewed_frame(rng, 30, 64, (3, 21), 40), 5, 23),
        "uniform": (_skewed_frame(rng, 32, 48), 4, 16),
        "P1": (_skewed_frame(rng, 20, 50, (2,)), 1, 9),
    }


_HEUR_CASES = _heur_cases()


def _former_step_reference(monkeypatch, fn, *args, static=True):
    """``fn(*args)`` traced anew with the former ``searchsorted`` step (and,
    where ``static``, the static scan in place of the bounded loop).  The
    jit caches are cleared around it, so no trace of ``optimal_1d_device``
    crosses the patch in either direction."""
    jax.clear_caches()
    try:
        with monkeypatch.context() as mp:
            mp.setattr(device, "_advance", _former_advance)
            if static:
                mp.setattr(device, "_probe_cuts_masked", _static_probe_cuts)
                mp.setattr(device, "_probe_bottleneck_masked",
                           _static_probe_bottleneck)
            return jax.jit(lambda *a: fn(*a))(*args)
    finally:
        jax.clear_caches()


@pytest.mark.parametrize("case", [*_HEUR_CASES, "plan_frames-T3",
                                  "plan_frames-T3-pallas"])
def test_jag_m_heur_bounded_loop_bit_identical(case, monkeypatch, request):
    """Row cuts, counts, column cuts and Lmax of JAG-M-HEUR equal those of
    the former static scan of ``searchsorted`` steps on every case."""
    if case.startswith("plan_frames-T3"):
        if case.endswith("pallas"):
            request.getfixturevalue("pallas_kernels")
        frames = np.stack([_HEUR_CASES[c][0][:24, :40] for c in
                           ("one-heavy-row", "uniform", "two-heavy-rows")])
        P, m = 4, 16

        def fn(f):
            return planner.plan_frames(f, P=P, m=m)

        arg = jnp.asarray(frames)
    else:
        A, P, m = _HEUR_CASES[case]

        def fn(g):
            return device.jag_m_heur_device_impl(g, P=P, m=m)

        arg = jnp.asarray(prefix.prefix_sum_2d(A), jnp.float32)
    got = jax.jit(fn)(arg)
    want = _former_step_reference(monkeypatch, fn, arg)
    for name, g, w in zip(("row_cuts", "counts", "col_cuts", "lmax"),
                          got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    counts = np.asarray(got[1]).reshape(-1, P)
    m_max = m - P + 1
    if case == "one-heavy-row":
        assert counts.min() == 1 and counts.max() == m_max
    if case == "two-heavy-rows":
        assert counts.min() == 1 and 1 < counts.max() < m_max
    if case.startswith("plan_frames-T3"):
        assert len(set(counts.max(axis=1).tolist())) == 3


@pytest.mark.parametrize("axis", [0, 1])
@pytest.mark.parametrize("case", list(_HEUR_CASES))
def test_optimal_1d_onepass_bit_identical(case, axis, monkeypatch):
    """``optimal_1d_device`` (JAG-M-HEUR's rows, SGORP's warm start) and
    the CPU branch of ``nicol_optimal_device`` give the cuts and bottleneck
    of the former ``searchsorted`` step, on the row and column prefixes
    of every case."""
    A, P, m = _HEUR_CASES[case]
    p = np.concatenate([[0], np.cumsum(A.sum(axis=1 - axis))])

    def fn(pf, pi):
        return (*device.optimal_1d_device(pf, m),
                *device.nicol_optimal_device(pi, m))

    args = jnp.asarray(p, jnp.float32), jnp.asarray(p, jnp.int32)
    got = jax.jit(fn)(*args)
    want = _former_step_reference(monkeypatch, fn, *args, static=False)
    for name, g, w in zip(("cuts", "L", "exact_cuts", "exact_L"),
                          got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


# ---------------------------------------------------------------------------
# exact row probe: the one-pass greedy step against the former searchsorted
# step, kept here as the reference


def _searchsorted_stripe_fits(gamma, b, e, L, Q, sp_slice=None):
    """The former ``_stripe_fits``: a ``searchsorted`` per greedy step."""
    q = device._stripe_row(gamma, b, e)
    n2 = q.shape[0] - 1
    if sp_slice is None:
        def step(pos, _):
            target = jnp.take(q, pos) + L
            nxt = jnp.searchsorted(q, target, side="right") - 1
            return jnp.clip(nxt, pos, n2), None

        pos, _ = jax.lax.scan(step, jnp.int32(0), None, length=Q)
    else:
        def step(pos, sp_k):
            target = jnp.take(q, pos) + L * sp_k
            nxt = jnp.searchsorted(q, target, side="right") - 1
            nxt = jnp.clip(nxt, pos, n2)
            return jnp.where(sp_k > 0, nxt, pos), None

        pos, _ = jax.lax.scan(step, jnp.int32(0), sp_slice)
    return pos == n2


def _exact_cases():
    rng = np.random.default_rng(17)
    zero_cols = rng.integers(0, 40, (24, 40))
    zero_cols[:, [0, 3, 4, 5, 19, 39]] = 0
    # all load in the top rows: the realized row cuts repeat (b == e)
    empty = np.zeros((20, 24), np.int64)
    empty[:3] = rng.integers(1, 30, (3, 24))
    heavy_col = rng.integers(1, 20, (16, 30))
    heavy_col[:, 11] *= 500
    wide = rng.integers(0, 60, (12, 129))     # n2 + 1 = 130
    speeds = np.abs(rng.normal(1.0, 0.4, 12))
    speeds[[2, 7]] = 0.0                       # dead positions are skipped
    hetero = rng.integers(1, 50, (18, 26))
    hetero[:, [1, 2, 9]] = 0                   # ... and do not pass ties
    return {
        "zero-columns": (zero_cols, 4, 4, None),
        "empty-stripe": (empty, 4, 3, None),
        "heavy-column": (heavy_col, 3, 4, None),
        # uniform loads: every bottleneck is a prefix value of its stripe
        "L-at-prefix": (np.ones((16, 32), np.int64), 4, 4, None),
        "n2-130": (wide, 3, 5, None),
        "float-gamma": (rng.integers(0, 50, (18, 26)), 3, 4, "float"),
        "speeds": (hetero, 3, 4, speeds),
    }


_EXACT_CASES = _exact_cases()


@pytest.mark.parametrize("case", [*_EXACT_CASES, "plan_frames-T3"])
def test_exact_row_onepass_bit_identical(case, monkeypatch):
    """Row cuts, column cuts and Lmax of the exact JAG-PQ-OPT equal those
    of the former ``searchsorted`` greedy step on every case."""
    if case == "plan_frames-T3":
        frames = np.stack([_EXACT_CASES[c][0][:16, :24].astype(np.int32)
                           for c in ("zero-columns", "empty-stripe",
                                     "heavy-column")])
        P, m = 4, 12

        def fn(f):
            return planner.plan_frames(f, P=P, m=m, exact=True)

        arg = jnp.asarray(frames)
    else:
        A, P, Q, kind = _EXACT_CASES[case]
        g = prefix.prefix_sum_2d(A)
        if kind is None:
            arg, sp = jnp.asarray(g, jnp.int32), None
        elif isinstance(kind, str):
            arg, sp = jnp.asarray(g, jnp.float32), None
        else:
            arg = jnp.asarray(g, jnp.int32)
            sp = jnp.asarray(search.normalize_speeds(kind, P * Q),
                             jnp.float32)

        def fn(g):
            return device.jag_pq_opt_device_impl(g, P=P, Q=Q, speeds=sp)

    got = jax.jit(fn)(arg)
    with monkeypatch.context() as mp:
        mp.setattr(device, "_stripe_fits", _searchsorted_stripe_fits)
        want = jax.jit(lambda a: fn(a))(arg)
    for name, g, w in zip(("row_cuts", "counts", "col_cuts", "lmax"),
                          got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    rc = np.asarray(got[0])
    if case == "empty-stripe":
        assert (np.diff(rc) == 0).any()
    if case == "heavy-column":
        # the heavy column alone exceeds the lower bound total / m, so the
        # bisection's infeasible probes stick at it
        A, P, Q, _ = _EXACT_CASES[case]
        assert A[:, 11].max() > A.sum() / (P * Q)
    if case == "L-at-prefix":
        assert int(got[3]) == 32


@pytest.mark.parametrize("form", ["feasible", "realize", "speeds"])
def test_exact_row_scan_loop_depth(form):
    """``_row_scan`` nests three loops (stripes, binary search, greedy
    steps); nothing loops inside a greedy step."""
    g = jnp.asarray(prefix.prefix_sum_2d(np.ones((8, 10), np.int64)),
                    jnp.int32)
    sp2 = jnp.ones((2, 3), jnp.float32) if form == "speeds" else None
    jaxpr = jax.make_jaxpr(lambda L: device._row_scan(
        g, L, 2, 3, sp2, realize=form == "realize"))(jnp.int32(5))
    assert _loop_depth(jaxpr.jaxpr) == 3
    fits = jax.make_jaxpr(lambda L: device._stripe_fits(
        g, 0, 8, L, 3))(jnp.int32(5))
    assert _loop_depth(fits.jaxpr) == 1
