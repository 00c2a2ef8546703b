"""On-device partitioners match host algorithms."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import device, jagged, oned, prefix
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
# JAG-M-HEUR stripe probes: a loop of max(counts) steps against the former
# static m - P + 1 scan, kept here as the reference


def _static_probe_cuts(p, m_max, count, L, steps=None):
    """The former probe: a scan of a static m_max steps, ``steps`` unused."""
    n = p.shape[0] - 1

    def step(pos, i):
        nxt = jnp.where(i < count, device._advance(p, pos[None], L)[0], pos)
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


def _heur_reference(monkeypatch, fn, *args):
    """``fn(*args)`` traced anew with the static scan in place of the loop."""
    with monkeypatch.context() as mp:
        mp.setattr(device, "_probe_cuts_masked", _static_probe_cuts)
        mp.setattr(device, "_probe_bottleneck_masked",
                   _static_probe_bottleneck)
        return jax.jit(lambda *a: fn(*a))(*args)


@pytest.mark.parametrize("case", [*_HEUR_CASES, "plan_frames-T3"])
def test_jag_m_heur_bounded_loop_bit_identical(case, monkeypatch):
    if case == "plan_frames-T3":
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
    want = _heur_reference(monkeypatch, fn, arg)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    counts = np.asarray(got[1]).reshape(-1, P)
    m_max = m - P + 1
    if case == "one-heavy-row":
        assert counts.min() == 1 and counts.max() == m_max
    if case == "two-heavy-rows":
        assert counts.min() == 1 and 1 < counts.max() < m_max
    if case == "plan_frames-T3":
        assert len(set(counts.max(axis=1).tolist())) == 3
