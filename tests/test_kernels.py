"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.rectload.ops import jagged_loads
from repro.kernels.rectload.ref import jagged_loads_ref
from repro.kernels.sat.ops import gamma, sat
from repro.kernels.sat.ref import gamma_ref, sat_ref

SAT_SHAPES = [(1, 1), (7, 9), (8, 128), (100, 130), (256, 512), (300, 700),
              (513, 129)]


@pytest.mark.parametrize("shape", SAT_SHAPES)
@pytest.mark.parametrize("dtype", ["float32", "int32"])
def test_sat_matches_ref(shape, dtype, rng):
    if dtype == "float32":
        a = rng.uniform(0, 10, shape).astype(np.float32)
        got = sat(jnp.asarray(a))
        want = sat_ref(jnp.asarray(a))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-6, atol=1e-4)
    else:
        a = rng.integers(0, 100, shape).astype(np.int32)
        got = sat(jnp.asarray(a))
        want = sat_ref(jnp.asarray(a))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", [(16, 16), (65, 200)])
def test_gamma_matches_ref_and_host(shape, rng):
    from repro.core.prefix import prefix_sum_2d
    a = rng.integers(0, 50, shape).astype(np.int32)
    got = gamma(jnp.asarray(a))
    want = gamma_ref(jnp.asarray(a))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got),
                                  prefix_sum_2d(a).astype(np.int32))


@pytest.mark.parametrize("n1,n2,P,Q", [
    (16, 16, 2, 2), (32, 40, 4, 3), (128, 257, 7, 5), (64, 600, 3, 9),
])
def test_rectload_matches_ref(n1, n2, P, Q, rng):
    a = rng.integers(0, 50, (n1, n2)).astype(np.int32)
    g = gamma_ref(jnp.asarray(a))
    rc = np.concatenate([[0], np.sort(rng.choice(
        np.arange(1, n1), P - 1, replace=False)), [n1]]).astype(np.int32)
    cc = np.stack([np.concatenate([
        [0], np.sort(rng.choice(np.arange(1, n2), Q - 1, replace=False)),
        [n2]]) for _ in range(P)]).astype(np.int32)
    got = jagged_loads(g.astype(jnp.float32), jnp.asarray(rc),
                       jnp.asarray(cc))
    want = jagged_loads_ref(g, jnp.asarray(rc), jnp.asarray(cc))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    # loads of a valid partition sum to the matrix total
    np.testing.assert_allclose(np.asarray(got).sum(), a.sum(), rtol=1e-6)


@pytest.mark.parametrize("S,n,K,cap", [
    (1, 1, 1, 1), (3, 17, 5, 4), (8, 128, 16, 7), (5, 300, 3, 12),
])
def test_probe_counts_pallas_matches_ref_and_host(S, n, K, cap, rng,
                                                  pallas_kernels):
    """Probe kernel == jnp oracle == the host scalar greedy, including
    the cap+1 infeasible sentinel and all-zero stripes."""
    from repro.core import oned
    from repro.kernels.probe import probe_counts, probe_counts_ref

    loads = rng.integers(0, 40, (S, n)).astype(np.int64)
    loads[0] = 0  # degenerate all-zero stripe
    p = np.cumsum(np.concatenate([np.zeros((S, 1), np.int64), loads],
                                 axis=1), axis=1).astype(np.int32)
    # candidate levels spanning infeasible (tiny) through trivial (total)
    Ls = np.stack([np.linspace(1, max(int(p[s, -1]), 2), K)
                   for s in range(S)]).astype(np.int32)
    got = np.asarray(probe_counts(jnp.asarray(p), jnp.asarray(Ls), cap,
                                  use_pallas=True))
    want = np.asarray(probe_counts_ref(jnp.asarray(p), jnp.asarray(Ls),
                                       cap))
    np.testing.assert_array_equal(got, want)
    for s in range(S):
        for j in range(K):
            assert got[s, j] == oned.probe_count(
                p[s].astype(np.int64), int(Ls[s, j]), cap)


def test_pallas_interpret_default_env_override(monkeypatch):
    from repro.backend import pallas_interpret_default

    monkeypatch.setenv("JAX_PALLAS_INTERPRET", "1")
    assert pallas_interpret_default() is True
    monkeypatch.setenv("JAX_PALLAS_INTERPRET", "0")
    assert pallas_interpret_default() is False
    monkeypatch.delenv("JAX_PALLAS_INTERPRET")
    import jax
    assert pallas_interpret_default() is (jax.default_backend() != "tpu")


def test_rectload_degenerate_stripes(rng):
    """Empty stripes / empty columns are legal (zero loads)."""
    a = rng.integers(0, 10, (20, 20)).astype(np.int32)
    g = gamma_ref(jnp.asarray(a))
    rc = np.array([0, 0, 10, 20], dtype=np.int32)          # empty stripe 0
    cc = np.array([[0, 0, 20], [0, 5, 20], [0, 20, 20]], dtype=np.int32)
    got = np.asarray(jagged_loads(g.astype(jnp.float32), jnp.asarray(rc),
                                  jnp.asarray(cc)))
    want = np.asarray(jagged_loads_ref(g, jnp.asarray(rc), jnp.asarray(cc)))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[0].sum() == 0


# ---------------------------------------------------------------------------
# rank-3 SAT (PR 10): the 3D kernel vs its oracle and the host prefix

SAT3_SHAPES = [(1, 1, 1), (5, 7, 9), (4, 8, 128), (6, 100, 130),
               (3, 129, 300)]


@pytest.mark.parametrize("shape", SAT3_SHAPES)
@pytest.mark.parametrize("dtype", ["float32", "int32"])
def test_sat3_matches_ref(shape, dtype, rng):
    from repro.kernels.sat.ops import sat3
    from repro.kernels.sat.ref import sat3_ref
    if dtype == "float32":
        a = rng.uniform(0, 10, shape).astype(np.float32)
        got = sat3(jnp.asarray(a))
        want = sat3_ref(jnp.asarray(a))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-6, atol=1e-3)
    else:
        a = rng.integers(0, 100, shape).astype(np.int32)
        got = sat3(jnp.asarray(a))
        want = sat3_ref(jnp.asarray(a))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("B", [1, 3])
def test_sat3_batched_matches_per_volume(B, rng):
    """A (B, n1, n2, n3) stack rides the leading grid axis — identical to
    stacking the per-volume results."""
    from repro.kernels.sat.ops import sat3
    a = rng.integers(0, 50, (B, 5, 20, 33)).astype(np.int32)
    got = np.asarray(sat3(jnp.asarray(a)))
    for b in range(B):
        np.testing.assert_array_equal(
            got[b], np.asarray(sat3(jnp.asarray(a[b]))))


@pytest.mark.parametrize("shape", [(4, 12, 17), (2, 65, 200)])
def test_gamma3_matches_ref_and_host(shape, rng):
    from repro.core.prefix import prefix_sum_3d
    from repro.kernels.sat.ops import gamma3
    from repro.kernels.sat.ref import gamma3_ref
    a = rng.integers(0, 50, shape).astype(np.int32)
    got = gamma3(jnp.asarray(a))
    want = gamma3_ref(jnp.asarray(a))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got),
                                  prefix_sum_3d(a).astype(np.int32))


# ---------------------------------------------------------------------------
# the TPU-compilable kernel forms, swept in interpret mode: tiles small
# enough that every carry crosses tile edges, ragged extents, B > 1 stacks


@pytest.mark.parametrize("shape", [(3, 7, 9), (2, 17, 300), (4, 33, 129)])
@pytest.mark.parametrize("dtype", ["float32", "int32"])
def test_sat_small_tiles_ragged_stack(shape, dtype, rng):
    """Multi-tile carries in both scan directions, per frame of a stack;
    int32 results bit-identical to the oracle."""
    from repro.kernels.sat.sat import sat_pallas
    a = rng.integers(0, 100, shape).astype(dtype)
    got = np.asarray(sat_pallas(jnp.asarray(a), bm=8, bn=128,
                                interpret=True))
    # integer-valued f32 far below 2**24 is exact too
    np.testing.assert_array_equal(got, np.asarray(sat_ref(jnp.asarray(a))))


@pytest.mark.parametrize("shape", [(2, 3, 9, 130), (3, 2, 17, 257)])
def test_sat3_small_tiles_ragged_stack(shape, rng):
    from repro.kernels.sat.ref import sat3_ref
    from repro.kernels.sat.sat3d import sat3_pallas
    a = rng.integers(0, 100, shape).astype(np.int32)
    got = np.asarray(sat3_pallas(jnp.asarray(a), bm=8, bn=128,
                                 interpret=True))
    np.testing.assert_array_equal(got, np.asarray(sat3_ref(jnp.asarray(a))))


def test_tile_cumsum_exact_past_f32_integers(rng):
    """The log-step scan adds exact int32 values: sums past 2**24 (where
    f32 would round) stay bit-identical to the oracle."""
    from repro.kernels.sat.sat import sat_pallas
    a = rng.integers(1 << 20, 1 << 21, (2, 20, 260)).astype(np.int32)
    got = np.asarray(sat_pallas(jnp.asarray(a), bm=8, bn=128,
                                interpret=True))
    assert int(got.max()) > 1 << 30
    np.testing.assert_array_equal(got, np.asarray(sat_ref(jnp.asarray(a))))


@pytest.mark.parametrize("B,n1,n2,P,Q", [(1, 13, 9, 3, 2), (3, 70, 300, 5, 4),
                                         (2, 9, 600, 2, 7)])
def test_rectload_int32_ragged_rows_exact(B, n1, n2, P, Q, rng):
    """int32 Gammas (totals past 2**24) price exactly, with row counts
    that are not multiples of the kernel's 8-row block."""
    from repro.kernels.rectload.rectload import jagged_loads_pallas
    a = rng.integers(0, 1 << 16, (B, n1, n2)).astype(np.int32)
    g = gamma_ref(jnp.asarray(a))
    rc = np.stack([np.concatenate([[0], np.sort(rng.choice(
        np.arange(1, n1), P - 1, replace=False)), [n1]])
        for _ in range(B)]).astype(np.int32)
    cc = np.stack([np.stack([np.concatenate([[0], np.sort(rng.choice(
        np.arange(1, n2), Q - 1, replace=False)), [n2]]) for _ in range(P)])
        for _ in range(B)]).astype(np.int32)
    got = jagged_loads_pallas(g, jnp.asarray(rc), jnp.asarray(cc),
                              interpret=True)
    assert got.dtype == jnp.int32
    want = np.asarray(jagged_loads_ref(g, jnp.asarray(rc), jnp.asarray(cc)))
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(got).sum(axis=(1, 2)),
                                  a.sum(axis=(1, 2)))


@pytest.mark.parametrize("S,n,K,cap", [(2, 4, 9, 3), (4, 129, 8, 32),
                                       (3, 300, 15, 6)])
def test_probe_ragged_candidates_matches_ref(S, n, K, cap, rng,
                                             pallas_kernels):
    """Row lengths off the 128-lane grid and candidate counts off the
    8-sublane grid (the exact path asks for k=8 per stripe)."""
    from repro.kernels.probe import probe_counts, probe_counts_ref
    loads = rng.integers(0, 40, (S, n))
    p = np.concatenate([np.zeros((S, 1), np.int64), loads.cumsum(1)],
                       axis=1).astype(np.int32)
    Ls = rng.integers(1, int(p[:, -1].max()) + 2, (S, K)).astype(np.int32)
    got = probe_counts(jnp.asarray(p), jnp.asarray(Ls), cap,
                       use_pallas=True)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(probe_counts_ref(jnp.asarray(p), jnp.asarray(Ls), cap)))


def test_probe_vmapped_inside_while_loop(rng, pallas_kernels):
    """The exact path's use: the probe kernel as the feasibility test of
    the lockstep column bisection (a ``while_loop``), vmapped over a
    frame stack — same minimal bottlenecks as the jnp probe."""
    import functools
    import jax
    from repro.core import device
    from repro.kernels.probe import probe_counts_impl

    T, S, n, Q = 3, 4, 50, 5
    loads = rng.integers(0, 30, (T, S, n))
    p = jnp.asarray(np.concatenate(
        [np.zeros((T, S, 1), np.int64), loads.cumsum(2)], axis=2), jnp.int32)

    def solve(ps, use_pallas):
        def feasible(cand):
            return probe_counts_impl(ps, cand, Q,
                                     use_pallas=use_pallas) <= Q
        lo, hi = jax.vmap(lambda q: device._exact_1d_bounds_int(q, Q))(ps)
        return device._wide_bisect_exact_batch(feasible, lo, hi, k=8)

    got = jax.jit(jax.vmap(functools.partial(solve, use_pallas=True)))(p)
    want = jax.jit(jax.vmap(functools.partial(solve, use_pallas=False)))(p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# platform resolution and the compile cache (repro.backend)


def test_cpu_default_device_resolves_cpu_paths():
    import jax
    from repro import backend
    with jax.default_device(jax.devices("cpu")[0]):
        assert backend.platform() == "cpu"
        assert backend.use_pallas_default() is False


@pytest.mark.parametrize("module", [
    "repro.rebalance.planner", "repro.rebalance.batch_device",
    "repro.rebalance.execute", "repro.core.device", "repro.core.sgorp",
    "repro.kernels.sat.ops", "repro.kernels.probe.ops",
    "repro.kernels.rectload.ops",
])
def test_planning_path_takes_no_kernel_choice(module):
    """The platform picks each kernel where it is called: no function of
    the planning path takes the choice as a parameter, and no kernel
    wrapper (which keeps ``use_pallas``, kernel or oracle) takes Pallas's
    interpret mode.  Jitted and cached functions are included."""
    import importlib
    import inspect
    mod = importlib.import_module(module)
    fns = {n: f for n, f in vars(mod).items()
           if callable(f) and getattr(f, "__module__", None) == module}
    knobs = {"interpret"} if module.startswith("repro.kernels.") else \
        {"use_pallas", "use_pallas_probe", "interpret"}
    assert fns
    for name, f in fns.items():
        found = knobs & set(inspect.signature(f).parameters)
        assert not found, (module, name, found)


def test_compile_cache_dir_env_wins_else_fixed_in_checkout(monkeypatch,
                                                            tmp_path):
    import os
    import jax
    from repro import backend
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", prev)
        assert backend.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        d = backend.enable_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert d == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == d
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
