"""SGORP device refiner (PR 10): warm-start floor, validity, batching,
mesh sharding.

The refiner's structural guarantee — it tracks the best integer cuts
seen, starting from the per-axis 1D warm start — means its Lmax can
never exceed the warm start's; that floor, bit-identical batched vs
looped planning, and the 1/2/8-device sharded sweep are the acceptance
bars here.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import prefix, registry, sgorp, threed
from repro.core.types import from_grid
from repro.obs import counters


def _vol(n=16, seed=0):
    return prefix.pic_like_instance_3d(n, n, n, seed=seed)


# ---------------------------------------------------------------------------
# processor-grid factorization


def test_default_grid_factorization():
    assert sgorp.default_grid(64, (64, 64, 64)) == (4, 4, 4)
    assert sgorp.default_grid(12, (16, 16, 16)) == (3, 2, 2)
    g = sgorp.default_grid(30, (8, 8, 8))
    assert int(np.prod(g)) == 30 and all(gi <= 8 for gi in g)
    # 2D too
    g2 = sgorp.default_grid(6, (32, 32))
    assert int(np.prod(g2)) == 6


def test_default_grid_rejects_unplaceable_prime():
    with pytest.raises(ValueError, match="prime"):
        sgorp.default_grid(17, (16, 2, 2))


# ---------------------------------------------------------------------------
# the warm-start floor (never worse than the 1D-projection start)


def _warm_partition_3d(A, m):
    grid = sgorp.default_grid(m, A.shape)
    g3 = prefix.prefix_sum_3d(A)
    warm = sgorp.warm_start_impl(jnp.asarray(g3, jnp.float32), grid=grid)
    return threed.partition3d_from_grid(*[np.asarray(w) for w in warm],
                                        shape=A.shape), g3


@pytest.mark.parametrize("m", [8, 27, 12])
def test_sgorp_3d_valid_and_never_worse_than_warm(m):
    A = _vol()
    part = registry.partition("sgorp-3d", A, m)
    assert part.is_valid()
    assert len(part.boxes) == m
    np.testing.assert_allclose(part.loads(A).sum(), A.sum())
    warm, g3 = _warm_partition_3d(A, m)
    assert part.max_load(A, gamma3=g3) <= warm.max_load(A, gamma3=g3)


def test_sgorp_2d_valid_and_never_worse_than_warm():
    A2 = prefix.pic_like_instance(24, 24, seed=1)
    g2 = prefix.prefix_sum_2d(A2)
    m = 12
    part = registry.partition("sgorp-2d", g2, m)
    assert part.is_valid()
    grid = sgorp.default_grid(m, A2.shape)
    warm = sgorp.warm_start_impl(jnp.asarray(g2, jnp.float32), grid=grid)
    rc, cc = (np.asarray(w) for w in warm)
    wpart = from_grid(rc, cc, A2.shape)
    assert part.max_load(g2) <= wpart.max_load(g2)


def test_sgorp_counters_and_explain():
    A = _vol(12, seed=2)
    report = registry.explain("sgorp-3d", A, 8)
    assert report.shape == A.shape
    assert report.counters["sgorp_iterations"] > 0
    assert report.counters["sgorp_projections"] > 0
    assert report.bottleneck == pytest.approx(
        report.partition.max_load(A))


def test_sgorp_3d_speeds_valid():
    A = _vol(12, seed=3)
    speeds = np.array([1, 1, 2, 2, 1, 3, 1, 1], dtype=float)
    part = registry.partition("sgorp-3d", A, 8, speeds=speeds)
    assert part.is_valid()
    assert len(part.boxes) == 8


# ---------------------------------------------------------------------------
# batched planning: vmap == loop, rank-4 plan_stream dispatch


def _frames(T=4, n=12, seed=0):
    from repro.rebalance import stream
    return stream.pic_series_3d(T, n, n, n, seed=seed)


def test_batched_plan_matches_looped():
    from repro.rebalance import planner
    frames = _frames()
    ref = [np.asarray(x) for x in planner.plan_stream_3d(frames, m=8)]
    for t in range(frames.shape[0]):
        one = planner.plan_stream_3d(frames[t:t + 1], m=8)
        for a, b in zip(one, ref):
            np.testing.assert_array_equal(np.asarray(a)[0], b[t])


def test_plan_stream_rank4_dispatch():
    from repro.rebalance import planner
    frames = _frames(T=3)
    via_2d_entry = planner.plan_stream(frames, P=0, m=8)
    direct = planner.plan_stream_3d(frames, m=8)
    for a, b in zip(via_2d_entry, direct):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="exact"):
        planner.plan_stream(frames, P=0, m=8, exact=True)
    with pytest.raises(ValueError, match="rank"):
        planner.plan_stream_3d(frames[0], m=8)
    with pytest.raises(ValueError, match="grid"):
        planner.plan_stream_3d(frames, m=8, grid=(2, 2, 1))


def test_plan3d_pallas_interpret_matches_oracle(pallas_kernels):
    """The rank-3 Pallas SAT inside the planning chain (interpret mode)
    must not change the cuts vs the same plan on the jnp oracle's Gamma3
    (f32 sums of int-valued loads are exact at this scale)."""
    from repro.kernels.sat.ref import gamma3_ref
    from repro.rebalance import planner
    frames = _frames(T=2)
    got = planner.plan_stream_3d(frames, m=8)
    grid = sgorp.default_grid(8, frames.shape[1:])
    cuts, *rest = jax.vmap(lambda g: sgorp.sgorp_plan_impl(g, grid=grid))(
        gamma3_ref(jnp.asarray(frames, jnp.float32)))
    ref = (*cuts, *rest)
    assert len(got) == len(ref) == 6
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# mesh sharding: bit-identical cuts on 1/2/8-device meshes


def test_sharded_3d_bit_identical_forced_8dev():
    """Like test_planner_sharded's sweep, for the 3D SGORP chain: forced
    8-device host platform in a subprocess (XLA_FLAGS must be set before
    jax initializes), ragged T included."""
    child = """
import numpy as np, jax
assert jax.device_count() == 8, jax.device_count()
from repro.dist import ctx
from repro.rebalance import planner, stream
T, n, m = 6, 12, 8
frames = stream.pic_series_3d(T, n, n, n, seed=5)
ref = [np.asarray(x) for x in planner.plan_stream_3d(frames, m=m)]
for D in (1, 2, 8):
    out = planner.plan_stream_3d(frames, m=m, mesh=ctx.planner_mesh(D))
    for name, a, b in zip(("c1", "c2", "c3", "L", "it", "pr"), out, ref):
        assert np.array_equal(np.asarray(a), b), (D, name)
print("SGORP-SHARDED-BIT-IDENTICAL")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(list(repro.__path__)[0])]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SGORP-SHARDED-BIT-IDENTICAL" in proc.stdout
