"""Mesh-sharded planner: bit-identical cuts across mesh sizes.

Per-frame computations never cross the time axis, so frame-sharding the
stream must change *nothing* about the cuts — the acceptance bar is
bit-identity of ``(row_cuts, counts, col_cuts, Lmax)`` between the
single-device vmap reference and the ``shard_map`` path on 1-, 2- and
8-device meshes, including a T the device count does not divide.

Multi-device cases run in-process when the platform exposes enough
devices (the CI multi-device leg forces 8 host devices via XLA_FLAGS);
``test_sharded_bit_identical_forced_8dev`` additionally forces an
8-device host platform in a subprocess so the full sweep is exercised in
every tier-1 run.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.dist import ctx
from repro.kernels.sat.ref import gamma_ref
from repro.rebalance import batch_device, planner, stream

P, M = 3, 10


def _assert_same(got, ref):
    names = ("row_cuts", "counts", "col_cuts", "Lmax")
    for name, a, b in zip(names, got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def _reference(frames):
    return batch_device.plan_stream(jnp.asarray(frames), P=P, m=M)


# ---------------------------------------------------------------------------
# mesh construction / axis resolution


def test_planner_mesh_shape_and_axes():
    mesh = ctx.planner_mesh(1)
    assert mesh.axis_names == ("data",)
    assert ctx.planner_axes(mesh) == ("data",)
    with pytest.raises(ValueError, match="devices requested"):
        ctx.planner_mesh(jax.device_count() + 1)


def test_planner_axes_rejects_meshless_dp():
    mesh = ctx.abstract_mesh((2,), ("model",))
    with pytest.raises(ValueError, match="no data-parallel axis"):
        ctx.planner_axes(mesh)


def test_resolve_mesh():
    assert planner.resolve_mesh(None, None) is None
    assert planner.resolve_mesh(None, 1) is None
    mesh = ctx.planner_mesh(1)
    assert planner.resolve_mesh(mesh, 7) is mesh
    assert planner.resolve_mesh(None, 1) is None


# ---------------------------------------------------------------------------
# sharded == single-device (in-process, device-count permitting)


@pytest.mark.parametrize("D,T", [(1, 6), (1, 7), (2, 8), (2, 7),
                                 (8, 16), (8, 13)])
def test_sharded_matches_single_device(D, T):
    """Bit-identical cuts on a D-device mesh, divisible and ragged T."""
    if jax.device_count() < D:
        pytest.skip(f"needs {D} devices (CI multi-device leg forces 8; "
                    f"the subprocess test covers this sweep everywhere)")
    frames = stream.drifting_hotspot(T, 24, 20, seed=5)
    got = planner.plan_stream(frames, P=P, m=M, mesh=ctx.planner_mesh(D))
    _assert_same(got, _reference(frames))


def test_sharded_bit_identical_forced_8dev():
    """The full 1/2/8-device sweep on a forced 8-device host platform.

    Runs the comparison in a subprocess because XLA_FLAGS must be set
    before jax first initializes — the tier-1 parent process typically
    already holds a 1-device platform.
    """
    child = """
import numpy as np, jax, jax.numpy as jnp
assert jax.device_count() == 8, jax.device_count()
from repro.dist import ctx
from repro.rebalance import batch_device, planner, stream
T, n, P, m = 13, 24, 3, 10
frames = stream.drifting_hotspot(T, n, n, seed=5)
ref = [np.asarray(x)
       for x in batch_device.plan_stream(jnp.asarray(frames), P=P, m=m)]
for D in (1, 2, 8):
    out = planner.plan_stream(frames, P=P, m=m, mesh=ctx.planner_mesh(D))
    for name, a, b in zip(("rc", "ct", "cc", "L"), out, ref):
        assert np.array_equal(np.asarray(a), b), (D, name)
print("SHARDED-BIT-IDENTICAL")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(list(repro.__path__)[0])]  # .../src (repro is a
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
           if p])                                   # namespace package)
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHARDED-BIT-IDENTICAL" in proc.stdout


# ---------------------------------------------------------------------------
# exact device solver through the planner (plan_stream(exact=True))

PE, ME = 2, 10  # exact path needs m % P == 0 (Q = 5)


def test_plan_stream_exact_matches_host_solver():
    """Per-frame Lmax from the exact device path equals the host
    JAG-PQ-OPT bottleneck, and every unstacked Plan validates."""
    from repro.core import jagged, prefix
    T, n = 5, 12
    frames = stream.drifting_hotspot(T, n, n, seed=3)
    out = batch_device.plan_stream(jnp.asarray(frames), P=PE, m=ME,
                                   exact=True)
    lmax = np.asarray(out[3])
    for t in range(T):
        g = prefix.prefix_sum_2d(frames[t])
        want = jagged.jag_pq_opt(g, ME, P=PE, Q=ME // PE, orient="hor")
        assert int(lmax[t]) == int(want.max_load(g)), t
    for plan in batch_device.unstack_plans(out, (n, n)):
        plan.validate()


def test_plan_stream_exact_rejects_indivisible_m():
    frames = stream.drifting_hotspot(3, 8, 8, seed=0)
    with pytest.raises(ValueError, match="divisible by P"):
        batch_device.plan_stream(jnp.asarray(frames), P=3, m=10,
                                 exact=True)


@pytest.mark.parametrize("D,T", [(1, 5), (2, 7), (8, 13)])
def test_sharded_exact_matches_single_device(D, T):
    """The exact path shards like the heuristic one: bit-identical cuts
    and bottlenecks on a D-device mesh, including ragged T."""
    if jax.device_count() < D:
        pytest.skip(f"needs {D} devices (the CI multi-device leg forces 8)")
    frames = stream.drifting_hotspot(T, 16, 16, seed=5)
    ref = batch_device.plan_stream(jnp.asarray(frames), P=PE, m=ME,
                                   exact=True)
    got = planner.plan_stream(frames, P=PE, m=ME, exact=True,
                              mesh=ctx.planner_mesh(D))
    _assert_same(got, ref)


# ---------------------------------------------------------------------------
# lazy per-slice iteration


def test_iter_plan_slices_covers_stream_in_order():
    frames = stream.refinement_bursts(11, 20, 16, seed=2)
    spans = []
    for t0, t1, batched in planner.iter_plan_slices(frames, P=P, m=M,
                                                    slice_size=4):
        spans.append((t0, t1))
        assert np.asarray(batched[0]).shape[0] == t1 - t0
    assert spans == [(0, 4), (4, 8), (8, 11)]


def test_plan_iter_matches_plan_stream():
    """Lazy per-slice plans are the same Plans the one-shot call yields,
    whatever the slice size (incl. ragged tails)."""
    frames = stream.drifting_hotspot(9, 24, 20, seed=1)
    ref = batch_device.unstack_plans(_reference(frames), frames.shape[1:])
    for slice_size in (1, 4, 9, None):
        lazy = list(planner.plan_iter(frames, P=P, m=M,
                                      slice_size=slice_size))
        assert len(lazy) == len(ref)
        for a, b in zip(lazy, ref):
            np.testing.assert_array_equal(a.row_cuts, b.row_cuts)
            np.testing.assert_array_equal(a.counts, b.counts)
            np.testing.assert_array_equal(a.col_cuts, b.col_cuts)


def test_plan_iter_on_mesh_matches_reference():
    frames = stream.drifting_hotspot(7, 24, 20, seed=4)
    ref = batch_device.unstack_plans(_reference(frames), frames.shape[1:])
    lazy = list(planner.plan_iter(frames, P=P, m=M,
                                  mesh=ctx.planner_mesh(1), slice_size=3))
    assert len(lazy) == len(ref)
    for a, b in zip(lazy, ref):
        np.testing.assert_array_equal(a.col_cuts, b.col_cuts)


def test_run_stream_accepts_lazy_iterator():
    """run_stream consuming the planner's lazy iterator reproduces the
    materialized-list run exactly."""
    from repro.rebalance import policy, runtime
    frames = stream.drifting_hotspot(10, 24, 24, seed=8)
    plans = runtime.plan_stream_host(frames, P=P, m=M)
    ref = runtime.run_stream(frames, policy.HysteresisPolicy(), P=P, m=M,
                             plans=plans)
    lazy = runtime.run_stream(
        frames, policy.HysteresisPolicy(), P=P, m=M,
        plans=planner.plan_iter(frames, P=P, m=M, slice_size=3))
    assert [dataclasses_tuple(r) for r in lazy.records] \
        == [dataclasses_tuple(r) for r in ref.records]


def dataclasses_tuple(rec):
    return (rec.step, rec.max_load, rec.ideal, rec.replanned,
            rec.migration_volume, rec.migration_cost)


# ---------------------------------------------------------------------------
# batched Pallas SAT under the planner stages


def test_sat_stage_pallas_batch_matches_oracle(pallas_kernels):
    """The Pallas path takes the (T, n1, n2) batch through its leading
    grid axis (no per-frame fallback) and, on integer-valued f32 frames,
    matches the jnp oracle exactly."""
    frames = jnp.asarray(stream.static(3, 20, 28), jnp.float32)
    got = planner.sat_stage(frames)
    want = gamma_ref(frames)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.shape == (3, 21, 29)
