"""Executed migrations + batched rectload: measured == priced.

The contract under test (``rebalance.execute``): performing a plan switch
— actually moving owner-changed cells' weights between devices — measures
*exactly* the volume/flow the paper ledger (``rebalance.migrate``)
priced, on integer streams where every sum is exact.  The per-rectangle
receipts ride the rectload Pallas kernel's new leading frame axis, so the
batched kernel is regression-tested here both directly (vs looped 2D
calls and the jnp oracle) and through the executor.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import prefix
from repro.kernels.rectload.ops import jagged_loads
from repro.kernels.rectload.ref import jagged_loads_ref
from repro.kernels.rectload.rectload import jagged_loads_pallas
from repro.rebalance import execute, migrate, planner, runtime, stream
from repro.rebalance.policy import AlwaysRebalance, EveryK

P, M = 4, 12


def _plans(frames):
    return planner.plan_host(np.asarray(frames), P=P, m=M)


# ---------------------------------------------------------------------------
# batched rectload kernel


def _random_case(rng, B, n1, n2, Pk, Q):
    frames = rng.integers(0, 10, size=(B, n1, n2)).astype(np.float64)
    g = np.zeros((B, n1 + 1, n2 + 1))
    g[:, 1:, 1:] = frames.cumsum(1).cumsum(2)
    rc = np.stack([np.sort(np.concatenate(
        [[0], rng.choice(np.arange(1, n1), Pk - 1, replace=False), [n1]]))
        for _ in range(B)])
    cc = np.stack([np.stack([np.sort(np.concatenate(
        [[0], rng.choice(np.arange(1, n2), Q - 1, replace=False), [n2]]))
        for _ in range(Pk)]) for _ in range(B)])
    return (jnp.asarray(g, jnp.float32), jnp.asarray(rc, jnp.int32),
            jnp.asarray(cc, jnp.int32))


@pytest.mark.parametrize("B,n1,n2,Pk,Q", [(1, 16, 24, 2, 3), (3, 40, 70, 4, 5),
                                          (2, 33, 513, 3, 6)])
def test_rectload_batched_matches_looped_and_ref(B, n1, n2, Pk, Q):
    g, rc, cc = _random_case(np.random.default_rng(B), B, n1, n2, Pk, Q)
    batched = np.asarray(jagged_loads_pallas(g, rc, cc, interpret=True))
    looped = np.stack([np.asarray(
        jagged_loads_pallas(g[b], rc[b], cc[b], interpret=True))
        for b in range(B)])
    want = np.asarray(jagged_loads_ref(g, rc, cc))
    np.testing.assert_array_equal(batched, looped)
    np.testing.assert_array_equal(batched, want)
    assert batched.shape == (B, Pk, Q)
    # conservation per frame: rectangle loads sum to the frame total
    np.testing.assert_allclose(batched.sum(axis=(1, 2)),
                               np.asarray(g)[:, -1, -1])


def test_rectload_dispatcher_handles_both_ranks():
    g, rc, cc = _random_case(np.random.default_rng(9), 2, 20, 36, 3, 4)
    b = np.asarray(jagged_loads(g, rc, cc))
    np.testing.assert_array_equal(b, np.asarray(jagged_loads_ref(g, rc, cc)))
    s = np.asarray(jagged_loads(g[0], rc[0], cc[0]))
    np.testing.assert_array_equal(s, b[0])
    # ref fallback agrees batched too
    nb = np.asarray(jagged_loads(g, rc, cc, use_pallas=False))
    np.testing.assert_array_equal(nb, b)


# ---------------------------------------------------------------------------
# executed migrations: measured == priced (integer streams -> exact)


@pytest.mark.parametrize("kind,weight", [("static", "load"),
                                         ("hotspot", "load"),
                                         ("hotspot", "cells")])
def test_executed_bytes_equal_migration_volume(kind, weight):
    frames = np.asarray(
        stream.static(4, 40, 40, seed=1) if kind == "static"
        else stream.drifting_hotspot(4, 40, 40, seed=2))
    assert np.issubdtype(frames.dtype, np.integer)
    res = runtime.run_stream(frames, AlwaysRebalance(), P=P, m=M,
                             weight=weight, execute=True)
    replans = [r for r in res.records if r.replanned and r.step > 0]
    assert replans, "AlwaysRebalance must replan every step"
    for r in replans:
        assert r.executed_bytes is not None
        assert r.executed_bytes == r.migration_volume, r.step
    if kind == "static":
        assert all(r.executed_bytes == 0.0 for r in replans)
    # keep-steps carry no execution
    res2 = runtime.run_stream(frames, EveryK(k=3), P=P, m=M, execute=True)
    for r in res2.records:
        if not r.replanned:
            assert r.executed_bytes is None


def test_receipt_matches_ledger_exactly():
    frames = np.asarray(stream.drifting_hotspot(3, 40, 56, seed=3))
    plans = _plans(frames)
    old, new = plans[0], plans[1]
    w = frames[1]
    r = execute.execute_migration(old, new, weights=w)
    assert r.executed_bytes == migrate.migration_volume(old, new, w)
    np.testing.assert_array_equal(r.pair_bytes,
                                  migrate.migration_matrix(old, new, w))
    # one transfer per pair with flow; diagonal never transfers
    assert r.n_transfers == int((r.pair_bytes > 0).sum())
    assert not np.diag(r.pair_bytes).any()
    # per-rectangle receipts: device rectload == host Plan.loads, and
    # received == measured inflow
    g = prefix.prefix_sum_2d(w)
    np.testing.assert_allclose(r.rect_loads, np.asarray(new.loads(g)))
    np.testing.assert_allclose(r.rect_received, r.pair_bytes.sum(axis=0))
    execute.verify_receipt(old, new, w, receipt=r)


def test_plan_rect_loads_exact_past_f32_integers():
    """Integer frames whose totals exceed 2**24 are priced on an int32
    Gamma: the rectload kernel equals the host int64 Plan.loads."""
    frames = np.asarray(stream.drifting_hotspot(2, 40, 56, base=20000,
                                                seed=6))
    assert frames[0].sum() > 2 ** 24
    plan = _plans(frames)[0]
    got = execute.plan_rect_loads(plan, weights=frames[0])
    np.testing.assert_array_equal(
        got, plan.loads(prefix.prefix_sum_2d(frames[0])))


def test_identity_plan_moves_nothing():
    frames = np.asarray(stream.static(2, 32, 32, seed=0))
    plan = _plans(frames)[0]
    r = execute.execute_migration(plan, plan, weights=frames[0])
    assert r.executed_bytes == 0.0 and r.n_transfers == 0
    assert not r.pair_bytes.any() and not r.rect_received.any()


def test_execute_validates_inputs():
    frames = np.asarray(stream.drifting_hotspot(2, 24, 24, seed=1))
    plans = _plans(frames)
    with pytest.raises(ValueError, match="weights shape"):
        execute.execute_migration(plans[0], plans[1],
                                  weights=np.ones((3, 3)))
    with pytest.raises(ValueError, match="devices"):
        execute.execute_migration(plans[0], plans[1], weights=frames[1],
                                  devices=jax.device_count() + 1)


def test_execute_interpret_mode_pallas_leg(pallas_kernels):
    """Force the Pallas interpret path explicitly (the CI interpret leg)."""
    frames = np.asarray(stream.drifting_hotspot(2, 24, 40, seed=4))
    plans = _plans(frames)
    r = execute.execute_migration(plans[0], plans[1], weights=frames[1])
    execute.verify_receipt(plans[0], plans[1], frames[1], receipt=r)


def test_executed_bytes_forced_8dev_subprocess():
    """The 1/2/8-device sweep on a forced 8-device host platform:
    executed_bytes == migration_volume whatever the device count, and the
    receipts agree bit-for-bit across mesh sizes (the transfers change,
    the measurement must not)."""
    child = """
import numpy as np, jax
assert jax.device_count() == 8, jax.device_count()
from repro.rebalance import execute, migrate, planner, stream
frames = np.asarray(stream.drifting_hotspot(3, 40, 40, seed=2))
plans = planner.plan_host(frames, P=4, m=12)
old, new, w = plans[0], plans[1], frames[1]
vol = migrate.migration_volume(old, new, w)
flow = migrate.migration_matrix(old, new, w)
for D in (1, 2, 8):
    r = execute.execute_migration(old, new, weights=w, devices=D)
    assert r.executed_bytes == vol, (D, r.executed_bytes, vol)
    assert np.array_equal(r.pair_bytes, flow), D
    assert len(set(r.device_of.tolist())) == min(D, 12), D
print("EXECUTED-EQ-PRICED")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(list(repro.__path__)[0])]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "EXECUTED-EQ-PRICED" in proc.stdout


def test_run_stream_execute_multidevice_inprocess():
    """When the platform exposes >= 2 devices (CI multi-device leg),
    run_stream(execute=True) holds the contract across real transfers."""
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices (subprocess test covers this "
                    "everywhere)")
    frames = np.asarray(stream.drifting_hotspot(3, 32, 32, seed=5))
    res = runtime.run_stream(frames, AlwaysRebalance(), P=P, m=M,
                             execute=True, execute_devices=2)
    for r in res.records:
        if r.replanned and r.step > 0:
            assert r.executed_bytes == r.migration_volume
