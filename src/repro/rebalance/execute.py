"""Executed migrations: actually move owned state between devices.

``migrate`` prices a plan switch on paper (owner-map diff, weight sums);
this module *performs* it and reports what was measured, so the runtime's
cost model can be audited against real transfers.  The contract — tested
on integer streams, where every sum is exact — is::

    receipt.executed_bytes == migrate.migration_volume(old, new, weights)
    receipt.pair_bytes     == migrate.migration_matrix(old, new, weights)

Execution model: processor ``i`` lives on device ``devices[i % D]``
(round-robin, matching the planner's positional rectangle identity).  For
every (src, dst) processor pair with a non-empty owner-change flow, the
moved cells' weights are materialized on the source device and
``jax.device_put`` to the destination; ``executed_bytes`` sums the
buffers *after* the transfer — the measurement comes from the data that
actually arrived, not from the plan diff.  Integer frames travel as
``int32`` (exact sums); anything else as ``float32``.

Per-rectangle accounting rides the :mod:`repro.kernels.rectload` Pallas
kernel with its leading frame axis: one batched launch over the stack
``[Gamma(weights), Gamma(retained weights)]`` under the *adopted* plan's
cuts prices every rectangle's total and retained load on device, and
their difference is the weight each rectangle received
(``receipt.rect_received``, cross-checked against the measured pair
inflows).  Gammas of integer weights are int32 on device, so the prices
are exact up to a total of 2**31; any other Gamma is f32.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import prefix
from repro.kernels.rectload.ops import jagged_loads
from repro.obs import trace as _trace

from . import migrate
from .batch_device import Plan

__all__ = ["MigrationReceipt", "execute_migration", "plan_rect_loads",
           "verify_receipt"]


@dataclasses.dataclass(frozen=True)
class MigrationReceipt:
    """What an executed plan switch actually moved.

    ``executed_bytes`` is weight measured from the transferred buffers
    (the unit is weight, like ``migration_volume`` — "bytes" names the
    role: it is the wire-transfer ledger entry, proportional to bytes
    for fixed-size per-unit state).
    """

    executed_bytes: float       # total measured weight moved
    pair_bytes: np.ndarray      # (m, m) measured per (src, dst) flow
    n_transfers: int            # device_put calls issued
    rect_loads: np.ndarray      # (m,) adopted-plan loads (device rectload)
    rect_received: np.ndarray   # (m,) weight each rectangle received
    device_of: np.ndarray       # (m,) device index per processor


def _resolve_devices(devices) -> list:
    if devices is None:
        return list(jax.devices())
    if isinstance(devices, int):
        avail = jax.devices()
        if devices > len(avail):
            raise ValueError(f"asked for {devices} devices, "
                             f"have {len(avail)}")
        return list(avail[:devices])
    return list(devices)


def _weight_array(plan: Plan, weights) -> tuple[np.ndarray, np.dtype]:
    """Per-cell weights as (n1, n2) + the on-wire dtype (int32 when the
    frame is integral so the measured sums are exact)."""
    if weights is None:
        w = np.ones(plan.shape, dtype=np.int64)
    else:
        w = np.asarray(weights)
        if w.shape != plan.shape:
            raise ValueError(f"weights shape {w.shape} != grid "
                             f"{plan.shape}")
    integral = np.issubdtype(w.dtype, np.integer)
    return w, (np.int32 if integral else np.float32)


def _live_loads(plan: Plan, loads_pq: np.ndarray) -> np.ndarray:
    """Flatten a (P, m_max) rectload result to the (m,) row-major live
    vector (masked trailing intervals dropped)."""
    live = np.arange(1, plan.col_cuts.shape[1])[None, :] \
        <= np.asarray(plan.counts)[:, None]
    return loads_pq[live]


def _device_gammas(*ws: np.ndarray) -> jnp.ndarray:
    """(len(ws), n1+1, n2+1) Gammas on device: int32 when the weights are
    integers whose totals fit it (exact), f32 otherwise."""
    gs = np.stack([prefix.prefix_sum_2d(w) for w in ws])
    exact = np.issubdtype(gs.dtype, np.integer) \
        and int(gs[:, -1, -1].max()) < 2 ** 31
    return jnp.asarray(gs, dtype=jnp.int32 if exact else jnp.float32)


def plan_rect_loads(plan: Plan, weights=None) -> np.ndarray:
    """(m,) per-rectangle loads of ``plan`` computed on device via the
    rectload kernel (host twin: :meth:`Plan.loads` on the frame's Gamma).
    """
    w, _ = _weight_array(plan, weights)
    g = _device_gammas(w)[0]
    out = jagged_loads(g, jnp.asarray(plan.row_cuts, dtype=jnp.int32),
                       jnp.asarray(plan._live_col_cuts(), dtype=jnp.int32))
    return _live_loads(plan, np.asarray(out))


def execute_migration(old: Plan, new: Plan, weights=None, *,
                      devices=None) -> MigrationReceipt:
    """Move every owner-changed cell's weight to its new processor's
    device and measure what arrived.  See the module docstring for the
    exactness contract against :mod:`repro.rebalance.migrate`.
    """
    w, wire_dtype = _weight_array(old, weights)
    m = max(old.m, new.m)
    dev = _resolve_devices(devices)
    device_of = np.arange(m) % len(dev)

    o = old.owner_map().ravel()
    n = new.owner_map().ravel()
    wf = w.ravel()
    moved = o != n

    pair_bytes = np.zeros((m, m))
    executed = 0.0
    n_transfers = 0
    with _trace.span("rebalance.execute", m=m, devices=len(dev),
                     moved_cells=int(moved.sum())) as sp:
        if moved.any():
            src, dst, vals = o[moved], n[moved], wf[moved]
            # group moved cells by (src, dst) pair: one transfer per pair
            key = src.astype(np.int64) * m + dst
            order = np.argsort(key, kind="stable")
            key, vals = key[order], vals[order]
            starts = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
            bounds = np.r_[starts, key.size]
            for a, b in zip(bounds[:-1], bounds[1:]):
                i, j = divmod(int(key[a]), m)
                payload = jax.device_put(
                    jnp.asarray(vals[a:b], dtype=wire_dtype),
                    dev[device_of[i]])
                received = jax.device_put(payload, dev[device_of[j]])
                received.block_until_ready()
                got = float(np.asarray(received).sum(dtype=np.float64))
                pair_bytes[i, j] += got
                executed += got
                n_transfers += 1
        sp.args["executed"] = executed

        # per-rectangle receipt: one batched rectload launch prices the
        # adopted plan on [full weights, retained weights] — their
        # difference is what each rectangle received
        stack = _device_gammas(w, np.where((o == n).reshape(w.shape), w, 0))
        rc = jnp.broadcast_to(
            jnp.asarray(new.row_cuts, dtype=jnp.int32),
            (2,) + new.row_cuts.shape)
        cc = jnp.broadcast_to(
            jnp.asarray(new._live_col_cuts(), dtype=jnp.int32),
            (2,) + new.col_cuts.shape)
        both = np.asarray(jagged_loads(stack, rc, cc))
        rect_loads = _live_loads(new, both[0])
        rect_received = _live_loads(new, both[0] - both[1])

    return MigrationReceipt(executed_bytes=executed, pair_bytes=pair_bytes,
                            n_transfers=n_transfers, rect_loads=rect_loads,
                            rect_received=rect_received,
                            device_of=device_of)


def verify_receipt(old: Plan, new: Plan, weights=None, *,
                   receipt: MigrationReceipt, rtol: float = 0.0,
                   atol: float = 0.0) -> None:
    """Assert the measured receipt matches the paper ledger (exact by
    default — the integer-stream contract; pass tolerances for float
    frames).  Raises ``AssertionError`` with the deltas on mismatch."""
    vol = migrate.migration_volume(old, new, weights)
    if not np.isclose(receipt.executed_bytes, vol, rtol=rtol, atol=atol):
        raise AssertionError(f"executed_bytes {receipt.executed_bytes} != "
                             f"migration_volume {vol}")
    flow = migrate.migration_matrix(old, new, weights)
    if not np.allclose(receipt.pair_bytes, flow, rtol=rtol, atol=atol):
        delta = float(np.abs(receipt.pair_bytes - flow).max())
        raise AssertionError(f"pair_bytes != migration_matrix "
                             f"(max delta {delta})")
    inflow = receipt.pair_bytes.sum(axis=0)
    if not np.allclose(receipt.rect_received[:inflow.size], inflow,
                       rtol=max(rtol, 1e-6), atol=max(atol, 1e-4)):
        raise AssertionError("rect_received disagrees with measured pair "
                             "inflows")
