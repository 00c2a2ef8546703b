"""Mesh-sharded stream planner: the batched pipeline as composable stages.

``batch_device.plan_stream`` (PR 3) fused SAT build + ``jag_m_heur_device``
over a ``(T, n1, n2)`` frame stream under one jit — on *one* device.  This
module is the distribution layer above it: the same chain, split into
named stages

    frame ingest -> SAT build -> partition -> cut collect

and executed either

- on one device (the reference path — today's vmap, still exactly
  ``batch_device.plan_stream``), or
- sharded over the data-parallel axis of a mesh
  (``dist.ctx.planner_mesh``) via ``shard_map``: each device owns a
  contiguous time slice, frames and Gammas stay device-local, and only
  the O(T * m) cut vectors are gathered.

Per-frame computations never cross the time axis, so the sharded plans
are **bit-identical** to the single-device reference on 1-, 2- and
8-device meshes (regression-tested, including T not divisible by the
device count — the ragged tail is zero-padded on device and trimmed from
the result).

The platform picks the kernels (:mod:`repro.backend`): the SAT stage
and the exact solver's column probe run their Pallas kernels on a TPU
and their jnp oracles elsewhere.  No function here takes that choice.

``iter_plan_slices`` / ``plan_iter`` expose the stream lazily: every
slice is dispatched up front (jax dispatch is asynchronous), so a policy
loop consuming slice ``i`` overlaps with the devices still planning
slices ``i+1..`` instead of blocking on the full stream.

The graded replan decision (:func:`repro.rebalance.policy.replan_mode`)
is re-exported here: planning and deciding-when-to-adopt are the two
halves of the planner API that ``rebalance.runtime``,
``dist.cp_balance`` and ``serve.batcher`` consume.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import use_pallas_default
from repro.core import device
from repro.kernels.sat import ops as sat_ops
from repro.obs import trace as _trace
from repro.obs.counters import C as _C
from repro.rebalance.policy import replan_mode

__all__ = ["ingest_stage", "sat_stage", "partition_stage", "plan_frames",
           "plan_frames_3d", "plan_stream", "plan_stream_3d",
           "iter_plan_slices", "plan_iter", "plan_host",
           "resolve_mesh", "replan_mode"]

# How many slices the lazy iterator aims for when none is requested: deep
# enough that the policy loop starts after ~1/4 of the stream is planned,
# shallow enough that per-slice dispatch overhead stays negligible.
_DEFAULT_SLICES = 4


def _check_finite(frames, t0: int, t1: int, *, what: str) -> None:
    """Refuse NaN/inf frames *before* they reach the device pipeline.

    A poisoned frame does not crash the partitioner — NaNs propagate
    through the SAT scan and the device bisection silently produces
    garbage cuts for every frame sharing the slice — so ingest is the
    one place the corruption is still attributable.  Names the offending
    absolute time-steps and the slice they were batched into.  The
    ``planner.check`` span covers the whole check: the host copy, the
    dtype test and the NaN scan.
    """
    with _trace.span("planner.check", frames=t1 - t0):
        arr = np.asarray(frames)
        if not np.issubdtype(arr.dtype, np.floating):
            return  # integer loads cannot encode NaN/inf
        bad = ~np.isfinite(arr.reshape(arr.shape[0], -1)).all(axis=1)
        if bad.any():
            steps = (t0 + np.flatnonzero(bad)).tolist()
            shown = ", ".join(map(str, steps[:8]))
            more = f" (+{len(steps) - 8} more)" if len(steps) > 8 else ""
            raise ValueError(
                f"{what}: non-finite load frame(s) at step(s) {shown}"
                f"{more} in [{t0}, {t1}) — NaN/inf would silently corrupt "
                f"every cut in this slice; clean or drop the frames before "
                f"planning")


# ---------------------------------------------------------------------------
# stages (pure jnp, unjitted — composed under exactly one jit boundary)


def resolve_gamma_dtype(gamma_dtype, *, exact: bool):
    """Accumulator dtype: explicit wins; else int32 (exact) / f32 (heur).

    The exact path bisects on *integers* — int32 accumulation is lossless
    up to 2**31 total load, where f32 already lies above 2**24 — while the
    heuristic path keeps its historical f32 default.
    """
    if gamma_dtype is not None:
        return gamma_dtype
    return jnp.int32 if exact else jnp.float32


def ingest_stage(frames: jnp.ndarray, *,
                 gamma_dtype=jnp.float32) -> jnp.ndarray:
    """Frame ingest: cast to the accumulator dtype *before* the SAT scan.

    Accumulation happens in ``gamma_dtype`` (f32 saturates above 2**24
    total load; pass ``jnp.float64`` with x64 enabled for large integer
    loads).
    """
    return frames.astype(gamma_dtype)


def sat_stage(frames: jnp.ndarray) -> jnp.ndarray:
    """SAT build: (T, n1, n2) frames -> (T, n1+1, n2+1) Gammas.

    Both backends take the batch natively — the Pallas kernel's leading
    batch grid axis (so the blocked path lowers under the sharded trace
    instead of falling back to the jnp oracle) and the oracle's
    trailing-axes cumsum.  The platform picks one (:mod:`repro.backend`):
    the compiled kernel on a TPU, the oracle elsewhere.
    """
    return sat_ops.gamma_impl(frames, use_pallas=use_pallas_default())


def partition_stage(gammas: jnp.ndarray, *, P: int, m: int, k: int = 8,
                    rounds: int = 8, gamma_dtype=None, exact: bool = False):
    """Partition: vmapped partitioner over the (T, n1+1, n2+1) Gamma batch.

    ``exact=False`` (default) runs JAG-M-HEUR; ``exact=True`` runs the
    device-native exact JAG-PQ-OPT (``device.jag_pq_opt_device_impl``,
    ``Q = m // P`` intervals per stripe — cuts bit-identical to the host
    ``jagged.jag_pq_opt(orient='hor')``), whose column probes take the
    fused ``kernels.probe`` kernel on a TPU.  Returns (row_cuts (T, P+1),
    counts (T, P), col_cuts (T, P, *), Lmax (T,)).
    """
    if exact:
        if m % P != 0:
            raise ValueError(
                f"exact planning needs m divisible by P (m={m}, P={P}): "
                f"the exact device solver is the P x Q form")
        fn = functools.partial(device.jag_pq_opt_device_impl, P=P, Q=m // P,
                               k=max(k, 2))
    else:
        fn = functools.partial(device.jag_m_heur_device_impl, P=P, m=m, k=k,
                               rounds=rounds, gamma_dtype=gamma_dtype)
    return jax.vmap(fn)(gammas)


def plan_frames(frames: jnp.ndarray, *, P: int, m: int, k: int = 8,
                rounds: int = 8, gamma_dtype=None, exact: bool = False):
    """The full unjitted chain: ingest -> SAT -> partition.

    Every intermediate (frames, Gammas) stays on the executing device;
    the returned pytree is the O(T * m) cut vectors only — the "cut
    collect" stage is whoever fetches them (the host, or the all-gather
    implicit in reading a sharded result).  ``exact=True`` swaps the
    partition stage for the exact device JAG-PQ-OPT and defaults the
    accumulator to int32 (see :func:`resolve_gamma_dtype`) — on a TPU
    this is the fused SAT -> probe -> cut path, no host round-trip
    between integral image and cuts.

    Each stage runs under a ``jax.named_scope`` (``planner.ingest``,
    ``planner.sat``, ``planner.partition``), which names the ``op_name``
    of its instructions in the compiled HLO and changes nothing else.
    """
    gamma_dtype = resolve_gamma_dtype(gamma_dtype, exact=exact)
    with jax.named_scope("planner.ingest"):
        f = ingest_stage(frames, gamma_dtype=gamma_dtype)
    with jax.named_scope("planner.sat"):
        g = sat_stage(f)
    with jax.named_scope("planner.partition"):
        return partition_stage(g, P=P, m=m, k=k, rounds=rounds,
                               gamma_dtype=gamma_dtype, exact=exact)


def plan_frames_3d(frames: jnp.ndarray, *, grid: tuple[int, ...],
                   max_iters: int = 256, patience: int = 32, k: int = 8,
                   rounds: int = 8, gamma_dtype=None):
    """The rank-3 chain: ingest -> 3D SAT -> vmapped SGORP plan.

    The volumetric twin of :func:`plan_frames` for ``(T, n1, n2, n3)``
    frame batches: one 3D Gamma build (``kernels.sat.gamma3``), then the
    device SGORP planner per frame — per-axis 1D warm start refined by
    the subgradient fixed point (``core.sgorp``), all under the caller's
    jit boundary.  ``grid`` is the static (p1, p2, p3) processor grid.
    Returns ``(cuts1 (T, p1+1), cuts2, cuts3, Lmax (T,), iters (T,),
    projections (T,))``.
    """
    from repro.core import sgorp
    return sgorp.sgorp_plan_3d_impl(
        frames, grid=grid, max_iters=max_iters, patience=patience,
        k=k, rounds=rounds, gamma_dtype=gamma_dtype)


# ---------------------------------------------------------------------------
# mesh execution


def resolve_mesh(mesh=None, devices: int | None = None):
    """Planner-mesh resolution for consumer-facing ``devices=N`` knobs.

    An explicit mesh wins; ``devices=N`` builds the 1-D
    ``dist.ctx.planner_mesh`` over the first N host devices; ``N=1`` /
    nothing means the single-device reference path (``None``).
    """
    if mesh is not None:
        return mesh
    if devices is None or devices <= 1:
        return None
    from repro.dist import ctx
    return ctx.planner_mesh(devices)


def _dp_spec(mesh):
    """(PartitionSpec over the DP axes, total DP size) for ``mesh``."""
    from jax.sharding import PartitionSpec
    from repro.dist import ctx
    axes = ctx.planner_axes(mesh)
    sizes = ctx.mesh_sizes(mesh)
    spec = PartitionSpec(axes if len(axes) > 1 else axes[0])
    return spec, int(math.prod(sizes[a] for a in axes))


@functools.lru_cache(maxsize=None)
def _sharded_plan_fn(mesh, P, m, k, rounds, gamma_dtype, exact):
    """jit(shard_map(chain)) for one (mesh, signature) — cached so repeat
    calls reuse the compiled executable."""
    spec, _ = _dp_spec(mesh)
    body = functools.partial(plan_frames, P=P, m=m, k=k, rounds=rounds,
                             gamma_dtype=gamma_dtype, exact=exact)
    # every computation is frame-local — no value ever varies across the
    # mesh except through the sharded time axis — so the varying-axes
    # check has nothing to prove; it is off because the solvers' scan and
    # while_loop carries start from unsharded constants
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(spec,),
                                 out_specs=spec, check_vma=False))


@functools.lru_cache(maxsize=None)
def _sharded_plan3d_fn(mesh, grid, max_iters, patience, k, rounds,
                       gamma_dtype):
    """jit(shard_map(3D chain)) for one (mesh, signature) — cached like
    :func:`_sharded_plan_fn`."""
    spec, _ = _dp_spec(mesh)
    body = functools.partial(plan_frames_3d, grid=grid, max_iters=max_iters,
                             patience=patience, k=k, rounds=rounds,
                             gamma_dtype=gamma_dtype)
    # frame-local like the 2D chain above, so the check is off likewise
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(spec,),
                                 out_specs=spec, check_vma=False))


def plan_stream_3d(frames, *, m: int, grid: tuple[int, ...] | None = None,
                   mesh=None, max_iters: int = 256, patience: int = 32,
                   k: int = 8, rounds: int = 8, gamma_dtype=None):
    """SGORP planning for a whole (T, n1, n2, n3) volume stream.

    The rank-3 twin of :func:`plan_stream`: ``mesh=None`` runs the whole
    batch on one device under one jit; with a mesh, the time axis is
    sharded over its data-parallel axes exactly like the 2D path (cuts
    bit-identical across 1/2/8-device meshes; a ragged T is zero-padded
    on device and trimmed — an all-zero frame converges trivially and is
    discarded).  ``grid=None`` derives the (p1, p2, p3) processor grid
    from ``m`` via :func:`repro.core.sgorp.default_grid`.  Returns the
    stacked ``(cuts1, cuts2, cuts3, Lmax, iters, projections)`` pytree.
    """
    from repro.core import sgorp
    frames = jnp.asarray(frames)
    if frames.ndim != 4:
        raise ValueError(
            f"plan_stream_3d takes (T, n1, n2, n3) frames, got rank "
            f"{frames.ndim}")
    _check_finite(frames, 0, frames.shape[0], what="plan_stream_3d")
    if grid is None:
        grid = sgorp.default_grid(m, tuple(frames.shape[1:]))
    grid = tuple(int(g) for g in grid)
    if math.prod(grid) != m:
        raise ValueError(f"grid {grid} has {math.prod(grid)} cells, "
                         f"expected m={m}")
    gamma_dtype = jnp.float32 if gamma_dtype is None else gamma_dtype
    if mesh is None:
        fn = jax.jit(functools.partial(
            plan_frames_3d, grid=grid, max_iters=max_iters,
            patience=patience, k=k, rounds=rounds,
            gamma_dtype=jnp.dtype(gamma_dtype)))
        return fn(frames)
    from jax.sharding import NamedSharding
    spec, D = _dp_spec(mesh)
    T = frames.shape[0]
    Tpad = -(-T // D) * D
    if Tpad != T:
        frames = jnp.concatenate(
            [frames, jnp.zeros((Tpad - T,) + frames.shape[1:],
                               frames.dtype)])
    fr = jax.device_put(frames, NamedSharding(mesh, spec))
    out = _sharded_plan3d_fn(mesh, grid, max_iters, patience, k, rounds,
                             jnp.dtype(gamma_dtype))(fr)
    if Tpad != T:
        out = jax.tree_util.tree_map(lambda x: x[:T], out)
    return out


def plan_stream(frames, *, P: int, m: int, mesh=None, k: int = 8,
                rounds: int = 8, gamma_dtype=None, exact: bool = False):
    """SAT + partitioner for a whole (T, n1, n2) stream.

    ``mesh=None`` is the single-device reference (identical to
    ``batch_device.plan_stream``); with a mesh, the time axis is sharded
    over its data-parallel axes — each device plans its own contiguous
    slice and only the cut vectors leave it.  Cuts are bit-identical
    across mesh sizes.  When T does not divide the DP size, the stream is
    zero-padded on device and the padding trimmed from the result.

    ``exact=True`` plans every frame with the exact device JAG-PQ-OPT
    (``Q = m // P``) instead of the heuristic — cuts bit-identical to
    the host ``jagged.jag_pq_opt(orient='hor')`` per frame, sharded over
    the mesh exactly like the heuristic path.

    Rank-4 ``(T, n1, n2, n3)`` frames route to :func:`plan_stream_3d`
    (the SGORP chain): ``P`` — a 2D stripe count — is ignored there; the
    (p1, p2, p3) processor grid is derived from ``m``.
    """
    from repro.rebalance import batch_device
    frames = jnp.asarray(frames)
    if frames.ndim == 4:
        if exact:
            raise ValueError(
                "exact=True has no rank-3 solver; the 3D path plans with "
                "the SGORP refiner (plan_stream_3d)")
        return plan_stream_3d(frames, m=m, mesh=mesh, k=k, rounds=rounds,
                              gamma_dtype=gamma_dtype)
    _check_finite(frames, 0, frames.shape[0], what="plan_stream")
    if exact:
        # every exact frame's row probe takes the one-pass greedy step
        # (device._onepass_step); plan_iter and plan_host count here too
        _C.exact_row_onepass_frames += frames.shape[0]
    gamma_dtype = resolve_gamma_dtype(gamma_dtype, exact=exact)
    if mesh is None:
        return batch_device.plan_stream(
            frames, P=P, m=m, k=k, rounds=rounds, gamma_dtype=gamma_dtype,
            exact=exact)
    from jax.sharding import NamedSharding
    spec, D = _dp_spec(mesh)
    T = frames.shape[0]
    Tpad = -(-T // D) * D
    if Tpad != T:
        frames = jnp.concatenate(
            [frames, jnp.zeros((Tpad - T,) + frames.shape[1:],
                               frames.dtype)])
    fr = jax.device_put(frames, NamedSharding(mesh, spec))
    out = _sharded_plan_fn(mesh, P, m, k, rounds, jnp.dtype(gamma_dtype),
                           exact)(fr)
    if Tpad != T:
        out = jax.tree_util.tree_map(lambda x: x[:T], out)
    return out


def _count_probe_steps(plans, *, P: int, m: int) -> None:
    """Bump the JAG-M-HEUR stripe-probe step counters from host Plans.

    Each frame's greedy probes run ``max(counts)`` steps where a static
    loop would run ``m - P + 1``; the counts are already on the host, so
    this costs no device sync and no extra copy.
    """
    _C.heur_probe_steps += sum(int(pl.counts.max()) for pl in plans)
    _C.heur_probe_steps_static += len(plans) * (m - P + 1)


# ---------------------------------------------------------------------------
# lazy per-slice consumption


def iter_plan_slices(frames, *, P: int, m: int, mesh=None,
                     slice_size: int | None = None, k: int = 8,
                     rounds: int = 8, gamma_dtype=None, exact: bool = False):
    """Yield ``(t0, t1, batched_slice)`` over the stream, planned lazily.

    All slices are dispatched before the first yield — jax dispatch is
    asynchronous, so a consumer working through slice ``i``'s cuts
    overlaps with the device(s) still planning slices ``i+1..``.  Every
    full slice has ``slice_size`` frames (rounded up to a DP-size
    multiple on a mesh) and shares one compiled program; a ragged tail
    is a second, smaller shape and compiles once more (on the mesh path
    it is first padded up to the next DP-size multiple, which only
    coincides with ``slice_size`` when the tail is within D of it).
    """
    frames = jnp.asarray(frames)
    T = frames.shape[0]
    D = 1 if mesh is None else _dp_spec(mesh)[1]
    if slice_size is None:
        slice_size = max(D, -(-T // _DEFAULT_SLICES))
    slice_size = -(-slice_size // D) * D
    pending = []
    for i, t0 in enumerate(range(0, T, slice_size)):
        t1 = min(t0 + slice_size, T)
        _check_finite(frames[t0:t1], t0, t1, what=f"planner slice {i}")
        # host-side span: measures the *dispatch* only (jax dispatch is
        # async), so instrumentation never serializes the slice overlap
        with _trace.span("planner.dispatch", slice=i, t0=t0, t1=t1):
            pending.append((t0, t1, plan_stream(
                frames[t0:t1], P=P, m=m, mesh=mesh, k=k, rounds=rounds,
                gamma_dtype=gamma_dtype, exact=exact)))
    yield from pending


def plan_iter(frames, *, P: int, m: int, mesh=None,
              slice_size: int | None = None, k: int = 8, rounds: int = 8,
              gamma_dtype=None, exact: bool = False):
    """Per-frame :class:`~repro.rebalance.batch_device.Plan` iterator.

    The lazy flattening of :func:`iter_plan_slices` — what the runtime's
    policy loop consumes in lockstep with the frames.
    """
    from repro.rebalance import batch_device
    shape = tuple(frames.shape[1:])
    for t0, t1, batched in iter_plan_slices(
            frames, P=P, m=m, mesh=mesh, slice_size=slice_size, k=k,
            rounds=rounds, gamma_dtype=gamma_dtype, exact=exact):
        # collect blocks on the slice's device results (the first host
        # read) — its span width is the wait the policy loop actually saw
        with _trace.span("planner.collect", t0=t0, t1=t1):
            plans = batch_device.unstack_plans(batched, shape)
        if not exact:
            _count_probe_steps(plans, P=P, m=m)
        yield from plans


def plan_host(frames, *, P: int, m: int, mesh=None, k: int = 8,
              rounds: int = 8, gamma_dtype=None, exact: bool = False):
    """Whole-stream planning to host Plans (one dispatch, no slicing)."""
    from repro.rebalance import batch_device
    batched = plan_stream(frames, P=P, m=m, mesh=mesh, k=k, rounds=rounds,
                          gamma_dtype=gamma_dtype, exact=exact)
    plans = batch_device.unstack_plans(batched, tuple(frames.shape[1:]))
    if not exact:
        _count_probe_steps(plans, P=P, m=m)
    return plans
