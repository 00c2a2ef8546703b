"""SGORP: subgradient-descent d-dimensional rectilinear partitioning.

The combinatorial DPs in this package (jagged, hier, hybrid) are exact but
inherently sequential — they bisect, probe and backtrack on the host.
SGORP (PAPERS.md, arXiv 2310.02470) trades exactness for a shape that
devices love: cut positions become *continuous* variables, each iteration

1. projects the d per-axis cut vectors back to sorted integer cuts,
2. evaluates every cell of the ``p1 x ... x pd`` grid in one gather over
   the d-dimensional SAT prefix (``kernels/sat``'s Gamma / Gamma3) plus d
   ``jnp.diff`` passes,
3. takes a subgradient step on the max-loaded cell's 2d bounding cuts —
   the lower cut of each axis moves up, the upper cut moves down, by a
   Newton-like step ``excess * width / (2d * Lmax)`` (the uniform-density
   estimate of how far each face must travel to shed its share of the
   excess),

under one ``lax.while_loop``, so the whole optimizer is a fixed-point
iteration that jit-compiles once and ``vmap``s over frames.  Convergence
is monitored on the *best projected integer cuts seen*: the loop exits
after ``patience`` non-improving iterations, and because iteration 0
evaluates the warm-start cuts themselves, the result can never be worse
than its warm start — the refiner's contract, which the tests check.

The warm start is the d-axis rectilinear heuristic: an optimal 1D
partition of each axis' margin prefix (``device.optimal_1d_device``),
computed on device so warm start + refinement stay one jit boundary.

Heterogeneous ``speeds`` are supported in the same relative-load sense as
the jagged family: cell ``(i1, .., id)`` belongs to processor
``ravel(i1, .., id)`` (row-major) and the loop minimizes
``max(load / speed)``; the ideal driving the step size becomes
``total / speeds.sum()``.  Speeds must be strictly positive — a fixed
rectilinear grid has no zero-width cell to hand a dead (speed=0)
processor, so ``_run`` raises rather than chase an infinite relative
load; the slab algorithms (``jag-m-heur-3d``) handle dead parts.

Like ``core.device``, this module imports jax at the top — the registry
imports it lazily so the host algorithms stay usable in numpy-only
contexts.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as _trace
from repro.obs.counters import C as _C

__all__ = ["default_grid", "sgorp_2d", "sgorp_3d", "sgorp_refine",
           "sgorp_refine_impl", "sgorp_plan_impl", "sgorp_plan_3d_impl",
           "warm_start_impl"]


def default_grid(m: int, shape: tuple[int, ...]) -> tuple[int, ...]:
    """Factor ``m`` into ``len(shape)`` grid extents, as square as fits.

    Prime factors of m (largest first) go to the dimension with the
    smallest running factor that can still absorb them (``p_i <= n_i``);
    a prime that fits nowhere means no rectilinear m-cell grid exists.
    """
    d = len(shape)
    primes = []
    q, r = m, 2
    while r * r <= q:
        while q % r == 0:
            primes.append(r)
            q //= r
        r += 1
    if q > 1:
        primes.append(q)
    fac = [1] * d
    for pr in sorted(primes, reverse=True):
        cands = [i for i in range(d) if fac[i] * pr <= shape[i]]
        if not cands:
            raise ValueError(
                f"m={m} has no rectilinear grid within shape {shape}: "
                f"prime factor {pr} fits no dimension")
        i = min(cands, key=lambda c: fac[c])
        fac[i] *= pr
    return tuple(fac)


# ---------------------------------------------------------------------------
# device fixed-point loop (pure jnp, unjitted bodies for pipeline fusion)


def _cell_loads(gamma, ics):
    """All grid-cell loads from one Gamma gather: index each axis at its
    cut positions, then one diff per axis (d-dim inclusion–exclusion)."""
    sub = gamma
    for ax, ic in enumerate(ics):
        sub = jnp.take(sub, ic, axis=ax)
    for ax in range(len(ics)):
        sub = jnp.diff(sub, axis=ax)
    return sub


def _project(x, n: int):
    """Continuous cuts -> sorted, clipped integer cuts with pinned ends."""
    xi = jnp.sort(jnp.clip(jnp.round(x), 0, n)).astype(jnp.int32)
    return xi.at[0].set(0).at[-1].set(n)


def sgorp_refine_impl(gamma, warm, speed_grid=None, *, grid,
                      max_iters: int = 256, patience: int = 32):
    """The SGORP fixed-point loop for one frame (unjitted body).

    gamma: (n1+1, .., nd+1) device Gamma; warm: tuple of d integer cut
    vectors ((p_j+1,) each, endpoints 0 / n_j); speed_grid: optional
    grid-shaped per-cell speeds (relative-load objective).  Returns
    ``(cuts, Lmax, iters, projections)`` — cuts are the best projected
    integer cut vectors seen (never worse than ``warm``), ``projections``
    counts iterations whose projection reached a new lattice point.
    """
    d = len(grid)
    shape = tuple(s - 1 for s in gamma.shape)
    fdt = jnp.float32
    total = gamma[(-1,) * d].astype(fdt)
    if speed_grid is None:
        ideal = total / math.prod(grid)
    else:
        ideal = total / jnp.sum(speed_grid).astype(fdt)

    xs0 = tuple(w.astype(fdt) for w in warm)
    best0 = tuple(w.astype(jnp.int32) for w in warm)
    # prev0 deliberately != any projection so iteration 0 counts as one
    prev0 = tuple(jnp.full_like(b, -1) for b in best0)
    inf = jnp.asarray(jnp.inf, fdt)
    state0 = (xs0, best0, inf, prev0, jnp.int32(0), jnp.int32(0),
              jnp.int32(0))

    def cond(state):
        _, _, _, _, t, stall, _ = state
        return (t < max_iters) & (stall < patience)

    def body(state):
        xs, best, best_L, prev, t, stall, proj = state
        ics = tuple(_project(x, n) for x, n in zip(xs, shape))
        loads = _cell_loads(gamma, ics).astype(fdt)
        rel = loads if speed_grid is None else loads / speed_grid
        Lmax = jnp.max(rel)
        improved = Lmax < best_L
        best_L = jnp.where(improved, Lmax, best_L)
        best = tuple(jnp.where(improved, ic, b)
                     for ic, b in zip(ics, best))
        changed = functools.reduce(
            jnp.logical_or, [jnp.any(ic != pv) for ic, pv in zip(ics, prev)])
        proj = proj + changed.astype(jnp.int32)
        stall = jnp.where(improved, jnp.int32(0), stall + 1)
        # subgradient step: shrink the max cell through all 2d faces
        idx = jnp.unravel_index(jnp.argmax(rel), grid)
        excess = jnp.maximum(Lmax - ideal, 0.0)
        new_xs = []
        for j in range(d):
            x = xs[j]
            lo_i, hi_i = idx[j], idx[j] + 1
            w = jnp.maximum(x[hi_i] - x[lo_i], 1e-6)
            delta = jnp.clip(excess * w / (2 * d * jnp.maximum(Lmax, 1e-6)),
                             0.0, 0.45 * w)
            x = x.at[lo_i].add(delta * (lo_i > 0))
            x = x.at[hi_i].add(-delta * (hi_i < grid[j]))
            new_xs.append(jnp.sort(jnp.clip(x, 0.0, shape[j])))
        return (tuple(new_xs), best, best_L, ics, t + 1, stall, proj)

    _, best, best_L, _, t, _, proj = jax.lax.while_loop(cond, body, state0)
    return best, best_L, t, proj


def warm_start_impl(gamma, *, grid, k: int = 8, rounds: int = 8):
    """Rectilinear warm start: optimal 1D cuts of each axis margin prefix
    (the projection heuristic), fully on device."""
    from . import device
    d = len(grid)
    cuts = []
    for j in range(d):
        p = gamma
        for ax in range(d - 1, -1, -1):
            if ax != j:
                p = p[(slice(None),) * ax + (-1,)]
        c, _ = device.optimal_1d_device(p, grid[j], k=k, rounds=rounds)
        cuts.append(c)
    return tuple(cuts)


def sgorp_plan_impl(gamma, speed_grid=None, *, grid, max_iters: int = 256,
                    patience: int = 32, k: int = 8, rounds: int = 8):
    """Warm start + refine for one frame (unjitted — fuses under vmap /
    shard_map).  Returns (cuts tuple, Lmax, iters, projections)."""
    warm = warm_start_impl(gamma, grid=grid, k=k, rounds=rounds)
    return sgorp_refine_impl(gamma, warm, speed_grid, grid=grid,
                             max_iters=max_iters, patience=patience)


def sgorp_plan_3d_impl(frames, speed_grid=None, *, grid,
                       max_iters: int = 256, patience: int = 32,
                       k: int = 8, rounds: int = 8, gamma_dtype=None):
    """The batched 3D planning chain: (T, n1, n2, n3) frames -> stacked
    rectilinear cuts.  ingest -> Gamma3 (``kernels/sat`` rank-3 path) ->
    vmapped warm start + SGORP refine — one jit boundary, so the sharded
    planner traces it like the 2D chain.  The platform picks the Gamma3
    build (:mod:`repro.backend`): the compiled kernel on a TPU, the
    oracle elsewhere.  Returns (cuts1 (T, p1+1), cuts2 (T, p2+1),
    cuts3 (T, p3+1), Lmax (T,), iters (T,), projections (T,))."""
    from repro.backend import use_pallas_default
    from repro.kernels.sat import ops as sat_ops
    gamma_dtype = jnp.float32 if gamma_dtype is None else gamma_dtype
    g = sat_ops.gamma3_impl(frames.astype(gamma_dtype),
                            use_pallas=use_pallas_default())

    def one(gamma):
        cuts, L, it, pr = sgorp_plan_impl(gamma, speed_grid, grid=grid,
                                          max_iters=max_iters,
                                          patience=patience, k=k,
                                          rounds=rounds)
        return cuts + (L, it, pr)

    return jax.vmap(one)(g)


@functools.partial(jax.jit,
                   static_argnames=("grid", "max_iters", "patience"))
def sgorp_refine(gamma, warm, speed_grid=None, *, grid,
                 max_iters: int = 256, patience: int = 32):
    """Jitted standalone refiner (see :func:`sgorp_refine_impl`)."""
    return sgorp_refine_impl(gamma, warm, speed_grid, grid=grid,
                             max_iters=max_iters, patience=patience)


# ---------------------------------------------------------------------------
# host entry points (registry adapters)


def _device_gamma_nd(gamma: np.ndarray):
    """int32/f32 device copy with the same overflow guard as the 2D
    registry adapter (int32 accumulators cap exact totals at 2**31)."""
    g = np.asarray(gamma)
    if np.issubdtype(g.dtype, np.integer):
        if int(g[(-1,) * g.ndim]) >= 2 ** 31:
            raise ValueError(
                f"total load {int(g[(-1,) * g.ndim])} overflows the device "
                f"refiner's int32 accumulators; pass a float load array")
        return jnp.asarray(g, jnp.int32)
    return jnp.asarray(g)


@functools.lru_cache(maxsize=None)
def _jitted_plan(grid, max_iters, patience):
    def fn(gamma, speed_grid):
        cuts, L, it, pr = sgorp_plan_impl(gamma, speed_grid, grid=grid,
                                          max_iters=max_iters,
                                          patience=patience)
        return cuts + (L, it, pr)

    return jax.jit(fn)


def _run(gamma_host: np.ndarray, m: int, grid, speeds, max_iters, patience):
    """Shared host driver: resolve grid, jit the plan, bump counters."""
    d = gamma_host.ndim
    shape = tuple(s - 1 for s in gamma_host.shape)
    if grid is None:
        grid = default_grid(m, shape)
    grid = tuple(int(p) for p in grid)
    if math.prod(grid) != m:
        raise ValueError(f"grid {grid} has {math.prod(grid)} cells, "
                         f"need m={m}")
    if any(p > n for p, n in zip(grid, shape)):
        raise ValueError(f"grid {grid} exceeds shape {shape}")
    g = _device_gamma_nd(gamma_host)
    speed_grid = None
    if speeds is not None:
        sp = np.asarray(speeds, np.float64)
        if (sp <= 0).any():
            # a fixed (p1 x ... x pd) processor grid cannot hand a dead
            # processor a zero-width cell; the slab algorithms can
            raise ValueError(
                "sgorp requires strictly positive speeds (its rectilinear "
                "grid has no zero-width cells for dead processors); use "
                "jag-m-heur-3d / jag-m-heur for speed=0 parts")
        speed_grid = jnp.asarray(sp.reshape(grid), jnp.float32)
    fn = _jitted_plan(grid, int(max_iters), int(patience))
    with _trace.span("sgorp.refine", grid=str(grid), m=int(m)):
        out = fn(g, speed_grid)
        cuts = [np.asarray(c, np.int64) for c in out[:d]]
    _C.sgorp_iterations += int(out[d + 1])
    _C.sgorp_projections += int(out[d + 2])
    return cuts


def sgorp_2d(gamma: np.ndarray, m: int, *,
             grid: tuple[int, int] | None = None, speeds=None,
             max_iters: int = 256, patience: int = 32):
    """Registry entry ``sgorp-2d``: rectilinear p1 x p2 partition of a 2D
    Gamma by the device SGORP loop; never worse than the per-axis 1D
    projection heuristic it warm-starts from."""
    from .types import from_grid
    gamma = np.asarray(gamma)
    rc, cc = _run(gamma, m, grid, speeds, max_iters, patience)
    return from_grid(rc, cc, (gamma.shape[0] - 1, gamma.shape[1] - 1))


def sgorp_3d(A: np.ndarray, m: int, *,
             grid: tuple[int, int, int] | None = None, speeds=None,
             max_iters: int = 256, patience: int = 32):
    """Registry entry ``sgorp-3d``: rectilinear p1 x p2 x p3 partition of
    a raw ``(n1, n2, n3)`` load volume (rank-3 registry convention)."""
    from .prefix import prefix_sum_3d
    from .threed import partition3d_from_grid
    A = np.asarray(A)
    cuts = _run(prefix_sum_3d(A), m, grid, speeds, max_iters, patience)
    return partition3d_from_grid(*cuts, shape=A.shape)
