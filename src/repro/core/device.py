"""On-device (jittable) partitioners — the TPU adaptation of Section 2.2.

The paper's NicolPlus machinery is pointer-chasing parametric search — fine
on a host CPU, hostile to a TPU's vector units. We restructure it:

- ``probe_device``: the Han-et-al greedy probe as a ``lax.scan`` of
  one-pass greedy steps (``_advance``: one compare-and-reduce over the
  row, carrying the prefix value, no search), *vectorized over a batch
  of candidate bottleneck values* (the VPU sweeps many L values at the
  price of one).
- ``wide_bisect_device``: the device twin of ``search.bisect_bottleneck`` —
  each round probes K ascending candidates spanning [lo, hi] simultaneously,
  shrinking the interval by (K+1)x per round instead of 2x; the default 8
  rounds at K=8 are a 4.3e7x reduction of the initial DirectCut gap. The
  limiting factor is the *accumulator* dtype, not the bisection: an f32
  prefix array loses integer exactness once loads cross 2**24, so
  ``jag_m_heur_device`` takes a ``gamma_dtype`` (pass ``jnp.float64`` with
  x64 enabled for large integer loads). Both on-device wide bisections
  (``optimal_1d_device`` and the per-stripe loop of ``jag_m_heur_device``)
  run through this one helper, mirroring how every host bisection runs
  through ``repro.core.search``.
- ``jag_m_heur_device``: the paper's JAG-M-HEUR end-to-end on device: main
  dimension by wide bisection, proportional processor counts, per-stripe
  cuts by a batched masked probe (vmapped over stripes) whose greedy
  loop runs as many steps as the fullest stripe has processors. Only the O(m) cut
  vectors ever leave the device — the load matrix stays in HBM, enabling
  the distributed rebalancing the paper's Section 6 calls for.

Exact solvers (ported from the host engine, PR 7):

- ``wide_bisect_exact_device`` / ``wide_bisect_float_device``: the
  ``lax.while_loop`` twins of ``search.bisect_bottleneck``'s two branches.
  Unlike the fixed-round ``wide_bisect_device`` scan, the integer loop runs
  until the interval closes, so it terminates at the *true* minimal
  feasible integer — the same value the host bisection finds, whatever
  candidate schedule either side probes.
- ``nicol_optimal_device`` / ``jag_pq_opt_device`` / ``jag_m_opt_device``:
  the paper's exact 1D / P x Q jagged / m-way jagged solvers fully
  on-device.  For integer inputs the bottlenecks are bit-identical to
  ``oned.probe_bisect_optimal`` / ``jagged.jag_pq_opt`` /
  ``jagged.jag_m_opt`` (equivalence-swept in the tests), and the 1D and
  jagged-PQ *cuts* match the host greedy realization bit-for-bit: greedy
  maximal extension at any L in [L*, next realizable value) yields the
  same cut array, and both sides realize at an L in that window.
  Integer inputs should be int32 with total load < 2**30 (targets are
  ``p + L``; jax x64 is off by default).  All three batch under ``vmap``
  — the batched ``while_loop`` runs rounds until every lane converges.

All functions are pure jnp/lax: they jit, vmap, and lower under pjit.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.backend import use_pallas_default

# ---------------------------------------------------------------------------
# probes


def _advance(p: jnp.ndarray, pos: jnp.ndarray, v: jnp.ndarray,
             L: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One greedy step: furthest index e with p[e] <= p[pos] + L, >= pos.

    ``v`` is ``p[pos]``, carried by the caller's loop; returns ``(e,
    p[e])``.  ``pos``, ``v`` and ``L`` may carry batch dimensions; ``p``
    is one row.  One compare and one reduction over the row that keeps
    the (index, value) pair with the larger index: no dependent lookups,
    no gather.  On a non-decreasing ``p``, e is ``searchsorted(p, v + L,
    "right") - 1`` clipped to ``[pos, n]``, bit for bit; a float32
    stripe prefix need not be monotone, and e is then still the largest
    such index.
    """
    le = p <= (v + L)[..., None]
    axis = le.ndim - 1
    idx = jnp.where(le, jax.lax.broadcasted_iota(jnp.int32, le.shape, axis),
                    -1)
    zero = jnp.zeros((), p.dtype)

    def later(a, b):
        take_b = b[0] > a[0]
        return (jax.lax.select(take_b, b[0], a[0]),
                jax.lax.select(take_b, b[1], a[1]))

    e, w = jax.lax.reduce((idx, jnp.where(le, p, zero)), (jnp.int32(-1), zero),
                          later, (axis,))
    stuck = e < pos  # a single element > L: the step stays
    return jnp.where(stuck, pos, e), jnp.where(stuck, v, w)


def probe_device(p: jnp.ndarray, m: int, Ls: jnp.ndarray) -> jnp.ndarray:
    """Feasibility of each candidate bottleneck in ``Ls`` ((B,) bool)."""
    init = (jnp.zeros(Ls.shape, jnp.int32), jnp.broadcast_to(p[0], Ls.shape))

    def step(carry, _):
        return _advance(p, *carry, Ls), None

    (pos, _), _ = jax.lax.scan(step, init, None, length=m)
    return pos == p.shape[0] - 1


def probe_cuts_device(p: jnp.ndarray, m: int, L: jnp.ndarray) -> jnp.ndarray:
    """Cut array (m+1,) realizing bottleneck L (garbage if infeasible)."""
    def step(carry, _):
        carry = _advance(p, *carry, L)
        return carry, carry[0]

    _, cuts = jax.lax.scan(step, (jnp.int32(0), p[0]), None, length=m)
    return jnp.concatenate([jnp.zeros(1, jnp.int32), cuts])


def wide_bisect_device(feasible, lo: jnp.ndarray, hi: jnp.ndarray, *,
                       k: int = 8, rounds: int = 8,
                       dtype=None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Device twin of ``search.bisect_bottleneck``: K candidates per round.

    ``feasible(Ls)`` maps an ascending (k,) candidate vector to a (k,) bool
    mask (monotone).  Returns the final (lo, hi); hi converges to the
    optimum from above, within (hi0-lo0)/(k+1)^rounds.
    """
    dtype = dtype or jnp.result_type(lo, hi)
    fr = jnp.arange(1, k + 1, dtype=dtype) / (k + 1)

    def round_(carry, _):
        lo, hi = carry
        Ls = lo + (hi - lo) * fr
        feas = feasible(Ls)
        # new hi: smallest feasible candidate (or old hi)
        hi_new = jnp.min(jnp.where(feas, Ls, hi))
        # new lo: largest infeasible candidate (or old lo)
        lo_new = jnp.max(jnp.where(~feas, Ls, lo))
        return (jnp.minimum(lo_new, hi_new), hi_new), None

    (lo, hi), _ = jax.lax.scan(round_, (lo, hi), None, length=rounds)
    return lo, hi


@functools.partial(jax.jit, static_argnames=("m", "k", "rounds"))
def optimal_1d_device(p: jnp.ndarray, m: int, *, k: int = 8,
                      rounds: int = 8) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Optimal 1D partition by wide bisection. Returns (cuts, bottleneck).

    Exact to within (hi-lo)/(k+1)^rounds of the true optimum -- with the
    default 8 rounds of 9-way splitting that is a 4.3e7 reduction of the
    initial DirectCut gap, i.e. exact for integer loads below ~4e7 * m.
    """
    n = p.shape[0] - 1
    total = p[n]
    el_max = jnp.max(jnp.diff(p))
    lo = jnp.maximum(total / m, el_max)  # infeasible-or-optimal
    hi = total / m + el_max              # always feasible (DirectCut bound)
    _, hi = wide_bisect_device(lambda Ls: probe_device(p, m, Ls), lo, hi,
                               k=k, rounds=rounds, dtype=p.dtype)
    cuts = probe_cuts_device(p, m, hi)
    return cuts, hi


# ---------------------------------------------------------------------------
# masked per-stripe probe (variable processor counts, static shapes)


def _masked_step(p, count, L, i, pos, v, pn):
    """Greedy step ``i`` of a ``count``-interval probe from ``pos`` (``v``
    is ``p[pos]``): advance while ``i < count``; the last live interval
    (``i == count - 1``) runs to n.  Returns the new ``(pos, p[pos])``.

    ``pn`` is ``p[n]``, read once before the loop: read inside it, the
    v5e compile copies the stripe rows to another layout at every step.
    """
    nxt, w = _advance(p, pos, v, L)
    live = i < count
    nxt, w = jnp.where(live, nxt, pos), jnp.where(live, w, v)
    last = i == count - 1
    return jnp.where(last, p.shape[0] - 1, nxt), jnp.where(last, pn, w)


def _probe_cuts_masked(p: jnp.ndarray, m_max: int, count: jnp.ndarray,
                       L: jnp.ndarray, steps: jnp.ndarray) -> jnp.ndarray:
    """Cuts (m_max+1,) using only ``count`` intervals; rest collapse at n.

    The greedy runs ``steps`` (traced, ``count <= steps <= m_max``) steps,
    not m_max: the buffer starts at n, so entries past ``count`` are n
    without being visited.
    """
    n = p.shape[0] - 1
    pn = p[n]

    def step(i, carry):
        pos, v, cuts = carry
        nxt, w = _masked_step(p, count, L, i, pos, v, pn)
        return nxt, w, cuts.at[i + 1].set(nxt)

    cuts0 = jnp.full(m_max + 1, n, jnp.int32).at[0].set(0)
    _, _, cuts = jax.lax.fori_loop(jnp.int32(0), steps, step,
                                   (jnp.int32(0), p[0], cuts0))
    return cuts


def _probe_bottleneck_masked(p: jnp.ndarray, m_max: int, count: jnp.ndarray,
                             L: jnp.ndarray,
                             steps: jnp.ndarray) -> jnp.ndarray:
    """``_stripe_bottleneck(p, _probe_cuts_masked(...))`` without the cuts.

    The same float subtractions ``p[nxt] - p[pos]``, of the carried
    prefix values, folded into a running max; the m_max - count
    intervals never visited are n..n, load 0 (a float32 stripe prefix
    need not be monotone, so no interval is assumed >= 0).
    """
    worst0 = jnp.where(count < m_max, 0, -jnp.inf).astype(p.dtype)
    pn = p[-1]

    def step(i, carry):
        pos, v, worst = carry
        nxt, w = _masked_step(p, count, L, i, pos, v, pn)
        return nxt, w, jnp.maximum(worst, w - v)

    _, _, worst = jax.lax.fori_loop(jnp.int32(0), steps, step,
                                    (jnp.int32(0), p[0], worst0))
    return worst


def _stripe_bottleneck(p, cuts):
    return jnp.max(jnp.take(p, cuts[1:]) - jnp.take(p, cuts[:-1]))


def jag_m_heur_device_impl(gamma: jnp.ndarray, *, P: int, m: int, k: int = 8,
                           rounds: int = 8, gamma_dtype=None):
    """Unjitted body of :func:`jag_m_heur_device`.

    Pipelines that fuse this with other kernels under a single jit (the
    rebalancing planner's partition stage) call the body directly so the
    composed chain keeps exactly one jit boundary.

    gamma: (n1+1, n2+1) device prefix sums (e.g. from kernels/sat).
    gamma_dtype: floating dtype for the bisection accumulators (row and
    stripe prefix arrays). Defaults to gamma's own dtype when floating,
    else float32. f32 ulps exceed 1 above 2**24, so batched runs on large
    integer loads should pass ``jnp.float64`` (requires jax x64).
    Returns (row_cuts (P+1,), counts (P,), col_cuts (P, m_max+1), Lmax)
    with m_max = m - P + 1 (a stripe can never get more than that, since
    every other stripe keeps at least one processor).

    The three stages run under ``jax.named_scope``s, which name the
    ``op_name`` of their instructions in the compiled HLO: ``heur.rows``
    (the row 1D solve), ``heur.counts`` (the proportional allocation)
    and ``heur.stripes`` (the per-stripe bisections and their cuts).
    """
    if gamma_dtype is None:
        gamma_dtype = gamma.dtype if jnp.issubdtype(
            gamma.dtype, jnp.floating) else jnp.float32
    gamma_dtype = jnp.dtype(gamma_dtype)
    n2 = gamma.shape[1] - 1
    with jax.named_scope("heur.rows"):
        row_prefix = gamma[:, n2].astype(gamma_dtype)
        row_cuts, _ = optimal_1d_device(row_prefix, P, k=k, rounds=rounds)

    with jax.named_scope("heur.counts"):
        stripe_prefix = (jnp.take(gamma, row_cuts[1:], axis=0)
                         - jnp.take(gamma, row_cuts[:-1], axis=0)
                         ).astype(gamma_dtype)  # (P, n2+1)
        loads = stripe_prefix[:, n2]
        total = jnp.maximum(row_prefix[-1], 1)

        # paper's proportional allocation: ceil((m - P) * load / total), >= 1
        counts = jnp.ceil((m - P) * loads / total).astype(jnp.int32)
        counts = jnp.maximum(counts, 1)

        def give_leftover(counts, _):
            s = jnp.argmax(loads / counts)
            return counts.at[s].add(jnp.where(counts.sum() < m, 1, 0)), None

        counts, _ = jax.lax.scan(give_leftover, counts, None, length=P)

    m_max = m - P + 1
    # the greedy probes run as many steps as the fullest stripe has
    # processors (all m/P when the rows balance), not m_max
    steps = jnp.max(counts)

    def stripe_optimal(p, count):
        n = p.shape[0] - 1
        total_s = p[n]
        el = jnp.max(jnp.diff(p))
        lo = jnp.maximum(total_s / count, el)
        hi = total_s / count + el

        def feasible(Ls):
            def feas_one(L):
                return _probe_bottleneck_masked(p, m_max, count, L,
                                                steps) <= L

            return jax.vmap(feas_one)(Ls)

        _, hi_f = wide_bisect_device(feasible, lo, hi, k=k, rounds=rounds,
                                     dtype=p.dtype)
        cuts = _probe_cuts_masked(p, m_max, count, hi_f, steps)
        return cuts, _stripe_bottleneck(p, cuts)

    with jax.named_scope("heur.stripes"):
        col_cuts, bots = jax.vmap(stripe_optimal)(stripe_prefix, counts)
        return row_cuts, counts, col_cuts, jnp.max(bots)


jag_m_heur_device = jax.jit(
    jag_m_heur_device_impl,
    static_argnames=("P", "m", "k", "rounds", "gamma_dtype"))
# same contract as the impl, stated once there — only the first line differs
jag_m_heur_device.__doc__ = ("JAG-M-HEUR fully on device (jitted).\n"
                             + jag_m_heur_device_impl.__doc__
                             .split("\n", 1)[1])


# ---------------------------------------------------------------------------
# exact wide bisection (lax.while_loop — runs until the interval closes)


def _interior_candidates(lo, hi, j, k: int):
    """The k interior integer candidates ``lo + span*j // (k+1)``.

    Same schedule as the host engine's integral branch, factored to avoid
    the ``span * j`` overflow: ``span*j // (k+1)`` is computed as
    ``(span // (k+1)) * j + ((span % (k+1)) * j) // (k+1)`` (exact
    identity), so no intermediate ever exceeds ``span``.
    """
    span = hi - lo
    return lo + (span // (k + 1)) * j + ((span % (k + 1)) * j) // (k + 1)


def wide_bisect_exact_device(feasible, lo, hi, *, k: int = 15):
    """Minimal feasible integer in [lo, hi] — exact device bisection.

    ``feasible(cand)`` maps a (k,) integer candidate vector to a (k,)
    bool mask (monotone: once True, always True); ``hi`` must be
    feasible.  Each round probes the host schedule's interior candidates
    and shrinks [lo, hi] to the bracketing verdicts; the ``while_loop``
    runs until ``lo == hi``, so the result is the true optimum (the
    fixed-round ``wide_bisect_device`` scan only brackets it).  Batches
    under vmap: the batched loop iterates until every lane converges.
    """
    lo = jnp.asarray(lo)
    hi = jnp.asarray(hi)
    dtype = jnp.result_type(lo, hi)
    j = jnp.arange(1, k + 1, dtype=dtype)

    def cond(c):
        clo, chi = c
        return clo < chi

    def body(c):
        clo, chi = c
        cand = _interior_candidates(clo, chi, j, k)
        feas = feasible(cand)
        hi_new = jnp.min(jnp.where(feas, cand, chi))
        lo_new = jnp.max(jnp.where(feas, clo, cand + 1))
        return jnp.maximum(clo, lo_new), jnp.minimum(chi, hi_new)

    _, hi = jax.lax.while_loop(cond, body,
                               (lo.astype(dtype), hi.astype(dtype)))
    return hi


def _wide_bisect_exact_batch(feasible, lo, hi, *, k: int = 15):
    """Lockstep exact integer bisection over S independent intervals.

    ``lo``/``hi`` are (S,) vectors; ``feasible(cand)`` maps an (S, k)
    candidate matrix to an (S, k) bool mask.  One probe round serves all
    rows (the device twin of ``search.bisect_bottleneck_batch``) — this
    is what lets the per-stripe column solves share one probe kernel
    call per round instead of vmapping S independent loops.
    """
    lo = jnp.asarray(lo)
    hi = jnp.asarray(hi)
    dtype = jnp.result_type(lo, hi)
    j = jnp.arange(1, k + 1, dtype=dtype)

    def cond(c):
        clo, chi = c
        return jnp.any(clo < chi)

    def body(c):
        clo, chi = c
        cand = _interior_candidates(clo[:, None], chi[:, None], j[None, :], k)
        feas = feasible(cand)
        hi_new = jnp.min(jnp.where(feas, cand, chi[:, None]), axis=1)
        lo_new = jnp.max(jnp.where(feas, clo[:, None], cand + 1), axis=1)
        return jnp.maximum(clo, lo_new), jnp.minimum(chi, hi_new)

    _, hi = jax.lax.while_loop(cond, body,
                               (lo.astype(dtype), hi.astype(dtype)))
    return hi


def wide_bisect_float_device(feasible, lo, hi, *, k: int = 15,
                             rel_tol: float = 1e-9, abs_tol: float = 1e-12,
                             max_rounds: int = 128):
    """Float twin: converge ``hi`` to within the host engine's tolerance.

    Mirrors the float branch of ``search.bisect_bottleneck`` (candidates
    ``lo + (hi-lo) * j/(k+1)``, tolerance ``max(rel|hi|, abs)``); the
    relative tolerance is floored at 4 ulp of the working dtype so f32
    inputs terminate, and ``max_rounds`` backstops degenerate intervals
    where rounding stalls both endpoints.
    """
    lo = jnp.asarray(lo)
    hi = jnp.asarray(hi)
    dtype = jnp.result_type(lo, hi, jnp.float32)
    rel = max(rel_tol, 4 * float(jnp.finfo(dtype).eps))
    fr = jnp.arange(1, k + 1, dtype=dtype) / (k + 1)

    def cond(c):
        clo, chi, r = c
        open_ = chi - clo > jnp.maximum(rel * jnp.abs(chi), abs_tol)
        return open_ & (r < max_rounds)

    def body(c):
        clo, chi, r = c
        cand = clo + (chi - clo) * fr
        feas = feasible(cand)
        hi_new = jnp.min(jnp.where(feas, cand, chi))
        lo_new = jnp.max(jnp.where(feas, clo, cand))
        return (jnp.maximum(clo, lo_new), jnp.minimum(chi, hi_new), r + 1)

    _, hi, _ = jax.lax.while_loop(
        cond, body, (lo.astype(dtype), hi.astype(dtype), jnp.int32(0)))
    return hi


# ---------------------------------------------------------------------------
# exact greedy realization (host ``oned.probe`` semantics, bit-for-bit)


def _greedy_cuts_exact(p: jnp.ndarray, m: int, L: jnp.ndarray) -> jnp.ndarray:
    """Greedy cuts at a *feasible* L, mirroring ``oned.probe`` exactly.

    Intervals extend maximally; once the remainder fits in one interval
    the chain collapses — cuts stay at the current position and the final
    cut takes the tail — exactly the host probe's early-return pattern,
    so the realized cut arrays (not just bottlenecks) are bit-identical
    to ``search.realize`` output for integer loads.
    """
    n = p.shape[0] - 1
    pn = p[n]  # read before the loop, as in ``_masked_step``

    def step(carry, _):
        pos, v = carry
        rem_fits = pn - v <= L
        nxt, w = _advance(p, pos, v, L)
        out = jnp.where(rem_fits, pos, nxt), jnp.where(rem_fits, v, w)
        return out, out[0]

    _, cuts = jax.lax.scan(step, (jnp.int32(0), p[0]), None, length=m)
    cuts = jnp.concatenate([jnp.zeros(1, jnp.int32), cuts])
    return cuts.at[m].set(n)


def _greedy_cuts_speeds(p: jnp.ndarray, L: jnp.ndarray,
                        speeds: jnp.ndarray) -> jnp.ndarray:
    """Capacity-aware greedy cuts: position k packs at most L * speeds[k].

    Mirrors the hetero branch of ``oned.probe``: dead (speed 0) positions
    keep the current cut (an empty interval), no remainder collapse.  At
    an infeasible L the final cut simply falls short of n — callers check
    ``cuts[-1] == n`` for feasibility.
    """
    n = p.shape[0] - 1

    def step(pos, sp_k):
        target = jnp.take(p, pos) + L * sp_k
        nxt = jnp.searchsorted(p, target, side="right") - 1
        nxt = jnp.clip(nxt, pos, n)
        out = jnp.where(sp_k > 0, nxt, pos)
        return out, out

    _, cuts = jax.lax.scan(step, jnp.int32(0), speeds)
    return jnp.concatenate([jnp.zeros(1, jnp.int32), cuts])


def _cut_loads(p: jnp.ndarray, cuts: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p, cuts[1:]) - jnp.take(p, cuts[:-1])


def _exact_1d_bounds_int(p: jnp.ndarray, m: int):
    """Integer [lo, hi] bracketing the 1D optimum: lo any lower bound,
    hi a feasible integer (floor of the DirectCut bound, +1 for the
    integer-division slack)."""
    n = p.shape[0] - 1
    total = p[n]
    maxel = jnp.max(jnp.diff(p))
    lo = jnp.maximum((total + m - 1) // m, maxel)
    hi = total // m + maxel + 1
    return lo, jnp.maximum(hi, lo)


# ---------------------------------------------------------------------------
# exact 1D (NicolPlus-quality bottleneck, device-native)


def nicol_optimal_device_impl(p: jnp.ndarray, m: int,
                              speeds: jnp.ndarray | None = None, *,
                              k: int = 15):
    """Unjitted body of :func:`nicol_optimal_device`.

    Returns ``(cuts (m+1,) int32, bottleneck scalar)``.  Integer ``p``
    takes the exact integer bisection (bottleneck and cuts bit-identical
    to ``oned.probe_bisect_optimal`` / ``oned.nicol_optimal``); float
    ``p`` converges to the host float tolerance.  ``speeds`` switches to
    the relative-load objective (always float; pass the vector already
    normalized by ``search.normalize_speeds`` — uniform vectors should
    be dropped to ``None`` host-side to keep the homogeneous path
    bit-identical).  On a TPU the homogeneous feasibility probe is the
    ``kernels.probe`` Pallas kernel, elsewhere the jnp scan
    (:mod:`repro.backend`).
    """
    n = p.shape[0] - 1
    if speeds is not None:
        sp = jnp.asarray(speeds)
        ft = jnp.result_type(sp.dtype, jnp.float32)
        pf = p.astype(ft)
        total = pf[n] - pf[0]
        maxel = jnp.max(jnp.diff(pf))
        smax = jnp.max(sp)
        lo = jnp.maximum(total / jnp.sum(sp), maxel / smax)
        hi = (total / smax) * (1 + 1e-9) + 1e-12

        def feasible(cand):
            def one(L):
                return _greedy_cuts_speeds(p, L, sp)[-1] == n
            return jax.vmap(one)(cand)

        L = wide_bisect_float_device(feasible, lo, hi, k=k)
        cuts = _greedy_cuts_speeds(p, L, sp)
        loads = _cut_loads(p, cuts).astype(ft)
        rel = jnp.where(loads > 0, loads / sp, 0.0)
        return cuts, jnp.max(rel)

    integral = jnp.issubdtype(p.dtype, jnp.integer)
    if integral:
        lo, hi = _exact_1d_bounds_int(p, m)
    else:
        total = p[n]
        maxel = jnp.max(jnp.diff(p))
        lo = jnp.maximum(total / m, maxel)
        hi = total / m + maxel

    if use_pallas_default():
        from repro.kernels.probe import ops as probe_ops

        def feasible(cand):
            cnt = probe_ops.probe_counts_impl(
                p[None, :], cand[None, :].astype(p.dtype), m)
            return cnt[0] <= m
    else:
        def feasible(cand):
            return probe_device(p, m, cand)

    if integral:
        L = wide_bisect_exact_device(feasible, lo, hi, k=k)
    else:
        L = wide_bisect_float_device(feasible, lo, hi, k=k)
    cuts = _greedy_cuts_exact(p, m, L)
    return cuts, jnp.max(_cut_loads(p, cuts))


nicol_optimal_device = jax.jit(nicol_optimal_device_impl,
                               static_argnames=("m", "k"))
nicol_optimal_device.__doc__ = (
    "Exact 1D partition fully on device (jitted).\n"
    + nicol_optimal_device_impl.__doc__.split("\n", 1)[1])


# ---------------------------------------------------------------------------
# exact P x Q jagged (JAG-PQ-OPT, device-native)


def _bs_steps(n1: int) -> int:
    """Static binary-search step count resolving an index in [0, n1+1)."""
    return max(1, math.ceil(math.log2(n1 + 2)))


def _stripe_row(gamma: jnp.ndarray, b, e) -> jnp.ndarray:
    """Column prefix array of stripe [b, e): (n2+1,) non-decreasing."""
    return jnp.take(gamma, e, axis=0) - jnp.take(gamma, b, axis=0)


def _onepass_step(q: jnp.ndarray, pos, v, target):
    """One greedy step over a non-decreasing row ``q``, without a search.

    ``v`` is ``q[pos]``.  The largest index whose value is <= ``target``
    is the count of such entries minus one (q is sorted), clipped to
    ``[pos, len(q) - 1]`` as ``searchsorted(q, target, "right") - 1``
    was; the value there is the largest entry <= ``target``, or ``v``
    when the step sticks.  One compare and two reductions over the row:
    no dependent lookups, no gather.
    """
    le = q <= target
    nxt = jnp.clip(jnp.sum(le, dtype=jnp.int32) - 1, pos, q.shape[0] - 1)
    return nxt, jnp.max(jnp.where(le, q, v))


def _stripe_fits(gamma: jnp.ndarray, b, e, L, Q: int,
                 sp_slice: jnp.ndarray | None = None):
    """Does stripe [b, e) pack into <= Q column intervals of load <= L?

    Greedy maximal extension over the stripe's column prefix (exact for
    the monotone objective), Q :func:`_onepass_step` steps carrying the
    position and the prefix value there.  With ``sp_slice`` ((Q,)
    speeds) position q packs at most ``L * sp_slice[q]`` and dead
    positions are skipped — the device twin of
    ``oned.probe_count(speeds=...) <= Q``.
    """
    q = _stripe_row(gamma, b, e)
    n2 = q.shape[0] - 1
    init = (jnp.int32(0), q[0])
    if sp_slice is None:
        def step(carry, _):
            pos, v = carry
            return _onepass_step(q, pos, v, v + L), None

        (pos, _), _ = jax.lax.scan(step, init, None, length=Q)
    else:
        def step(carry, sp_k):
            pos, v = carry
            # a dead position (sp_k == 0) targets v itself: w is v
            nxt, w = _onepass_step(q, pos, v, v + L * sp_k)
            return (jnp.where(sp_k > 0, nxt, pos), w), None

        (pos, _), _ = jax.lax.scan(step, init, sp_slice)
    return pos == n2


def _largest_stripe_end(gamma: jnp.ndarray, b, L, Q: int,
                        sp_slice: jnp.ndarray | None = None):
    """Largest e in [b, n1] whose stripe [b, e) fits (binary search).

    Fitting is monotone non-increasing in e (pointwise load domination),
    the same assumption the host ``_RowProbe`` bisects under.  The empty
    stripe always fits, so the invariant end is ``b``; the step count is
    static (worst case over the whole row range).
    """
    n1 = gamma.shape[0] - 1

    def bs(carry, _):
        glo, ghi = carry
        mid = (glo + ghi) // 2
        ok = _stripe_fits(gamma, b, mid, L, Q, sp_slice)
        return (jnp.where(ok, mid, glo), jnp.where(ok, ghi, mid)), None

    init = (jnp.asarray(b, jnp.int32), jnp.full_like(jnp.asarray(b,
                                                     jnp.int32), n1 + 1))
    (glo, _), _ = jax.lax.scan(bs, init, None, length=_bs_steps(n1))
    return glo


def _row_scan(gamma: jnp.ndarray, L, P: int, Q: int,
              sp2: jnp.ndarray | None = None, *, realize: bool = False):
    """P greedy stripe steps at bottleneck L.

    ``realize=False``: feasibility — final position == n1.
    ``realize=True``: the host ``_RowProbe.cuts`` realization — once the
    remainder fits the chain collapses (cuts stay at b, final cut n1),
    bit-identical to the host row cuts at the same L.  ``sp2`` is the
    (P, Q) per-stripe speed schedule for the capacity-aware form (which,
    like the host hetero realizer, has no collapse shortcut).
    """
    n1 = gamma.shape[0] - 1

    if sp2 is None:
        def step(b, _):
            e = _largest_stripe_end(gamma, b, L, Q)
            if realize:
                rem = _stripe_fits(gamma, b, n1, L, Q)
                e = jnp.where(rem, b, e)
            out = jnp.maximum(e, b)
            return out, out

        b, cuts = jax.lax.scan(step, jnp.int32(0), None, length=P)
    else:
        def step(b, sp_s):
            e = _largest_stripe_end(gamma, b, L, Q, sp_s)
            out = jnp.maximum(e, b)
            return out, out

        b, cuts = jax.lax.scan(step, jnp.int32(0), sp2)
    if not realize:
        return b == n1
    cuts = jnp.concatenate([jnp.zeros(1, jnp.int32), cuts])
    if sp2 is None:
        cuts = cuts.at[P].set(n1)
    return cuts


def _collapse_cuts(n2: int, m: int) -> jnp.ndarray:
    """The host probe's zero-load pattern: [0, ..., 0, n2]."""
    return jnp.zeros(m + 1, jnp.int32).at[m].set(n2)


def jag_pq_opt_device_impl(gamma: jnp.ndarray, *, P: int, Q: int,
                           speeds: jnp.ndarray | None = None, k: int = 15):
    """Unjitted body of :func:`jag_pq_opt_device` (JAG-PQ-OPT on device).

    gamma: (n1+1, n2+1) device prefix sums, 'hor' orientation (transpose
    the Gamma for 'ver'; the registry wrapper runs both and keeps the
    better, like the host ``orient='best'``).

    Returns ``(row_cuts (P+1,), counts (P,) == Q, col_cuts (P, Q+1),
    Lmax)``.  Integer gammas take the exact integer bisection: bottleneck
    *and* cuts are bit-identical to ``jagged.jag_pq_opt(orient='hor')``
    — the row probe is the same greedy maximal stripe extension, and the
    per-stripe column solves converge to each stripe's own minimal
    feasible integer before realizing with the host probe's collapse
    semantics.  ``speeds`` ((P*Q,) pre-normalized) switches everything to
    relative load (always float; bottleneck matches the host hetero
    solver to its 1e-9 tolerance).  On a TPU the per-stripe column
    feasibility probes are the ``kernels.probe`` Pallas kernel
    (:mod:`repro.backend`) — with a Pallas SAT stage in front this is the
    fused SAT -> probe -> cut path, no host round-trip anywhere.

    Without ``speeds`` the four stages run under ``jax.named_scope``s,
    which name the ``op_name`` of their instructions in the compiled HLO:
    ``exact.row_bisect`` (the bisection over ``_row_scan``),
    ``exact.row_realize`` (the row cuts and stripe prefixes),
    ``exact.col_bisect`` (the per-stripe bisections and their probes)
    and ``exact.col_realize`` (the column cuts and their loads).
    """
    n1 = gamma.shape[0] - 1
    n2 = gamma.shape[1] - 1
    m = P * Q
    total = gamma[n1, n2]
    integral = jnp.issubdtype(gamma.dtype, jnp.integer) and speeds is None
    maxrow = jnp.max(jnp.diff(gamma[:, n2]))
    # the per-stripe column greedy's "element" is a column sum *within the
    # stripe*, bounded by the full-column load — not by the max cell
    maxcol = jnp.max(jnp.diff(gamma[n1, :]))

    if speeds is not None:
        sp = jnp.asarray(speeds)
        sp2 = sp.reshape(P, Q)
        ft = jnp.result_type(sp.dtype, jnp.float32)
        smin_pos = jnp.min(jnp.where(sp > 0, sp, jnp.inf))
        lo = total.astype(ft) / jnp.sum(sp)
        hi = (total.astype(ft) / smin_pos) * (1 + 1e-9) + 1e-12
        hi = jnp.maximum(hi, lo)

        def feasible(cand):
            return jax.vmap(
                lambda L: _row_scan(gamma, L, P, Q, sp2))(cand)

        L = wide_bisect_float_device(feasible, lo, hi, k=k)
        row_cuts = _row_scan(gamma, L, P, Q, sp2, realize=True)
        sm = (jnp.take(gamma, row_cuts[1:], axis=0)
              - jnp.take(gamma, row_cuts[:-1], axis=0))  # (P, n2+1)

        def stripe_solve(p_s, sp_s):
            cuts, bott = nicol_optimal_device_impl(p_s, Q, sp_s, k=k)
            zero = p_s[n2] - p_s[0] <= 0
            cuts = jnp.where(zero, _collapse_cuts(n2, Q), cuts)
            return cuts, jnp.where(zero, jnp.asarray(0, bott.dtype), bott)

        col_cuts, bots = jax.vmap(stripe_solve)(sm, sp2)
        counts = jnp.full((P,), Q, jnp.int32)
        return row_cuts, counts, col_cuts, jnp.max(bots)

    with jax.named_scope("exact.row_bisect"):
        if integral:
            lo = (total + m - 1) // m
            hi = total // m + maxrow // Q + maxcol + 2
            hi = jnp.maximum(hi, lo)
        else:
            lo = total / m
            hi = (total / m + maxrow / Q + maxcol) * (1 + 1e-9) + 1e-12
            hi = jnp.maximum(hi, lo)

        def feasible(cand):
            return jax.vmap(lambda L: _row_scan(gamma, L, P, Q))(cand)

        if integral:
            L = wide_bisect_exact_device(feasible, lo, hi, k=k)
        else:
            L = wide_bisect_float_device(feasible, lo, hi, k=k)
    with jax.named_scope("exact.row_realize"):
        row_cuts = _row_scan(gamma, L, P, Q, realize=True)
        sm = (jnp.take(gamma, row_cuts[1:], axis=0)
              - jnp.take(gamma, row_cuts[:-1], axis=0))  # (P, n2+1)

    # per-stripe exact column solves, lockstep across stripes: one probe
    # round (optionally one Pallas kernel call) serves every open stripe.
    with jax.named_scope("exact.col_bisect"):
        los, his = jax.vmap(
            lambda p_s: _exact_1d_bounds_int(p_s, Q) if integral
            else (jnp.maximum(p_s[n2] / Q, jnp.max(jnp.diff(p_s))),
                  p_s[n2] / Q + jnp.max(jnp.diff(p_s))))(sm)

        if use_pallas_default():
            from repro.kernels.probe import ops as probe_ops

            def sfeasible(cand):
                cnt = probe_ops.probe_counts_impl(sm, cand.astype(sm.dtype),
                                                  Q)
                return cnt <= Q
        else:
            def sfeasible(cand):
                return jax.vmap(lambda p_s, c_s: probe_device(p_s, Q, c_s))(
                    sm, cand)

        if integral:
            Ls = _wide_bisect_exact_batch(sfeasible, los, his, k=k)
        else:
            # float columns: vmapped scalar float bisections (rarely hot)
            Ls = jax.vmap(lambda p_s, l_s, h_s: wide_bisect_float_device(
                lambda c: probe_device(p_s, Q, c), l_s, h_s, k=k))(
                    sm, los, his)
    with jax.named_scope("exact.col_realize"):
        col_cuts = jax.vmap(
            lambda p_s, L_s: _greedy_cuts_exact(p_s, Q, L_s))(sm, Ls)
        bots = jax.vmap(_cut_loads)(sm, col_cuts)
        counts = jnp.full((P,), Q, jnp.int32)
        return row_cuts, counts, col_cuts, jnp.max(bots)


jag_pq_opt_device = jax.jit(jag_pq_opt_device_impl,
                            static_argnames=("P", "Q", "k"))
jag_pq_opt_device.__doc__ = ("JAG-PQ-OPT fully on device (jitted).\n"
                             + jag_pq_opt_device_impl.__doc__
                             .split("\n", 1)[1])


# ---------------------------------------------------------------------------
# exact m-way jagged (JAG-M-OPT, device-native; small instances)


def _stripe_count_leq(gamma: jnp.ndarray, b, e, L, x, m: int):
    """Does stripe [b, e) pack into <= x column intervals at L?  The
    greedy runs a static m steps with steps past x masked off."""
    q = _stripe_row(gamma, b, e)
    n2 = q.shape[0] - 1

    def step(pos, i):
        target = jnp.take(q, pos) + L
        nxt = jnp.searchsorted(q, target, side="right") - 1
        nxt = jnp.clip(nxt, pos, n2)
        return jnp.where(i < x, nxt, pos), None

    pos, _ = jax.lax.scan(step, jnp.int32(0),
                          jnp.arange(m, dtype=jnp.int32))
    return pos == n2


def _jump(gamma: jnp.ndarray, b, L, x, m: int):
    """Largest e with stripe [b, e) packing into <= x intervals at L."""
    n1 = gamma.shape[0] - 1

    def bs(carry, _):
        glo, ghi = carry
        mid = (glo + ghi) // 2
        ok = _stripe_count_leq(gamma, b, mid, L, x, m)
        return (jnp.where(ok, mid, glo), jnp.where(ok, ghi, mid)), None

    (glo, _), _ = jax.lax.scan(bs, (jnp.asarray(b, jnp.int32),
                                    jnp.int32(n1 + 1)), None,
                               length=_bs_steps(n1))
    return glo


def _jag_m_reach(gamma: jnp.ndarray, L, m: int):
    """Reach DP: r[q] = furthest row coverable by q processors at L.

    ``r[q] = max over x in [1, q] of jump_x(r[q - x])`` — jump is
    monotone in its start, so the DP is exact.  Also records the argmax
    ``x`` per q for the realization backtrack.  Feasible iff r[m] == n1.
    """
    r0 = jnp.zeros(m + 1, jnp.int32)
    xs0 = jnp.zeros(m + 1, jnp.int32)

    def per_q(carry, q):
        r, xs = carry

        def per_x(inner, x):
            best_e, best_x = inner
            e = _jump(gamma, jnp.take(r, q - x), L, x, m)
            ok = (x <= q) & (e > best_e)
            return (jnp.where(ok, e, best_e), jnp.where(ok, x, best_x)), None

        (best_e, best_x), _ = jax.lax.scan(
            per_x, (jnp.int32(0), jnp.int32(1)),
            jnp.arange(1, m + 1, dtype=jnp.int32))
        r = r.at[q].set(best_e)
        xs = xs.at[q].set(best_x)
        return (r, xs), None

    (r, xs), _ = jax.lax.scan(per_q, (r0, xs0),
                              jnp.arange(1, m + 1, dtype=jnp.int32))
    return r, xs


def jag_m_opt_device_impl(gamma: jnp.ndarray, *, m: int, k: int = 7):
    """Unjitted body of :func:`jag_m_opt_device` (JAG-M-OPT on device).

    Exact m-way jagged: bisect the bottleneck with the reach DP as the
    feasibility probe, then backtrack the recorded stripe choices and
    realize per-stripe column cuts greedily at L*.  Integer gammas give
    bottlenecks bit-identical to ``jagged.jag_m_opt(orient='hor')`` (the
    minimal feasible integer is solver-independent); realized stripe
    structure may differ among equally-optimal decompositions.  Like the
    host DP this is for small instances — the DP is O(m^2 log n1) probe
    steps per candidate.

    Returns ``(row_cuts (m+1,), counts (m,), col_cuts (m, m+1),
    n_stripes, Lmax)`` — stripe arrays padded to m with empty stripes.
    """
    n1 = gamma.shape[0] - 1
    n2 = gamma.shape[1] - 1
    total = gamma[n1, n2]
    cells = (gamma[1:, 1:] - gamma[:-1, 1:] - gamma[1:, :-1]
             + gamma[:-1, :-1])
    maxel = jnp.max(cells)
    colmax = jnp.max(jnp.diff(gamma[n1, :]))
    integral = jnp.issubdtype(gamma.dtype, jnp.integer)

    def feasible_one(L):
        r, _ = _jag_m_reach(gamma, L, m)
        return r[m] == n1

    if integral:
        lo = jnp.maximum((total + m - 1) // m, maxel)
        hi = jnp.maximum(total // m + colmax + 1, lo)
        L = wide_bisect_exact_device(jax.vmap(feasible_one), lo, hi, k=k)
    else:
        lo = jnp.maximum(total / m, maxel)
        hi = jnp.maximum((total / m + colmax) * (1 + 1e-9) + 1e-12, lo)
        L = wide_bisect_float_device(jax.vmap(feasible_one), lo, hi, k=k)

    r, xs = _jag_m_reach(gamma, L, m)

    # backtrack: from q = m walk the recorded x choices; emits stripes
    # last-first, padded with x = 0 once q hits 0.
    def bt(q, _):
        x = jnp.where(q > 0, jnp.take(xs, q), 0)
        e = jnp.take(r, q)
        b = jnp.take(r, q - x)
        return q - x, (b, jnp.where(x > 0, e, b), x)

    _, (bs_rev, es_rev, xr_rev) = jax.lax.scan(bt, jnp.int32(m), None,
                                               length=m)
    bs_f, es_f, xr_f = bs_rev[::-1], es_rev[::-1], xr_rev[::-1]
    live = xr_f > 0
    n_stripes = jnp.sum(live.astype(jnp.int32))
    # compact live stripes to the front (stable order preserved)
    pos = jnp.where(live, jnp.cumsum(live.astype(jnp.int32)) - 1, m)
    # pad slots default to the empty stripe [n1, n1) so they carry no load
    starts = jnp.full(m + 1, n1, jnp.int32).at[pos].set(bs_f, mode="drop")
    ends = jnp.full(m + 1, n1, jnp.int32).at[pos].set(es_f, mode="drop")
    counts = jnp.zeros(m + 1, jnp.int32).at[pos].set(xr_f, mode="drop")
    starts, ends, counts = starts[:m], ends[:m], counts[:m]
    row_cuts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                jnp.where(jnp.arange(m) < n_stripes,
                                          ends, n1).astype(jnp.int32)])

    steps = jnp.max(counts)

    def stripe_cuts(b, e, x):
        p_s = _stripe_row(gamma, b, e)
        cuts = _probe_cuts_masked(p_s, m, x, L, steps)
        cuts = jnp.where(x > 0, cuts, _collapse_cuts(n2, m))
        bott = jnp.max(_cut_loads(p_s, cuts))
        return cuts, jnp.where(x > 0, bott, jnp.zeros_like(bott))

    col_cuts, bots = jax.vmap(stripe_cuts)(starts, ends, counts)
    return row_cuts, counts, col_cuts, n_stripes, jnp.max(bots)


jag_m_opt_device = jax.jit(jag_m_opt_device_impl,
                           static_argnames=("m", "k"))
jag_m_opt_device.__doc__ = ("JAG-M-OPT fully on device (jitted).\n"
                            + jag_m_opt_device_impl.__doc__
                            .split("\n", 1)[1])
