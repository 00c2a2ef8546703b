"""Plain reference for 2D jagged partitioning (numpy, exact integers).

Straightforward implementations of the paper's two jagged solvers on an
int64 prefix table, written from the paper's definitions (Saule, Bas,
Catalyurek, arXiv:1104.2566, Sections 2-3) and the realization rules
the planner documents.  Nothing here imports the program under test.

- ``jag_m_heur``: JAG-M-HEUR.  Optimal 1D cut of the row projection into
  P stripes, processors given to stripes in proportion to their load
  (``ceil((m - P) * load / total)``, at least one, leftovers one at a time
  to the stripe with the highest load per processor), then an optimal 1D
  cut of each stripe's columns.  Bottlenecks are the least feasible
  integers, found by bisection.
- ``jag_pq_opt``: exact P x Q jagged (JAG-PQ-OPT, horizontal stripes).
  The least integer bottleneck at which P greedy maximal stripes, each
  packing into Q greedy column intervals, cover every row; rows realized
  greedily with the remainder collapse, then every stripe's columns cut
  at that stripe's own least feasible bottleneck.

All sums are int64, so the answers are exact for any int32 frame.
"""
from __future__ import annotations

import numpy as np

__all__ = ["gamma", "stripe_loads", "jag_m_heur", "jag_pq_opt", "plan_lmax"]


def gamma(frame: np.ndarray) -> np.ndarray:
    """Exclusive int64 prefix table (n1+1, n2+1) of a 2D load frame."""
    a = np.asarray(frame, dtype=np.int64)
    g = np.zeros((a.shape[0] + 1, a.shape[1] + 1), np.int64)
    np.cumsum(np.cumsum(a, axis=0), axis=1, out=g[1:, 1:])
    return g


# ---------------------------------------------------------------------------
# 1D building blocks on a non-decreasing prefix array p (length n+1)


def _advance(p: np.ndarray, pos: int, L: int) -> int:
    """Furthest e >= pos with p[e] - p[pos] <= L."""
    e = int(np.searchsorted(p, p[pos] + L, side="right")) - 1
    return max(min(e, p.size - 1), pos)


def _greedy_count_fits(p: np.ndarray, L: int, parts: int) -> bool:
    """Do ``parts`` greedy maximal intervals of load <= L cover p?"""
    pos, n = 0, p.size - 1
    for _ in range(parts):
        pos = _advance(p, pos, L)
        if pos == n:
            return True
    return pos == n


def _least_feasible(fits, lo: int, hi: int) -> int:
    """Least integer L in [lo, hi] with fits(L); ``hi`` must fit."""
    while lo < hi:
        mid = (lo + hi) // 2
        if fits(mid):
            hi = mid
        else:
            lo = mid + 1
    return hi


def _opt_1d_bottleneck(p: np.ndarray, parts: int) -> int:
    n = p.size - 1
    total = int(p[n] - p[0])
    maxel = int(np.diff(p).max(initial=0))
    lo = max(-(-total // parts), maxel)
    hi = max(total // parts + maxel + 1, lo)
    return _least_feasible(lambda L: _greedy_count_fits(p, L, parts), lo, hi)


# ---------------------------------------------------------------------------
# JAG-M-HEUR


def jag_m_heur(g: np.ndarray, *, P: int, m: int) -> dict:
    """JAG-M-HEUR on prefix table ``g``.  Returns row_cuts (P+1,),
    counts (P,), col_cuts (list of per-stripe cut arrays) and lmax."""
    n1, n2 = g.shape[0] - 1, g.shape[1] - 1
    rows = g[:, n2]
    B = _opt_1d_bottleneck(rows, P)
    row_cuts = [0]
    for _ in range(P):
        row_cuts.append(_advance(rows, row_cuts[-1], B))
    row_cuts = np.asarray(row_cuts, np.int64)
    if row_cuts[-1] != n1:
        raise AssertionError("row cut does not reach the last row")
    stripes = g[row_cuts[1:]] - g[row_cuts[:-1]]          # (P, n2+1)
    loads = stripes[:, n2]
    total = max(int(rows[n1]), 1)
    counts = np.maximum(-(-((m - P) * loads) // total), 1)
    for _ in range(P):
        if counts.sum() < m:
            counts[int(np.argmax(loads / counts))] += 1
    col_cuts, lmax = [], 0
    for s in range(P):
        p, c = stripes[s], int(counts[s])
        Bs = _opt_1d_bottleneck(p, c)
        cuts = [0]
        for i in range(c):
            cuts.append(n2 if i == c - 1 else _advance(p, cuts[-1], Bs))
        cuts = np.asarray(cuts, np.int64)
        col_cuts.append(cuts)
        lmax = max(lmax, int(np.diff(p[cuts]).max()))
    return {"row_cuts": row_cuts, "counts": counts.astype(np.int64),
            "col_cuts": col_cuts, "lmax": lmax}


# ---------------------------------------------------------------------------
# JAG-PQ-OPT


def _stripe_fits(g, b: int, e: int, L: int, Q: int) -> bool:
    return _greedy_count_fits(g[e] - g[b], L, Q)


def _largest_stripe_end(g, b: int, L: int, Q: int) -> int:
    """Largest e in [b, n1] whose stripe [b, e) packs into Q intervals."""
    lo, hi = b, g.shape[0]            # stripe [b, lo) fits; [b, hi) does not
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if _stripe_fits(g, b, mid, L, Q):
            lo = mid
        else:
            hi = mid
    return lo


def _rows_fit(g, L: int, P: int, Q: int) -> bool:
    b, n1 = 0, g.shape[0] - 1
    for _ in range(P):
        b = _largest_stripe_end(g, b, L, Q)
        if b == n1:
            return True
    return b == n1


def jag_pq_opt(g: np.ndarray, *, P: int, Q: int) -> dict:
    """Exact P x Q jagged partition (horizontal stripes) of ``g``.
    Returns row_cuts (P+1,), col_cuts (P, Q+1) and lmax."""
    n1, n2 = g.shape[0] - 1, g.shape[1] - 1
    m = P * Q
    total = int(g[n1, n2])
    maxrow = int(np.diff(g[:, n2]).max())
    maxcol = int(np.diff(g[n1, :]).max())
    lo = -(-total // m)
    hi = max(total // m + maxrow // Q + maxcol + 2, lo)
    L = _least_feasible(lambda x: _rows_fit(g, x, P, Q), lo, hi)
    row_cuts, b = [0], 0
    for _ in range(P):
        if _stripe_fits(g, b, n1, L, Q):          # the rest fits: collapse
            e = b
        else:
            e = _largest_stripe_end(g, b, L, Q)
        b = max(e, b)
        row_cuts.append(b)
    row_cuts[-1] = n1
    row_cuts = np.asarray(row_cuts, np.int64)
    col_cuts, lmax = np.zeros((P, Q + 1), np.int64), 0
    for s in range(P):
        p = g[row_cuts[s + 1]] - g[row_cuts[s]]
        Ls = _opt_1d_bottleneck(p, Q)
        pos, cuts = 0, [0]
        for _ in range(Q):
            if p[n2] - p[pos] > Ls:
                pos = _advance(p, pos, Ls)
            cuts.append(pos)
        cuts[-1] = n2
        col_cuts[s] = cuts
        lmax = max(lmax, int(np.diff(p[col_cuts[s]]).max()))
    return {"row_cuts": row_cuts, "col_cuts": col_cuts, "lmax": lmax}


# ---------------------------------------------------------------------------
# exact loads of any jagged plan


def stripe_loads(frame: np.ndarray, row_cuts, counts, col_cuts) -> list:
    """Per-stripe int64 interval loads of a jagged plan, straight from the
    frame (no prefix table): stripe rows summed, then column intervals."""
    a = np.asarray(frame)
    out = []
    for s in range(len(counts)):
        r0, r1 = int(row_cuts[s]), int(row_cuts[s + 1])
        cols = a[r0:r1].sum(axis=0, dtype=np.int64)
        c = np.concatenate([[0], np.cumsum(cols)])
        cc = np.clip(np.asarray(col_cuts[s][:int(counts[s]) + 1], np.int64),
                     0, a.shape[1])
        out.append(np.diff(c[cc]))
    return out


def plan_lmax(frame: np.ndarray, row_cuts, counts, col_cuts) -> int:
    """Exact bottleneck of a jagged plan on ``frame``."""
    return int(max(x.max(initial=0) for x in
                   stripe_loads(frame, row_cuts, counts, col_cuts)))
