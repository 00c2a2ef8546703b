"""Arithmetic shared by the comparisons that decide ``correct``
(``checks/<name>.py``, named by the mix's ``check``).

A check module holds three functions:

- ``compare(records, frame, ref, cfg, traffic, seed)``: ``records`` are
  ``(t, plan)`` for every frame the window planned, ``t`` its index in
  the pool, and ``frame(t)`` returns that frame on the host.  Returns
  ``({number: value}, failed)``; a run is correct when every number is
  within its limit (``traffic["limits"]``);
- ``lmax(ref, frame, plan)``: the exact bottleneck of a plan;
- ``control(frame, ref, cfg)``: the plain reference one precision step
  below the configuration's, as a plan in the form the entry returns.

The plain reference (``reference/<name>.py``) is the only solver used.
"""
from __future__ import annotations

from collections import defaultdict

import numpy as np


def sample(records, k: int, seed: int) -> list[int]:
    """Up to ``k`` distinct planned frames, drawn from the seed."""
    ts = sorted({t for t, _ in records})
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    return sorted(rng.choice(ts, size=min(k, len(ts)), replace=False)
                  .tolist())


def by_frame(records) -> dict:
    out = defaultdict(list)
    for t, plan in records:
        out[t].append(plan)
    return out


def plan_key(plan) -> bytes:
    return b"|".join(np.asarray(plan[k]).tobytes()
                     for k in ("row_cuts", "counts", "col_cuts"))


def rounded(g, dtype) -> np.ndarray:
    """An int64 table stored in ``dtype`` and read back as integers."""
    return np.asarray(g).astype(dtype).astype(np.float64).round() \
        .astype(np.int64)


def valid_jagged(plan, shape, m: int) -> bool:
    """Row cuts span [0, n1] monotonically, every stripe's live column
    cuts span [0, n2] monotonically, and there are m rectangles."""
    n1, n2 = shape
    rc = np.asarray(plan["row_cuts"])
    ct = np.asarray(plan["counts"])
    cc = np.asarray(plan["col_cuts"])
    if rc.shape != (ct.size + 1,) or rc[0] != 0 or rc[-1] != n1 \
            or (np.diff(rc) < 0).any() or (ct < 1).any() \
            or int(ct.sum()) != m or cc.shape[0] != ct.size \
            or cc.shape[1] < int(ct.max()) + 1:
        return False
    for s in range(ct.size):
        c = cc[s, :int(ct[s]) + 1]
        if c[0] != 0 or c[-1] != n2 or (np.diff(c) < 0).any():
            return False
    return True


def imbalance_pct(records, frame, m: int, lmax_of) -> float:
    """100 * (sum Lmax / sum (total / m) - 1) over every plan; the Lmax
    of a plan repeated on its frame is computed once."""
    lmax_sum, ideal_sum = 0, 0.0
    for t, plans in by_frame(records).items():
        f = frame(t)
        total = int(f.sum(dtype=np.int64))
        seen = {}
        for plan in plans:
            key = plan_key(plan)
            if key not in seen:
                seen[key] = lmax_of(f, plan)
            lmax_sum += seen[key]
            ideal_sum += total / m
    return 100.0 * (lmax_sum / ideal_sum - 1.0)
