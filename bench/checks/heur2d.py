"""JAG-M-HEUR (float32 Gamma stated): the comparison that decides
``correct``, the exact Lmax of a plan, and the control.

Every plan of the sampled frames has to be a valid cover with m
rectangles (``invalid_plans``), and its exact Lmax has to lie within
``lmax_gap`` (relative) of the reference heuristic's, computed in exact
integers.  The control is that reference on a Gamma stored in bfloat16,
one precision step below the configuration's float32.
"""
import ml_dtypes
import numpy as np

from benchlib import checks

STEP_BELOW = ml_dtypes.bfloat16


def lmax(ref, frame, plan) -> int:
    return ref.plan_lmax(frame, plan["row_cuts"], plan["counts"],
                         plan["col_cuts"])


def compare(records, frame, ref, cfg, traffic, seed) -> tuple[dict, int]:
    P, m = cfg["P"], cfg["m"]
    lim = traffic["limits"]
    by = checks.by_frame(records)
    worst, invalid, failed = -np.inf, 0, 0
    for t in checks.sample(records, traffic["sample"], seed):
        f = frame(t)
        want = ref.jag_m_heur(ref.gamma(f), P=P, m=m)["lmax"]
        for plan in by[t]:
            if not checks.valid_jagged(plan, f.shape, m):
                invalid += 1
                failed += 1
                continue
            gap = lmax(ref, f, plan) / want - 1.0
            worst = max(worst, gap)
            failed += gap > lim["lmax_gap"]
    # a run whose sampled plans are all invalid compares no gap
    return {"lmax_gap": float(worst) if np.isfinite(worst) else 0.0,
            "invalid_plans": invalid}, failed


def control(frame, ref, cfg) -> dict:
    P, m = cfg["P"], cfg["m"]
    res = ref.jag_m_heur(checks.rounded(ref.gamma(frame), STEP_BELOW),
                         P=P, m=m)
    cc = np.full((P, m - P + 2), frame.shape[1], np.int64)
    for s, c in enumerate(res["col_cuts"]):
        cc[s, :c.size] = c
    return {"row_cuts": res["row_cuts"], "counts": res["counts"],
            "col_cuts": cc}
