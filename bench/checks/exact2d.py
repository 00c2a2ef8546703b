"""JAG-PQ-OPT (int32 Gamma, exact cuts stated): the comparison that
decides ``correct``, the exact Lmax of a plan, and the control.

The cuts of every plan of the sampled frames, and the Lmax the device
reports for it, have to equal the exact reference's bit for bit
(``cut_mismatch``, ``lmax_mismatch``: plans that differ).  The control is
that reference on a Gamma stored in float32, one precision step below
the configuration's int32.
"""
import numpy as np

from benchlib import checks

STEP_BELOW = np.float32


def lmax(ref, frame, plan) -> int:
    return ref.plan_lmax(frame, plan["row_cuts"], plan["counts"],
                         plan["col_cuts"])


def compare(records, frame, ref, cfg, traffic, seed) -> tuple[dict, int]:
    P, m = cfg["P"], cfg["m"]
    Q = m // P
    by = checks.by_frame(records)
    cut_bad, lmax_bad, failed = 0, 0, 0
    for t in checks.sample(records, traffic["sample"], seed):
        f = frame(t)
        want = ref.jag_pq_opt(ref.gamma(f), P=P, Q=Q)
        for plan in by[t]:
            cc = np.asarray(plan["col_cuts"])
            same = (np.array_equal(plan["row_cuts"], want["row_cuts"])
                    and np.array_equal(plan["counts"], np.full(P, Q))
                    and cc.shape == want["col_cuts"].shape
                    and np.array_equal(cc, want["col_cuts"]))
            lmax_ok = int(plan["lmax"]) == want["lmax"]
            cut_bad += not same
            lmax_bad += not lmax_ok
            failed += not (same and lmax_ok)
    return {"cut_mismatch": cut_bad, "lmax_mismatch": lmax_bad}, failed


def control(frame, ref, cfg) -> dict:
    P, m = cfg["P"], cfg["m"]
    res = ref.jag_pq_opt(checks.rounded(ref.gamma(frame), STEP_BELOW),
                         P=P, Q=m // P)
    return {"row_cuts": res["row_cuts"], "counts": np.full(P, m // P),
            "col_cuts": res["col_cuts"], "lmax": res["lmax"]}
