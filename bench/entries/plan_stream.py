"""``planner.plan_stream`` on a whole pool array, then ``unstack_plans``:
one dispatch per call, cuts to host Plans.  Returns, per frame, the cuts
and the Lmax the device computed."""
import numpy as np


def make(cfg: dict, traffic: dict):
    from repro.rebalance import batch_device, planner
    P, m, exact = cfg["P"], cfg["m"], bool(traffic.get("exact", False))

    def call(batch) -> list[dict]:
        out = planner.plan_stream(batch, P=P, m=m, exact=exact)
        plans = batch_device.unstack_plans(out, tuple(batch.shape[1:]))
        lmax = np.asarray(out[3])
        return [{"row_cuts": p.row_cuts, "counts": p.counts,
                 "col_cuts": p.col_cuts, "lmax": lmax[t]}
                for t, p in enumerate(plans)]
    return call
