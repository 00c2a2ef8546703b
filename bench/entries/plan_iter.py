"""``planner.plan_iter``: the per-frame Plan iterator the runtime's
policy loop consumes, one call per pool array, with the default
(heuristic) solver unless the mix sets ``"exact": true``.  Returns, per
frame, the cuts that reached the host."""


def make(cfg: dict, traffic: dict):
    from repro.rebalance import planner
    P, m, exact = cfg["P"], cfg["m"], bool(traffic.get("exact", False))

    def call(batch) -> list[dict]:
        return [{"row_cuts": p.row_cuts, "counts": p.counts,
                 "col_cuts": p.col_cuts}
                for p in planner.plan_iter(batch, P=P, m=m, exact=exact)]
    return call
