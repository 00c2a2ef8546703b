"""End-to-end particle-in-cell simulation with dynamic rebalancing.

The paper's own application: particles drift across a field; the field
update cost per cell is proportional to its particle count. We distribute
cells to processors with rectangular partitions, simulate the per-step
wall-clock as the most-loaded processor, and rebalance every K steps.

Two modes:

- default (no ``--algo``): the original 2D comparison — simulated
  speedup of JAG-M-HEUR-PROBE rebalancing vs a static uniform grid, the
  end-to-end number the paper's load-balance figures translate into.
- ``--algo {jag-m-heur-3d,sgorp-3d,project-then-2d}``: the volumetric
  version on drifting 3D PIC dumps, through the same registry (rank-3
  names take the raw (n1, n2, n3) volume), against a static uniform 3D
  grid.  ``--trace FILE`` records the run — registry phases, slab-memo
  and SGORP counters via the final ``explain()`` — as a Chrome/Perfetto
  ``trace_event`` JSON (load at https://ui.perfetto.dev).

    PYTHONPATH=src python examples/pic_simulation.py
    PYTHONPATH=src python examples/pic_simulation.py \
        --algo sgorp-3d --trace pic3d_trace.json
"""
import argparse
import json

import numpy as np

from repro import backend, obs
from repro.core import prefix, registry, threed
from repro.data.pipeline import ParticleFeed

ALGOS_3D = ("jag-m-heur-3d", "sgorp-3d", "project-then-2d")


def simulate(algo: str, feed: ParticleFeed, m: int, steps: int,
             rebalance_every: int):
    part = None
    cost = 0.0
    for t in range(steps):
        feed.step()
        A = feed.load_matrix()
        g = prefix.prefix_sum_2d(A)
        if part is None or (rebalance_every and t % rebalance_every == 0):
            part = registry.partition(algo, g, m)
        cost += part.max_load(g)  # wall-clock ~ most loaded processor
    return cost


def main_2d():
    m, steps = 256, 40
    base_feed = ParticleFeed(128, 128, n_particles=100_000)

    import copy
    ideal = 0.0
    feed = copy.deepcopy(base_feed)
    for t in range(steps):
        feed.step()
        ideal += feed.load_matrix().sum() / m

    results = {}
    for algo, re_every in [("rect-uniform", 0), ("hier-rb", 5),
                           ("jag-m-heur", 5), ("jag-m-heur-probe", 5)]:
        cost = simulate(algo, copy.deepcopy(base_feed), m, steps, re_every)
        results[algo] = cost
        print(f"{algo:20s} rebalance_every={re_every or '—':>2} "
              f"sim_time={cost:,.0f}  efficiency={ideal / cost * 100:.1f}%")

    speedup = results["rect-uniform"] / results["jag-m-heur-probe"]
    print(f"\nJAG-M-HEUR-PROBE vs static uniform grid: {speedup:.2f}x "
          f"simulated speedup")
    assert speedup > 1.05


def simulate_3d(algo: str, m: int, steps: int, rebalance_every: int,
                n: int, static: bool = False):
    """Per-step cost of partitioning drifting 3D PIC volumes via the
    registry (rank-3 names take the raw volume)."""
    part = None
    cost = ideal = 0.0
    for t in range(steps):
        with obs.span("pic3d.step", t=t):
            A = prefix.pic_like_instance_3d(n, n, n, iteration=t * 500,
                                            seed=0)
            g3 = prefix.prefix_sum_3d(A)
            if part is None:
                if static:
                    from repro.core.sgorp import default_grid
                    part = threed.uniform_3d(A, *default_grid(m, A.shape))
                else:
                    part = registry.partition(algo, A, m)
            elif not static and rebalance_every and \
                    t % rebalance_every == 0:
                part = registry.partition(algo, A, m)
            cost += part.max_load(A, gamma3=g3)
            ideal += A.sum() / m
    return cost, ideal


def main_3d(args) -> None:
    with obs.tracing() as tr:
        cost, ideal = simulate_3d(args.algo, args.m, args.steps,
                                  args.rebalance_every, args.size)
        static_cost, _ = simulate_3d(args.algo, args.m, args.steps, 0,
                                     args.size, static=True)
        # the explain() call lands the engine phases + counters (slab
        # memo hits, sgorp iterations) in the same trace
        A = prefix.pic_like_instance_3d(args.size, args.size, args.size,
                                        iteration=0, seed=0)
        report = registry.explain(args.algo, A, args.m)
        events = tr.events()

    print(f"{args.algo:16s} m={args.m} steps={args.steps} "
          f"size={args.size}^3")
    print(f"rebalanced sim_time={cost:,.0f}  "
          f"efficiency={ideal / cost * 100:.1f}%")
    print(f"static-uniform sim_time={static_cost:,.0f}  "
          f"efficiency={ideal / static_cost * 100:.1f}%")
    # no >1x assertion here: the drifting 3D shell has near-uniform
    # marginals, so a static uniform grid is already a strong baseline —
    # the interesting output is the per-frame LI and the engine counters
    print(f"speedup vs static uniform grid: {static_cost / cost:.2f}x")
    print(f"final-frame LI={report.imbalance * 100:.2f}%  "
          f"counters={ {k: v for k, v in report.counters.items() if v} }")

    if args.trace:
        obs.write_chrome_trace(args.trace, events, algo=args.algo,
                               m=args.m, steps=args.steps, size=args.size)
        with open(args.trace) as f:  # must be a loadable Chrome trace
            obs.validate_chrome_trace(json.load(f))
        print(f"wrote {len(events)} trace events to {args.trace} "
              f"(open at https://ui.perfetto.dev)")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--algo", choices=ALGOS_3D, default=None,
                    help="run the 3D simulation with this rank-3 registry "
                         "algorithm (default: the 2D comparison)")
    ap.add_argument("--m", type=int, default=64)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--size", type=int, default=32,
                    help="3D grid edge (size^3 cells)")
    ap.add_argument("--rebalance-every", type=int, default=3)
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="write a Chrome/Perfetto trace of the 3D run")
    args = ap.parse_args()
    backend.enable_compile_cache()
    if args.algo is None:
        main_2d()
    else:
        main_3d(args)


if __name__ == "__main__":
    main()
