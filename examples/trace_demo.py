"""End-to-end tracing demo: a drifting-hotspot rebalance run -> trace.json.

Runs the time-stepped rebalancing runtime under the obs tracer, asks the
registry to explain the final frame's partition, and writes everything as
one Chrome ``trace_event`` JSON:

- pid 0: live host spans — per-step ``runtime.step`` (with the graded
  replan mode), planner dispatch/collect, policy decision instants, and
  the explain() call's engine phases;
- pid 1: the run ledger's virtual timelines (``RunResult.trace_events``)
  — per-step bottleneck widths and replan markers.

Open the file at https://ui.perfetto.dev (or chrome://tracing): drag it
into the window, or use "Open trace file".

    PYTHONPATH=src python examples/trace_demo.py --out trace.json
"""
from __future__ import annotations

import argparse
import json

from repro import backend, obs
from repro.core import prefix, registry
from repro.rebalance import runtime, stream
from repro.rebalance.policy import HysteresisPolicy


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="trace.json")
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--size", type=int, default=48)
    ap.add_argument("--m", type=int, default=16)
    args = ap.parse_args()
    backend.enable_compile_cache()

    frames = stream.drifting_hotspot(T=args.steps, n1=args.size,
                                     n2=args.size, seed=0)
    with obs.tracing() as tr:
        result = runtime.run_stream(frames, HysteresisPolicy(), P=4,
                                    m=args.m, alpha=0.1,
                                    replan_overhead=5.0)
        report = registry.explain(
            "jag-m-heur-probe", prefix.prefix_sum_2d(frames[-1]), args.m)
        events = tr.events()

    events = [
        {"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
         "args": {"name": "host spans"}},
        {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
         "args": {"name": "run ledger (virtual time)"}},
    ] + events + result.trace_events(pid=1)

    obs.write_chrome_trace(args.out, events,
                           steps=args.steps, size=args.size, m=args.m,
                           run_summary=result.summary())

    # self-check: the file we just wrote must be a loadable Chrome trace
    with open(args.out) as f:
        obs.validate_chrome_trace(json.load(f))

    print(result.summary())
    print(report.summary())
    print(f"wrote {len(events)} events to {args.out}")
    print("open it at https://ui.perfetto.dev (drag the file in) "
          "or chrome://tracing")


if __name__ == "__main__":
    main()
