"""Dynamic rebalancing demo: a drifting hotspot, three replan policies.

Generates a time-evolving load stream, partitions every frame on the
device in one batched call, then replays the stream under never-rebalance,
every-step-rebalance, and the hysteresis policy, printing the cost ledger
(compute = per-step bottleneck, migration = moved load x alpha + overhead).

    PYTHONPATH=src python examples/rebalance_demo.py
    PYTHONPATH=src python examples/rebalance_demo.py --devices 8
    PYTHONPATH=src python examples/rebalance_demo.py --fail-at

``--devices N`` plans the stream frame-sharded over an N-device mesh
(forcing N host devices when the platform has fewer — the flag must be
set before jax initializes, which is why it is parsed before any repro
import); the cuts are bit-identical to the 1-device plan, only faster.

``--fail-at [STEP]`` injects a fault timeline (one processor fails at
STEP — default T/2 — and another straggles at 0.3x speed) and adds the
fault-aware policy to the comparison: failures force an immediate
degraded replan over surviving capacity and the ledger charges the
evacuated load.
"""
import argparse
import os

parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
parser.add_argument("--devices", type=int, default=1,
                    help="shard planning over N devices (default 1)")
parser.add_argument("--fail-at", type=int, nargs="?", const=-1,
                    default=None, metavar="STEP",
                    help="inject a processor failure at STEP "
                         "(no value: T/2)")
args = parser.parse_args()
if args.devices > 1:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count"
                                 f"={args.devices}")

import time                                                       # noqa: E402

from repro import backend                                         # noqa: E402
from repro.rebalance import faults, migrate, policy, runtime, \
    stream                                                        # noqa: E402

backend.enable_compile_cache()

T, N, P, M = 32, 64, 4, 16

frames = stream.drifting_hotspot(T, N, N, seed=0)
t0 = time.perf_counter()
plans = runtime.plan_stream_host(frames, P=P, m=M, devices=args.devices)
dt = time.perf_counter() - t0
where = f"sharded over {args.devices} devices" if args.devices > 1 \
    else "one batched device call"
print(f"{T} frames of {N}x{N} partitioned into m={M} rectangles "
      f"({where}, {dt * 1e3:.0f} ms incl. compile)")
vol = migrate.migration_volume(plans[0], plans[-1], weights=frames[-1])
print(f"plan drift over the run: {vol / frames[-1].sum() * 100:.1f}% "
      "of the load would migrate frame 0 -> frame -1\n")

policies = {"never": policy.NeverRebalance(),
            "always": policy.AlwaysRebalance(),
            "every-8": policy.EveryK(8),
            "hysteresis": policy.HysteresisPolicy()}
sched = None
if args.fail_at is not None:
    fail_at = T // 2 if args.fail_at == -1 else args.fail_at
    sched = faults.FaultSchedule(M, [
        faults.FaultEvent(fail_at, 3, "fail"),
        faults.FaultEvent(fail_at, 11, "straggle", speed=0.3),
    ])
    policies["fault-aware"] = policy.FaultAwareHysteresis()
    print(f"fault timeline: part 3 fails and part 11 drops to 0.3x speed "
          f"at step {fail_at}; every policy is forced off the dead part\n")

results = runtime.compare_policies(
    frames, policies,
    P=P, m=M, alpha=0.25, replan_overhead=1000.0,
    devices=args.devices, faults=sched, validate=sched is not None)

for name, res in results.items():
    extra = ""
    if sched is not None:
        extra = (f"  [forced={res.n_forced} "
                 f"evac={res.evacuation_volume:.0f}]")
    print(f"{name:>11}: {res.summary()}{extra}")

best = min(results, key=lambda k: results[k].total_cost)
print(f"\ncheapest policy: {best}")
