#!/usr/bin/env python3
"""Readings for the limits of ``correct``, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 101,102,103 --seconds <s>

For each of ``--seeds`` the program plans the cell's pool for
``--seconds`` (at least one call per pool array) and the run's numbers
are compared with the plain reference exactly as ``run.py`` compares
them: the largest of these is the lower reading of each limit.  For each
of ``--control-seeds`` the control (the ``control`` of the mix's check
module: the reference one precision step below the configuration's)
takes the program's place on the frames the check samples: the smallest
of these is the upper reading.  Prints one JSON line per seed and a summary line;
the benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import run as benchrun  # noqa: E402
from benchlib import checks, chip, window  # noqa: E402
from benchlib import pool as benchpool  # noqa: E402
from benchlib import spec as benchspec  # noqa: E402


def seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    cell = benchspec.Cell(benchspec.load(ROOT), args.workload)
    try:
        chip.devices(cell.chips)
    except chip.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    benchrun.setup_jax()
    clock = chip.CompileClock()
    cfg, traffic = cell.config, cell.traffic
    gen, ref, check = cell.generator(), cell.reference(), cell.check()
    entry = cell.entry().make(cfg, traffic)
    per_call = traffic["frames_per_call"]
    worst, best = {}, {}
    for role, seed in ([("program", s) for s in args.seeds]
                       + [("control", s) for s in args.control_seeds]):
        t0 = time.perf_counter()
        frames = benchpool.make(gen, cfg, traffic, seed)
        host = benchpool.HostFrames(frames, per_call)
        order = list(range(len(frames)))
        line = {"role": role, "seed": seed}
        if role == "program":
            entry(frames[order[0]])
            w = window.run(entry, frames, order, per_call,
                           args.seconds, clock)
            while w.calls < len(frames):
                more = window.run(entry, frames, order[w.calls:], per_call,
                                  0, clock)
                w.records += more.records
                w.calls += more.calls
            records = w.records
            line["frames"] = len(records)
            line["imbalance_pct"] = checks.imbalance_pct(
                records, host, cfg["m"],
                lambda f, plan: check.lmax(ref, f, plan))
        else:
            ts = list(range(len(frames) * per_call))
            picked = checks.sample([(t, None) for t in ts],
                                   traffic["sample"], seed)
            records = [(t, check.control(host(t), ref, cfg)) for t in picked]
        t1 = time.perf_counter()
        numbers, failed = check.compare(records, host, ref, cfg, traffic,
                                        seed)
        line.update(numbers=numbers, failed=failed,
                    check_s=time.perf_counter() - t1,
                    seconds=time.perf_counter() - t0)
        print(json.dumps(line), flush=True)
        table = worst if role == "program" else best
        for k, v in numbers.items():
            if role == "program":
                table[k] = max(table.get(k, v), v)
            else:
                table[k] = min(table.get(k, v), v)
        del frames, host
    print(json.dumps({"workload": args.workload,
                      "lower_reading": worst, "upper_reading": best,
                      "limits": traffic["limits"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
