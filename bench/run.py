#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``); the mix names the entry point
(``bench/entries/<name>.py``) and the check (``bench/checks/<name>.py``).
Set-up makes the frame pool on the device from ``--seed``, compiles the
cell's shapes and makes one warm-up call; the window then drives the
entry point in a closed loop for ``--seconds``.  After the window the
plans are compared with the plain reference (``bench/reference/``) and
the result line is printed: with ``--trace 0`` the cell's end-to-end
metrics (``bench/e2e/<name>.py``), with ``--trace 1`` its per-layer
metrics (``bench/metrics/<name>.py``) read from a profiler trace of the
window.

Exits 2, printing no result, where JAX finds no TPU or fewer chips than
the cell needs.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from benchlib import spec as benchspec  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_jax():
    """JAX's persistent compile cache at the fixed ``<checkout>/.jax_cache``
    (git-ignored), keeping every program, so that only a cell's first
    run in a checkout compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def measure(cell, args, clock, devs) -> dict:
    from benchlib import chip, pool as benchpool, window

    cfg, traffic = cell.config, cell.traffic
    frames = benchpool.make(cell.generator(), cfg, traffic, args.seed)
    order = list(range(len(frames)))      # every seed replays the same frames
    entry = cell.entry().make(cfg, traffic)
    entry(frames[order[0]])                         # compiles, then warm
    setup_s = time.perf_counter() - T_START
    print(f"bench: set-up {setup_s:.3f} s, compile {clock.seconds:.3f} s, "
          f"cache hits {clock.hits} misses {clock.misses}",
          file=sys.stderr, flush=True)

    per_call = traffic["frames_per_call"]
    if args.trace:
        part, tr, spans, w = window.traced(entry, frames, order, per_call,
                                           args.seconds, clock)
    else:
        w = window.run(entry, frames, order, per_call, args.seconds, clock)
    if w.compiles:
        print(f"bench: WARNING {w.compiles} compilations inside the window",
              file=sys.stderr, flush=True)
    device = chip.describe(devs)

    ref, check = cell.reference(), cell.check()
    host = benchpool.HostFrames(frames, per_call)
    t_check = time.perf_counter()
    numbers, failed = check.compare(w.records, host, ref, cfg, traffic,
                                    args.seed)
    check_s = time.perf_counter() - t_check

    metrics = {}
    if args.trace:
        run = window.Traced(part.records, part.calls, part.frames, tr, spans,
                            cfg, traffic, chip.peaks(device["kind"]))
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = tr.mean_busy_s()
        device["window_s"] = tr.window_s
    else:
        run = window.Measured(w, setup_s, host, ref, cfg, check)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(cell.e2e(m["name"]).read(run)),
                                  "unit": m["unit"]}

    limits = traffic["limits"]
    compared = {k: {"value": float(v), "limit": float(limits[k])}
                for k, v in numbers.items()}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    out = {"correct": bool(correct), "attempted": w.frames,
           "failed": int(failed), "metrics": metrics, "device": device}
    if args.trace:
        out["breakdown"] = {"device_ops": tr.top_ops(10),
                            "idle_gaps": tr.idle_gaps(10)}
    out["compared"] = compared
    print(f"bench: window {w.seconds:.3f} s, {w.calls} calls, {w.frames} "
          f"frames, {w.compiles} compiles; check of "
          f"{traffic['sample']} sampled frames {check_s:.3f} s",
          file=sys.stderr)
    for k, c in compared.items():
        print(f"compared {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return out


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = benchspec.Cell(benchspec.load(ROOT), args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from benchlib import chip
    try:
        devs = chip.devices(cell.chips)
    except chip.NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    setup_jax()
    clock = chip.CompileClock()
    out = measure(cell, args, clock, devs)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
