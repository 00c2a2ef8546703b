"""Helpers for the benchmark's CPU tests: the committed cells at a size
a test run can hold, driven through the harness with the chip lookup
skipped."""
from __future__ import annotations

import json
import sys
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = {
    "pic2d-hotspot-4096": {"frame": {"n1": 64, "n2": 48, "dtype": "int32"},
                           "P": 4, "m": 16},
}


def spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def tiny_root(tmp: Path) -> Path:
    """A checkout-shaped tree whose configurations and mixes are the
    committed ones cut to a test's size (pools of 2-3 small calls)."""
    bench = tmp / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    for d in ("gen", "reference", "metrics", "benchlib", "entries",
              "checks", "e2e"):
        (bench / d).symlink_to(BENCH / d)
    for name, cut in TINY_CONFIG.items():
        cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        cfg.update(cut)
        (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for path in (BENCH / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        mix["frames_per_call"] = min(mix["frames_per_call"], 2)
        mix["pool_calls"] = 3 if mix["frames_per_call"] == 1 else 2
        (bench / "traffic" / path.name).write_text(json.dumps(mix))
    (tmp / "BENCHMARK.json").write_text((ROOT / "BENCHMARK.json").read_text())
    return tmp


def drive(root: Path, workload: str, *, seed: int = 2 ** 33 + 5,
          seconds: float = 0.2, trace: int = 0, wrap=None):
    """Run a cell through ``run.measure`` on the CPU.  ``wrap(entry)``
    may replace the program's entry (to plant a fault)."""
    import jax

    import run as benchrun
    from benchlib import chip
    from benchlib import spec as benchspec

    cell = benchspec.Cell(spec(), workload, bench=root / "bench")
    args = types.SimpleNamespace(workload=workload, seed=seed,
                                 seconds=seconds, trace=trace)
    if wrap is not None:
        module = cell.entry()
        cell.entry = lambda: types.SimpleNamespace(
            make=lambda cfg, traffic: wrap(module.make(cfg, traffic)))
    return benchrun.measure(cell, args, chip.CompileClock(),
                            jax.devices()[:cell.chips])
