"""Find a cell's configuration, traffic mix, generator, reference, entry
point, check and metric readers from ``BENCHMARK.json`` and file names
alone: each is a file under ``bench/`` named by the name that
``BENCHMARK.json``, the configuration or the mix gives it."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def load_module(path: Path):
    """Import a file by path (names may hold '.' and '-')."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, spec: dict, name: str, bench: Path = BENCH):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        self.workload = cells[name]
        self.name = name
        self.chips = int(self.workload["chips"])
        configs = {c["name"]: c for c in spec["configs"]}
        entry = configs[self.workload["config"]]
        self.config = json.loads((bench.parent / entry["file"]).read_text())
        self.traffic_name = self.workload["traffic"]
        self.traffic = json.loads(
            (bench / "traffic" / f"{self.traffic_name}.json").read_text())
        self.end_to_end = [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in spec["per_layer"]
                          if name in m.get("workloads", [name])]
        self.bench = bench

    def generator(self):
        return load_module(self.bench / "gen" / f"{self.config['generator']}.py")

    def reference(self):
        return load_module(
            self.bench / "reference" / f"{self.config['reference']}.py")

    def entry(self):
        """The entry point the window drives: ``entries/<entry>.py``."""
        return load_module(
            self.bench / "entries" / f"{self.traffic['entry']}.py")

    def check(self):
        """The comparison that decides ``correct``: ``checks/<check>.py``."""
        return load_module(
            self.bench / "checks" / f"{self.traffic['check']}.py")

    def reader(self, metric: str):
        """A per-layer metric's reader: ``metrics/<name>.py``."""
        return load_module(self.bench / "metrics" / f"{metric}.py")

    def e2e(self, metric: str):
        """An end-to-end metric's reader: ``e2e/<name>.py``."""
        return load_module(self.bench / "e2e" / f"{metric}.py")


def load(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())
