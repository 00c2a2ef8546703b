"""BENCHMARK.json against the benchmark's contract, and the harness
finding every cell's files by name alone."""
from __future__ import annotations

import json
import re
import shutil

import benchtiny
import pytest

from benchlib import spec as benchspec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.fixture(scope="module")
def spec():
    return benchtiny.spec()


def test_keys_sizes_and_paths(spec):
    raw = (benchtiny.ROOT / "BENCHMARK.json").read_bytes()
    assert len(raw) <= 64 * 1024
    assert set(spec) == TOP
    assert 1 <= len(spec["paths"]) <= 16
    for p in spec["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    cmd = spec["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for word in cmd:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in spec["paths"])
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51


def test_names_units_and_entries(spec):
    assert 1 <= len(spec["configs"]) <= 24
    assert 1 <= len(spec["workloads"]) <= 24
    assert 1 <= len(spec["end_to_end"]) <= 16
    assert 1 <= len(spec["per_layer"]) <= 128
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in spec[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("bench/") and PATH.match(c["file"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    setup = [m for m in spec["end_to_end"] if m["name"] == "setup_s"]
    assert setup and "workloads" not in setup[0]
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_cell_reports_what_it_must(spec):
    names = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", names))
           for m in spec["end_to_end"]}
    for cells in e2e.values():
        assert cells <= names
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= e2e[m["moves"]], m["name"]
    for w in names:
        assert w in e2e["setup_s"]
        assert any(w in c for k, c in e2e.items() if k != "setup_s")
        assert any(w in m["workloads"] for m in spec["per_layer"])
    used = {w["config"] for w in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}


def test_cells_resolve_from_files(spec):
    for w in spec["workloads"]:
        cell = benchspec.Cell(spec, w["name"])
        assert cell.config["name"] == w["config"]
        check = cell.check()
        assert all(callable(getattr(check, f))
                   for f in ("compare", "lmax", "control"))
        assert callable(cell.entry().make)
        for m in cell.end_to_end:
            assert callable(cell.e2e(m["name"]).read)
        assert hasattr(cell.generator(), "frame")
        assert cell.reference().__doc__
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]).read)
        for k in cell.traffic["limits"]:
            assert isinstance(cell.traffic["limits"][k], (int, float))


def test_config_files_hold_their_source(spec):
    files = [c["file"] for c in spec["configs"]]
    assert len(files) == len(set(files))
    for c in spec["configs"]:
        cfg = json.loads((benchtiny.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"] and "assumed" in cfg


def test_a_new_cell_needs_only_files(tmp_path, spec):
    """A mix and a configuration added as data files, and an entry in
    BENCHMARK.json, are found with no change to any code."""
    root = tmp_path
    shutil.copytree(benchtiny.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    mix = json.loads((root / "bench/traffic/replan-heur.json").read_text())
    mix["frames_per_call"], mix["pool_calls"] = 16, 1
    (root / "bench/traffic/stream-heur.json").write_text(json.dumps(mix))
    cfg = json.loads(
        (root / "bench/configs/pic2d-hotspot-4096.json").read_text())
    cfg["name"], cfg["frame"] = "pic2d-hotspot-2048", {"n1": 2048,
                                                       "n2": 2048}
    (root / "bench/configs/pic2d-hotspot-2048.json").write_text(
        json.dumps(cfg))
    new = dict(spec)
    new["configs"] = spec["configs"] + [dict(
        spec["configs"][0], name="pic2d-hotspot-2048",
        file="bench/configs/pic2d-hotspot-2048.json")]
    new["workloads"] = spec["workloads"] + [dict(
        spec["workloads"][0], name="pic2d2k.stream-heur",
        config="pic2d-hotspot-2048", traffic="stream-heur")]
    cell = benchspec.Cell(new, "pic2d2k.stream-heur", bench=root / "bench")
    assert cell.config["frame"]["n1"] == 2048
    assert cell.traffic["frames_per_call"] == 16
    assert cell.generator().frame.__name__ == "frame"


def test_a_new_entry_and_check_need_only_files(tmp_path, spec):
    """An entry point and a check added as modules, named by a new mix,
    are found by name with no change to any code."""
    root = tmp_path
    shutil.copytree(benchtiny.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench/entries/echo.py").write_text(
        "def make(cfg, traffic):\n    return lambda batch: [batch]\n")
    (root / "bench/checks/nothing.py").write_text(
        "def compare(records, frame, ref, cfg, traffic, seed):\n"
        "    return {'seen': len(records)}, 0\n"
        "lmax = control = None\n")
    mix = json.loads((root / "bench/traffic/replan-heur.json").read_text())
    mix["entry"], mix["check"] = "echo", "nothing"
    (root / "bench/traffic/echo.json").write_text(json.dumps(mix))
    new = dict(spec)
    new["workloads"] = spec["workloads"] + [dict(
        spec["workloads"][0], name="pic2d.echo", traffic="echo")]
    cell = benchspec.Cell(new, "pic2d.echo", bench=root / "bench")
    assert cell.entry().make({}, mix)(7) == [7]
    assert cell.check().compare([1, 2], None, None, {}, mix, 0) == (
        {"seen": 2}, 0)
