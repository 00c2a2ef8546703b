"""Every traffic mix driven through the harness at a test's size, with
the chip lookup skipped: sound runs come out correct, the controls and
planted faults come out not correct."""
from __future__ import annotations

import benchtiny
import numpy as np
import pytest

from benchlib import spec as benchspec

ONE_CHIP = ["pic2d.replan-heur", "pic2d.stream-exact"]
BATCHED = ["pic2d.stream-exact"]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return benchtiny.tiny_root(tmp_path_factory.mktemp("cells"))


@pytest.fixture
def pallas_interpret(monkeypatch):
    """The planner takes its Pallas kernels (interpreted off the chip)."""
    from repro import backend
    from repro.core import device
    from repro.rebalance import planner
    for mod in (backend, device, planner):
        monkeypatch.setattr(mod, "use_pallas_default", lambda: True)
    monkeypatch.setenv("JAX_PALLAS_INTERPRET", "1")


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_mix_runs_one_window_with_pallas(tiny, pallas_interpret, workload):
    out = benchtiny.drive(tiny, workload, seconds=0.05)
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    cell = benchspec.Cell(benchtiny.spec(), workload)
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "compared"


# ---------------------------------------------------------------------------
# faults planted under the timed path


class Stale:
    """A call that returns the previous call's answers unchanged."""

    def __init__(self, entry):
        self.entry, self.last = entry, None

    def __call__(self, batch):
        out = self.entry(batch)
        prev, self.last = self.last, out
        return out if prev is None else prev


class HalfBatch:
    """Only the first half of the batch is planned; its answers stand in
    for the rest."""

    def __init__(self, entry):
        self.entry = entry

    def __call__(self, batch):
        out = self.entry(batch)
        h = len(out) // 2
        return out[:h] + out[:len(out) - h]


class Altered:
    """One cut of every answer moved where it is produced."""

    def __init__(self, entry):
        self.entry = entry

    def __call__(self, batch):
        out = []
        for plan in self.entry(batch):
            plan = dict(plan)
            rc = np.array(plan["row_cuts"])
            rc[1] += 1
            plan["row_cuts"] = rc
            out.append(plan)
        return out


FAULTS = [(w, f) for w in ONE_CHIP for f in (Stale, Altered)] \
    + [(w, HalfBatch) for w in BATCHED]


@pytest.mark.parametrize("workload,fault", FAULTS,
                         ids=[f"{w}-{f.__name__}" for w, f in FAULTS])
def test_planted_fault_is_not_correct(tiny, workload, fault):
    out = benchtiny.drive(tiny, workload, seconds=0.5, wrap=fault)
    assert out["correct"] is False, out["compared"]
    assert out["failed"] > 0


# ---------------------------------------------------------------------------
# the controls: the reference one precision step below


def _heavy_frame(n1=64, n2=48, seed=0):
    """A frame whose total is large enough (2**30) that a float32 prefix
    table rounds, with a light background whose column sums are small
    against that rounding (as a 4096^2 frame's are at 1.6e9)."""
    rng = np.random.default_rng(seed)
    f = rng.integers(40, 60, (n1, n2)).astype(np.int64)
    f[rng.integers(n1), rng.integers(n2)] = 2 ** 30
    return f


def _plain_frame(n1=128, n2=128, seed=0):
    """Loads of about 100 a cell, planned for the cell's own m = 1024: a
    bfloat16 prefix table (8 significant bits) cannot tell one
    processor's share (1/1024 of the total) from its rounding."""
    return np.random.default_rng(seed).integers(50, 150, (n1, n2))


CONTROL_FRAMES = {"heur2d": _plain_frame, "exact2d": _heavy_frame}


CELL_OF = {"heur2d": "pic2d.replan-heur", "exact2d": "pic2d.stream-exact"}


@pytest.mark.parametrize("kind", ["heur2d", "exact2d"])
def test_control_2d_is_not_correct(kind):
    cell = benchspec.Cell(benchtiny.spec(), CELL_OF[kind])
    cfg = dict(cell.config)
    if kind == "exact2d":
        cfg.update(benchtiny.TINY_CONFIG["pic2d-hotspot-4096"])
    ref, check = cell.reference(), cell.check()
    frames = {t: CONTROL_FRAMES[kind](seed=t) for t in range(4)}
    records = [(t, check.control(f, ref, cfg)) for t, f in frames.items()]
    numbers, failed = check.compare(records, frames.__getitem__, ref, cfg,
                                    cell.traffic, 3)
    assert failed > 0
    assert any(v > cell.traffic["limits"][k] for k, v in numbers.items())


def test_reference_agrees_with_itself_in_full_precision():
    """The controls' only change is the precision: at full precision the
    same code passes the comparison."""
    cell = benchspec.Cell(benchtiny.spec(), "pic2d.stream-exact")
    cfg = dict(cell.config, **benchtiny.TINY_CONFIG["pic2d-hotspot-4096"])
    ref, check = cell.reference(), cell.check()
    frames = {t: _heavy_frame(seed=t) for t in range(2)}
    records = []
    for t, f in frames.items():
        res = ref.jag_pq_opt(ref.gamma(f), P=cfg["P"], Q=cfg["m"] // cfg["P"])
        records.append((t, {"row_cuts": res["row_cuts"],
                            "counts": np.full(cfg["P"], cfg["m"] // cfg["P"]),
                            "col_cuts": res["col_cuts"],
                            "lmax": res["lmax"]}))
    numbers, failed = check.compare(records, frames.__getitem__, ref, cfg,
                                    cell.traffic, 3)
    assert failed == 0 and numbers == {"cut_mismatch": 0, "lmax_mismatch": 0}


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_traced_run_reads_its_trace(tiny, workload, monkeypatch):
    """A ``--trace 1`` run profiles its window and prints per-layer
    metrics only; off the chip the trace has no device plane, so only the
    span reader finds something to read."""
    from benchlib import chip
    monkeypatch.setattr(chip, "peaks", lambda kind: {"hbm_bytes_per_s": 1e9})
    out = benchtiny.drive(tiny, workload, seconds=0.3, trace=1)
    assert out["correct"] is True, out["compared"]
    cell = benchspec.Cell(benchtiny.spec(), workload)
    assert set(out["metrics"]) <= {m["name"] for m in cell.per_layer}
    if workload == "pic2d.replan-heur":
        assert out["metrics"]["dispatch_ms.replan"]["value"] > 0
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(out)[-1] == "compared"
