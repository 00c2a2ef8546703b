"""The device generators: frames repeat from the seed, and 2D frame
totals stay inside the exact solver's int32 accumulators."""
from __future__ import annotations

import json

import benchtiny
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import pool as benchpool
from benchlib import spec as benchspec

CELLS = ["pic2d.replan-heur", "pic2d.stream-exact"]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return benchtiny.tiny_root(tmp_path_factory.mktemp("gen"))


def _pool(root, workload, seed):
    cell = benchspec.Cell(benchtiny.spec(), workload, bench=root / "bench")
    frames = benchpool.make(cell.generator(), cell.config, cell.traffic,
                            seed)
    return np.stack([np.asarray(f) for f in frames])


@pytest.mark.parametrize("workload", CELLS)
def test_frames_repeat_from_the_seed(tiny, workload):
    big = 2 ** 33 + 12345
    a, b = _pool(tiny, workload, big), _pool(tiny, workload, big)
    c = _pool(tiny, workload, big + 1)
    assert a.dtype == np.int32 and a.min() >= 1
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    # consecutive frames of the stream differ: the load drifts
    flat = a.reshape(-1, *a.shape[2:])
    assert not np.array_equal(flat[0], flat[-1])


def test_seed_range():
    assert benchpool.seed_words(2 ** 40 + 3).tolist() == [3, 256]
    with pytest.raises(ValueError):
        benchpool.seed_words(-1)


def _expected_totals(cfg, T):
    """Mean total of each frame of a T-frame pool (the Poisson mean),
    from the separable density: base * (n1 n2 + amp sum_h Gi Gj)."""
    import jax

    from benchlib import spec as spec_mod
    gen = spec_mod.load_module(benchtiny.BENCH / "gen" / "hotspot2d.py")
    n1, n2 = cfg["frame"]["n1"], cfg["frame"]["n2"]
    H = cfg["hotspots"]
    g1, g2 = jax.random.split(jax.random.key(cfg["geometry_seed"]))
    pos = np.asarray(jax.random.uniform(g1, (H, 2), minval=0.15,
                                        maxval=0.85), np.float64)
    ang = np.asarray(jax.random.uniform(g2, (H,), minval=0.0,
                                        maxval=2 * np.pi), np.float64)
    vel = np.stack([np.cos(ang), np.sin(ang)], 1) * cfg["speed"] / (T - 1)
    ii, jj = np.arange(n1) / n1, np.arange(n2) / n2
    w2 = 2 * cfg["width"] ** 2
    out = []
    for t in range(T):
        q = (pos + vel * t) % 2.0
        q = np.where(q > 1.0, 2.0 - q, q)
        s = sum(np.exp(-(ii - q[h, 0]) ** 2 / w2).sum()
                * np.exp(-(jj - q[h, 1]) ** 2 / w2).sum() for h in range(H))
        out.append(cfg["base"] * (n1 * n2 + cfg["amplitude"] * s))
    assert gen.INT32_TOTAL_LIMIT == 2 ** 31
    return np.array(out)


def test_2d_totals_fit_int32_at_cell_size():
    """Every pool the committed 2D cells make, at the committed size:
    the mean total plus 10 Poisson standard deviations (and the floor
    at 1, under one count per cell) stays below 2**31."""
    spec = benchtiny.spec()
    for w in spec["workloads"]:
        cell = benchspec.Cell(spec, w["name"])
        if cell.config["generator"] != "hotspot2d":
            continue
        T = cell.traffic["pool_calls"] * cell.traffic["frames_per_call"]
        tot = _expected_totals(cell.config, T)
        cells = cell.config["frame"]["n1"] * cell.config["frame"]["n2"]
        assert (tot + 10 * np.sqrt(tot) + cells).max() < 2 ** 31, w["name"]
        assert tot.min() > 1.0e9


def test_total_ok_flags_an_overflowing_frame():
    from benchlib import spec as spec_mod
    gen = spec_mod.load_module(benchtiny.BENCH / "gen" / "hotspot2d.py")
    ok = jnp.full((1, 4, 4), 2 ** 26, jnp.int32)
    assert bool(gen.total_ok(ok))
    big = jnp.full((1, 8, 8), 2 ** 26, jnp.int32)   # 64 * 2**26 = 2**32
    assert not bool(gen.total_ok(big))


def test_configs_are_data():
    for path in (benchtiny.BENCH / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        assert path.stem == cfg["name"]
        assert (benchtiny.BENCH / "gen" / f"{cfg['generator']}.py").exists()
        assert (benchtiny.BENCH / "reference"
                / f"{cfg['reference']}.py").exists()
