"""Serving batcher + paper-technique integration layers (MoE/CP)."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import oned
from repro.dist import cp_balance, moe_placement
from repro.serve import batcher


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(1, 2048), min_size=1, max_size=60),
       st.integers(1, 8))
def test_batcher_optimal_beats_direct(lens, R):
    reqs = [batcher.Request(i, l) for i, l in enumerate(lens)]
    opt = batcher.plan(reqs, R, algo="optimal")
    dc = batcher.plan(reqs, R, algo="direct")
    assert sum(len(a.requests) for a in opt) == len(reqs)
    assert batcher.imbalance(opt) <= batcher.imbalance(dc) + 1e-9
    # DC bound: max load <= avg + max element
    total = sum(lens)
    assert max(a.load for a in dc) <= total / R + max(lens) + 1e-9


def test_straggler_rebalance_covers_remaining():
    reqs = [batcher.Request(i, 100 + i) for i in range(40)]
    plan = batcher.plan(reqs, 4)
    re = batcher.straggler_rebalance(plan, [1.0, 0.5, 0.0, 0.9])
    remaining = sum(len(a.requests) for a in re)
    expect = (len(plan[1].requests) - int(len(plan[1].requests) * 0.5)
              ) + len(plan[2].requests) + (
        len(plan[3].requests) - int(len(plan[3].requests) * 0.9))
    assert remaining == expect


def test_straggler_rebalance_length_mismatch_raises():
    """A short progress list used to be zip-truncated, silently dropping
    whole replicas' queues from the rebalanced plan; both directions of
    the mismatch must raise instead."""
    reqs = [batcher.Request(i, 100 + i) for i in range(12)]
    plan = batcher.plan(reqs, 4)
    with pytest.raises(ValueError, match="every replica must report"):
        batcher.straggler_rebalance(plan, [1.0, 0.5, 0.0])
    with pytest.raises(ValueError, match="every replica must report"):
        batcher.straggler_rebalance(plan, [1.0, 0.5, 0.0, 0.9, 0.2])


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(1, 2048), min_size=1, max_size=60),
       st.integers(1, 8))
def test_direct_cut_speeds_uniform_matches_direct_cut(lens, R):
    """At uniform speeds the capacity-proportional DirectCut degenerates to
    the paper's DirectCut — same targets, same searchsorted — so the cuts
    must be bit-identical."""
    p = np.concatenate([[0], np.cumsum(np.asarray(lens, dtype=np.int64))])
    got = batcher._direct_cut_speeds(p, np.ones(R, dtype=np.float64))
    want = oned.direct_cut(p, R)
    np.testing.assert_array_equal(got, want)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(1, 2048), min_size=1, max_size=60),
       st.integers(2, 8), st.integers(0, 100))
def test_direct_cut_speeds_dead_replica_and_coverage(lens, R, dead_seed):
    """Dead (speed=0) replicas get exactly empty ranges; the cuts always
    cover [0, n] monotonically so every request lands exactly once."""
    dead = dead_seed % R
    sp = np.ones(R, dtype=np.float64)
    sp[dead] = 0.0
    p = np.concatenate([[0], np.cumsum(np.asarray(lens, dtype=np.int64))])
    cuts = batcher._direct_cut_speeds(p, sp)
    n = len(p) - 1
    assert cuts[0] == 0 and cuts[-1] == n
    assert (np.diff(cuts) >= 0).all()
    assert cuts[dead + 1] == cuts[dead], "dead replica must get no requests"
    # live replicas partition the full range: total assigned == total work
    assigned = sum(int(p[cuts[i + 1]] - p[cuts[i]]) for i in range(R))
    assert assigned == int(p[-1])


def test_imbalance_edge_cases():
    """``imbalance`` is total on its domain: empty lists and all-empty
    queues are defined (0.0), never a ``max()``/ZeroDivision crash."""
    assert batcher.imbalance([]) == 0.0
    assert batcher.imbalance([batcher.Assignment(0, [])]) == 0.0
    assert batcher.imbalance([batcher.Assignment(i, [])
                              for i in range(4)]) == 0.0
    one = [batcher.Assignment(0, [batcher.Request(0, 7)])]
    assert batcher.imbalance(one) == 0.0
    assert batcher.replica_loads([]).size == 0


def _greedy_extend_scan(assignments, new_requests, speeds=None):
    """The pre-heap reference: linear min-scan per arrival."""
    from repro.core import search
    sp = search.normalize_speeds(speeds, len(assignments))
    out = [batcher.Assignment(a.replica, list(a.requests))
           for a in assignments]
    live = [i for i in range(len(out)) if sp is None or sp[i] > 0]
    rel = {i: out[i].load / (1.0 if sp is None else sp[i]) for i in live}
    for r in sorted(new_requests, key=lambda r: r.prompt_tokens,
                    reverse=True):
        i = min(live, key=lambda j: rel[j])
        out[i].requests.append(r)
        rel[i] += r.prompt_tokens / (1.0 if sp is None else sp[i])
    return out


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(1, 1000), min_size=0, max_size=40),
       st.lists(st.integers(1, 1000), min_size=1, max_size=40),
       st.integers(1, 8))
def test_greedy_extend_heap_matches_scan(base, arrivals, R):
    """Satellite: the heap-based ``_greedy_extend`` assigns identically to
    the linear min-scan it replaced.  Loads are perturbed to distinct
    floats via speeds so ties cannot mask an ordering bug; the uniform
    case is additionally covered tie-free by construction below."""
    reqs = [batcher.Request(i, t) for i, t in enumerate(base)]
    plan = batcher.plan(reqs, R) if reqs else \
        [batcher.Assignment(i, []) for i in range(R)]
    new = [batcher.Request(1000 + i, t) for i, t in enumerate(arrivals)]
    got = batcher._greedy_extend(plan, new)
    want = _greedy_extend_scan(plan, new)
    for a, b in zip(got, want):
        assert [r.rid for r in a.requests] == [r.rid for r in b.requests]
    # tie-free relative loads: distinct prime-ish speeds
    sp = (1.0 + np.arange(R)) / 7.0 + 1.0
    got_s = batcher._greedy_extend(plan, new, speeds=sp)
    want_s = _greedy_extend_scan(plan, new, speeds=sp)
    for a, b in zip(got_s, want_s):
        assert [r.rid for r in a.requests] == [r.rid for r in b.requests]


class _FixedMode:
    """Policy stub pinning replan_mode's grade (has ``mode``, so the
    shared decision point takes the graded branch)."""

    def __init__(self, mode):
        self._mode = mode

    def mode(self, state):
        return self._mode


def _mixed_ring_plan():
    """A mixed-speed ring: two fast replicas flanking two slow ones, plus
    a dead one appended — the capacity shape the satellite pins."""
    sp = np.array([2.0, 1.0, 1.0, 2.0, 0.0])
    rng = np.random.default_rng(11)
    reqs = [batcher.Request(i, int(t))
            for i, t in enumerate(rng.integers(1, 512, size=48))]
    return batcher.plan(reqs, 5, speeds=sp), sp


def test_replan_speeds_fast_path_is_direct_cut_speeds():
    """Satellite: under ``speeds`` the fast grade must be the
    capacity-proportional DirectCut — identical assignment sizes and
    loads to ``plan(algo='direct', speeds=...)``."""
    plan0, sp = _mixed_ring_plan()
    arrivals = [batcher.Request(100 + i, 64 + i) for i in range(16)]
    got, mode = batcher.replan(plan0, arrivals, policy=_FixedMode("fast"),
                               speeds=sp)
    assert mode == "fast"
    reqs = [r for a in plan0 for r in a.requests] + arrivals
    want = batcher.plan(reqs, 5, algo="direct", speeds=sp)
    assert [len(a.requests) for a in got] == [len(a.requests)
                                             for a in want]
    assert [a.load for a in got] == [a.load for a in want]
    assert got[4].load == 0  # dead replica stays empty


def test_replan_speeds_slow_path_is_capacity_optimal():
    plan0, sp = _mixed_ring_plan()
    arrivals = [batcher.Request(100 + i, 64 + i) for i in range(16)]
    got, mode = batcher.replan(plan0, arrivals, policy=_FixedMode("slow"),
                               speeds=sp)
    assert mode == "slow"
    reqs = [r for a in plan0 for r in a.requests] + arrivals
    want = batcher.plan(reqs, 5, algo="optimal", speeds=sp)
    assert [a.load for a in got] == [a.load for a in want]
    # capacity-aware: relative bottleneck never worse than the fast path
    fast = batcher.plan(reqs, 5, algo="direct", speeds=sp)
    live = sp > 0
    rel = lambda pl: max(a.load / s for a, s in zip(pl, sp) if s > 0)  # noqa: E731
    assert rel(got) <= rel(fast) + 1e-9
    assert got[4].load == 0 and not live[4]


def test_replan_speeds_keep_path_extends_lpt_no_migration():
    plan0, sp = _mixed_ring_plan()
    arrivals = [batcher.Request(100 + i, 64 + i) for i in range(16)]
    got, mode = batcher.replan(plan0, arrivals, policy=_FixedMode("keep"),
                               speeds=sp)
    assert mode == "keep"
    # zero migration: every previously queued request kept its replica
    for old, new in zip(plan0, got):
        old_ids = [r.rid for r in old.requests]
        assert [r.rid for r in new.requests][:len(old_ids)] == old_ids
    # dead replica received no arrivals
    assert [r.rid for r in got[4].requests] == \
        [r.rid for r in plan0[4].requests]
    assert sum(len(a.requests) for a in got) == \
        sum(len(a.requests) for a in plan0) + len(arrivals)


def test_replan_policy_none_honors_speeds_and_warm():
    """The ungraded path also stays capacity-aware: same cuts as a scratch
    capacity plan, with the prior bottleneck warm-seeding the bisection."""
    plan0, sp = _mixed_ring_plan()
    arrivals = [batcher.Request(100 + i, 32 + i) for i in range(8)]
    got, mode = batcher.replan(plan0, arrivals, speeds=sp)
    assert mode == "slow"
    reqs = [r for a in plan0 for r in a.requests] + arrivals
    want = batcher.plan(reqs, 5, algo="optimal", speeds=sp)
    assert [a.load for a in got] == [a.load for a in want]


def test_moe_placement_beats_uniform():
    counts = moe_placement.simulate_router_counts(16, 32, skew=1.2)
    plan = moe_placement.plan_expert_placement(counts, 16)
    assert plan.partition.is_valid()
    assert plan.load_imbalance < plan.uniform_imbalance


def test_cp_balanced_beats_contiguous():
    nb, R = 64, 8
    naive = cp_balance.plan_imbalance(
        cp_balance.contiguous_plan(nb, R), nb, R)
    bal = cp_balance.plan_imbalance(
        cp_balance.balanced_plan(nb, R), nb, R)
    zig = cp_balance.plan_imbalance(
        cp_balance.interleaved_assignment(nb, R), nb, R, contiguous=False)
    # contiguous equal-count split is ~2x imbalanced; optimal-contiguous is
    # far better. The non-contiguous zig-zag can reach exactly 0 (pairs
    # block i with 2R-1-i) — the balanced plan's value is that it is
    # optimal *among contiguous ranges*, which preserve KV locality
    # (the paper's rectangles-for-communication argument).
    assert naive > 0.5
    assert bal < naive / 3
    assert zig <= bal + 1e-9


def test_cp_windowed_costs():
    c = cp_balance.block_costs(10, window_blocks=3)
    assert list(c[:4]) == [1, 2, 3, 3]


def test_sharding_specs_divisible():
    """Every param/cache spec divides its dims on the production meshes."""
    import jax
    from jax.sharding import PartitionSpec as P
    import repro.configs as configs
    from repro.dist import ctx, sharding as shd
    from repro.models import api

    for multi in (False, True):
        shape = (2, 16, 16) if multi else (16, 16)
        axes = ("pod", "data", "model") if multi else ("data", "model")
        # jax 0.4.x/0.5.x AbstractMesh signatures differ; ctx papers over it
        mesh = ctx.abstract_mesh(shape, axes)
        sizes = dict(zip(axes, shape))
        for arch in configs.ARCHS:
            cfg = configs.get(arch)
            pspec = api.param_spec(cfg)
            specs = shd.param_specs(cfg, mesh, pspec)
            for leaf, sp in zip(jax.tree.leaves(pspec),
                                jax.tree.leaves(
                                    specs, is_leaf=lambda x: isinstance(
                                        x, P))):
                for dim, ax in zip(leaf.shape, tuple(sp)):
                    if ax is None:
                        continue
                    names = ax if isinstance(ax, tuple) else (ax,)
                    k = 1
                    for n in names:
                        k *= sizes[n]
                    assert dim % k == 0, (arch, leaf.shape, tuple(sp))
