"""Property-based invariants of the ``repro.dist`` subsystem."""
import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from hypothesis import given, settings, strategies as st

import repro.configs as configs
from repro.dist import cp_balance, ctx, moe_placement, sharding as shd
from repro.models import api


# ---------------------------------------------------------------------------
# cp_balance: every plan covers all blocks exactly once


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 300), st.integers(1, 16), st.integers(0, 12))
def test_cp_plans_cover_all_blocks_exactly_once(nb, R, w):
    for cuts in (cp_balance.contiguous_plan(nb, R),
                 cp_balance.balanced_plan(nb, R, window_blocks=w)):
        assert len(cuts) == R + 1
        assert cuts[0] == 0 and cuts[-1] == nb
        assert (np.diff(cuts) >= 0).all()  # disjoint contiguous cover
    owner = cp_balance.interleaved_assignment(nb, R)
    assert owner.shape == (nb,)  # a block -> rank *function*: exactly once
    assert ((owner >= 0) & (owner < R)).all()


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 300), st.integers(1, 16), st.integers(0, 12))
def test_cp_balanced_optimal_among_contiguous(nb, R, w):
    """The engine-driven plan never loses to the equal-count split, and
    its bottleneck is >= the trivial lower bounds (avg, max element)."""
    bal = cp_balance.balanced_plan(nb, R, window_blocks=w)
    naive = cp_balance.contiguous_plan(nb, R)
    ib = cp_balance.plan_imbalance(bal, nb, R, window_blocks=w)
    inaive = cp_balance.plan_imbalance(naive, nb, R, window_blocks=w)
    assert ib <= inaive + 1e-9
    c = cp_balance.block_costs(nb, w)
    p = np.concatenate([[0], np.cumsum(c)])
    lmax = float((p[bal[1:]] - p[bal[:-1]]).max(initial=0))
    assert lmax >= max(float(c.sum()) / R, float(c.max(initial=0))) - 1e-9


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 300), st.integers(1, 16), st.integers(0, 12))
def test_cp_two_phase_valid_and_between_bounds(nb, R, w):
    """The HYBRID-shaped two-phase split is a valid contiguous cover,
    never better than the exact split and never worse than equal-count."""
    if R > nb:
        R = nb
    tp = cp_balance.balanced_plan_two_phase(nb, R, window_blocks=w)
    assert len(tp) == R + 1
    assert tp[0] == 0 and tp[-1] == nb
    assert (np.diff(tp) >= 0).all()
    i_tp = cp_balance.plan_imbalance(tp, nb, R, window_blocks=w)
    i_opt = cp_balance.plan_imbalance(
        cp_balance.balanced_plan(nb, R, window_blocks=w), nb, R,
        window_blocks=w)
    i_naive = cp_balance.plan_imbalance(
        cp_balance.contiguous_plan(nb, R), nb, R, window_blocks=w)
    assert i_opt - 1e-12 <= i_tp <= i_naive + 1e-9


def test_cp_phase_aware_replan_modes():
    """TwoPhaseHysteresis grades the replan: static contexts keep, grown
    contexts adopt the fast two-phase split, and large excess escalates
    to the exact split warm-seeded at the two-phase bottleneck."""
    from repro.rebalance.policy import TwoPhaseHysteresis

    cuts = cp_balance.balanced_plan(64, 8)
    out, replanned = cp_balance.replan_contiguous(
        cuts, 64, two_phase=True, policy=TwoPhaseHysteresis())
    assert not replanned and (out == cuts).all()
    # a 50% context growth leaves the extension far above ideal: slow mode
    out, replanned = cp_balance.replan_contiguous(
        cuts, 96, two_phase=True, policy=TwoPhaseHysteresis())
    assert replanned
    np.testing.assert_array_equal(out, cp_balance.balanced_plan(96, 8))
    # an unreachable slow band stays in fast mode: two-phase cuts adopted
    out, replanned = cp_balance.replan_contiguous(
        cuts, 96, two_phase=True, policy=TwoPhaseHysteresis(slow_band=1e9))
    assert replanned
    np.testing.assert_array_equal(out,
                                  cp_balance.balanced_plan_two_phase(96, 8))


# ---------------------------------------------------------------------------
# moe_placement: valid partitions, never worse than the uniform grid


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 24), st.integers(2, 24), st.integers(2, 20),
       st.integers(0, 10**6))
def test_moe_plans_valid_and_never_worse_than_uniform(L, E, ranks, seed):
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, 500, (L, E)).astype(np.int64)
    plan = moe_placement.plan_expert_placement(counts, ranks)
    assert plan.partition.is_valid()
    assert plan.partition.shape == (L, E)
    assert plan.load_imbalance <= plan.uniform_imbalance + 1e-9
    # reported imbalance is honest: recompute from the raw counts
    loads = [counts[r.r0:r.r1, r.c0:r.c1].sum() for r in plan.partition.rects]
    avg = counts.sum() / ranks
    assert plan.load_imbalance == (max(loads) / avg - 1.0 if avg else 0.0)


# ---------------------------------------------------------------------------
# sharding: specs divide dims for randomized mesh shapes


def _assert_divisible(shapes_tree, specs, sizes):
    for leaf, sp in zip(
            jax.tree.leaves(shapes_tree),
            jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))):
        assert isinstance(sp, P)
        assert len(tuple(sp)) <= len(leaf.shape)
        for dim, ax in zip(leaf.shape, tuple(sp)):
            if ax is None:
                continue
            names = ax if isinstance(ax, tuple) else (ax,)
            k = 1
            for n in names:
                k *= sizes[n]
            assert dim % k == 0, (leaf.shape, tuple(sp))


@settings(max_examples=6, deadline=None)
@given(st.integers(1, 3), st.integers(1, 12), st.integers(1, 12),
       st.integers(0, len(configs.ARCHS) - 1))
def test_sharding_specs_divide_on_random_meshes(pod, data, model, ai):
    axes = ("data", "model") if pod == 1 else ("pod", "data", "model")
    shape = (data, model) if pod == 1 else (pod, data, model)
    mesh = ctx.abstract_mesh(shape, axes)
    sizes = dict(zip(axes, shape))
    cfg = configs.get_smoke(configs.ARCHS[ai])
    pspec = api.param_spec(cfg)
    for fsdp in (True, False):
        _assert_divisible(pspec, shd.param_specs(cfg, mesh, pspec,
                                                 fsdp=fsdp), sizes)
    batch = api.train_batch_spec(cfg, 8, 64)
    _assert_divisible(batch, shd.batch_specs(cfg, mesh, batch), sizes)
    cspec = api.cache_spec(cfg, 8, 64)
    _assert_divisible(cspec, shd.cache_specs(cfg, mesh, cspec), sizes)


# ---------------------------------------------------------------------------
# ctx: logical-axis resolution


def test_ctx_resolve_and_mesh_context():
    mesh = ctx.abstract_mesh((2, 4, 3), ("pod", "data", "model"))
    sp = ctx.resolve(mesh, ("dp", None, "model"), shape=(16, 5, 9))
    assert tuple(sp) == (("pod", "data"), None, "model")
    # divisibility safety: drop axes that do not divide the dim
    sp = ctx.resolve(mesh, ("dp", "model"), shape=(12, 5))
    assert tuple(sp) == (None, None)
    single = ctx.abstract_mesh((4, 3), ("data", "model"))
    sp = ctx.resolve(single, ("dp", "model"), shape=(12, 9))
    assert tuple(sp) == ("data", "model")
    assert ctx.current_mesh() is None
    with ctx.mesh_context(mesh) as m:
        assert ctx.current_mesh() is m
        with ctx.mesh_context(single):
            assert ctx.current_mesh() is single
        assert ctx.current_mesh() is m
    assert ctx.current_mesh() is None


def test_constrain_is_identity_without_mesh():
    x = np.arange(6.0).reshape(2, 3)
    assert ctx.constrain(x, "dp", "model") is x
