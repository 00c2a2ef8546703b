"""repro.rebalance: batched device partitioning + streaming runtime."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import device, prefix
from repro.dist import cp_balance
from repro.rebalance import batch_device, migrate, policy, runtime, stream
from repro.serve import batcher

P, M = 4, 12


def _plans(frames):
    batched = batch_device.plan_stream(jnp.asarray(frames), P=P, m=M)
    plans = batch_device.unstack_plans(batched, frames.shape[1:])
    # every device plan must pass the structural validator
    for t, pl in enumerate(plans):
        pl.validate(prefix.prefix_sum_2d(frames[t]), m=M)
    return plans


# ---------------------------------------------------------------------------
# streams


def test_streams_shapes_and_positivity():
    for name, gen in stream.STREAMS.items():
        frames = gen(5, 24, 20, seed=3)
        assert frames.shape == (5, 24, 20), name
        assert frames.dtype == np.int64, name
        assert (frames > 0).all(), name


def test_static_stream_is_static():
    frames = stream.static(4, 16, 16)
    assert (frames == frames[0]).all()


# ---------------------------------------------------------------------------
# batched device partitioner


def test_batch_bit_identical_to_looped(rng):
    """Acceptance: per-frame cuts bit-identical to looped jag_m_heur_device
    on >= 50 randomized instances."""
    T, n = 50, 32
    frames = rng.integers(1, 1000, (T, n, n)).astype(np.int64)
    gammas = batch_device.gamma_batch(jnp.asarray(frames))
    rc_b, ct_b, cc_b, L_b = batch_device.jag_m_heur_batch(gammas, P=P, m=M)
    for t in range(T):
        rc, ct, cc, L = device.jag_m_heur_device(gammas[t], P=P, m=M)
        assert (np.asarray(rc) == np.asarray(rc_b[t])).all()
        assert (np.asarray(ct) == np.asarray(ct_b[t])).all()
        assert (np.asarray(cc) == np.asarray(cc_b[t])).all()
        assert np.asarray(L) == np.asarray(L_b[t])


def test_single_compilation_for_all_frames():
    frames = jnp.asarray(stream.drifting_hotspot(6, 16, 16, seed=2))
    before = batch_device.plan_stream._cache_size()
    batch_device.plan_stream(frames, P=2, m=4)
    batch_device.plan_stream(frames, P=2, m=4)
    assert batch_device.plan_stream._cache_size() == before + 1


def test_plan_stream_one_jit_boundary():
    """Regression: plan_stream composes the *unjitted* stage bodies, so one
    (shape, P, m) signature triggers exactly one XLA compilation and never
    routes through the standalone jitted stage wrappers' caches."""
    import logging

    class _CompileCounter(logging.Handler):
        def __init__(self):
            super().__init__()
            self.n = 0

        def emit(self, record):
            if "Finished XLA compilation" in record.getMessage():
                self.n += 1

    frames = jnp.asarray(stream.drifting_hotspot(3, 17, 13, seed=6))
    stage_caches = (batch_device.gamma_batch._cache_size(),
                    batch_device.jag_m_heur_batch._cache_size())
    counter = _CompileCounter()
    logger = logging.getLogger("jax._src.dispatch")
    logger.addHandler(counter)
    try:
        with jax.log_compiles():
            batch_device.plan_stream(frames, P=2, m=5)
            first = counter.n
            batch_device.plan_stream(frames, P=2, m=5)
            second = counter.n - first
    finally:
        logger.removeHandler(counter)
    assert first == 1, f"expected exactly one XLA compilation, got {first}"
    assert second == 0, f"cached signature recompiled {second}x"
    assert (batch_device.gamma_batch._cache_size(),
            batch_device.jag_m_heur_batch._cache_size()) == stage_caches


def test_owner_map_vectorized_matches_loop(rng):
    """Property: the vectorized owner map / loads equal the per-stripe
    reference construction on random plans of random geometry."""
    def owner_map_loop(p):
        own = np.empty(p.shape, dtype=np.int32)
        base = 0
        for s in range(len(p.counts)):
            r0, r1 = int(p.row_cuts[s]), int(p.row_cuts[s + 1])
            cc = p.stripe_col_cuts(s)
            band = np.repeat(base + np.arange(len(cc) - 1, dtype=np.int32),
                             np.diff(cc))
            own[r0:r1, :] = band[None, :]
            base += len(cc) - 1
        return own

    def loads_loop(p, gamma):
        out = np.empty(p.m, dtype=np.asarray(gamma).dtype)
        base = 0
        for s in range(len(p.counts)):
            r0, r1 = int(p.row_cuts[s]), int(p.row_cuts[s + 1])
            cc = p.stripe_col_cuts(s)
            band = gamma[r1, cc] - gamma[r0, cc]
            out[base:base + len(cc) - 1] = np.diff(band)
            base += len(cc) - 1
        return out

    for _ in range(8):
        n1 = int(rng.integers(8, 40))
        n2 = int(rng.integers(8, 40))
        T = int(rng.integers(1, 4))
        Pp = int(rng.integers(2, 6))
        mm = int(rng.integers(Pp + 1, Pp + 9))
        frames = rng.integers(1, 500, (T, n1, n2)).astype(np.int64)
        batched = batch_device.plan_stream(jnp.asarray(frames), P=Pp, m=mm)
        for t, p in enumerate(batch_device.unstack_plans(batched,
                                                         (n1, n2))):
            np.testing.assert_array_equal(p.owner_map(), owner_map_loop(p))
            g = prefix.prefix_sum_2d(frames[t])
            np.testing.assert_array_equal(p.loads(g), loads_loop(p, g))


def test_every_frame_covers_grid(rng):
    """Property: every frame's cuts cover [0, n) — valid disjoint cover."""
    for name in ("drifting-hotspot", "refinement-bursts"):
        frames = stream.STREAMS[name](4, 20, 28, seed=5)
        for p in _plans(frames):
            n1, n2 = p.shape
            rc = p.row_cuts
            assert rc[0] == 0 and rc[-1] == n1 and (np.diff(rc) >= 0).all()
            for s in range(len(p.counts)):
                cc = p.stripe_col_cuts(s)
                assert cc[0] == 0 and cc[-1] == n2
                assert (np.diff(cc) >= 0).all()
            assert p.to_partition().is_valid()
            assert p.m == M


def test_plan_loads_match_partition(rng):
    frames = stream.particle_advection(3, 24, 24, n_particles=20_000, seed=1)
    for t, p in enumerate(_plans(frames)):
        g = prefix.prefix_sum_2d(frames[t])
        np.testing.assert_array_equal(
            np.sort(p.loads(g)), np.sort(p.to_partition().loads(g)))
        assert p.loads(g).sum() == g[-1, -1]


def test_gamma_dtype_f64_exact_on_large_loads(rng):
    """f32 prefix sums saturate above 2**24; gamma_dtype=f64 stays exact."""
    A = rng.integers(1 << 20, 1 << 22, (24, 24)).astype(np.int64)
    g = prefix.prefix_sum_2d(A)  # int64, total ~1.7e9 >> 2**24
    with jax.enable_x64(True):
        rc, ct, cc, L = device.jag_m_heur_device(
            jnp.asarray(g, jnp.float64), P=3, m=8, gamma_dtype=jnp.float64)
        p = batch_device.Plan(np.asarray(rc), np.asarray(ct),
                              np.asarray(cc), A.shape)
        # realized bottleneck is exact: f64 represents these integers
        assert float(np.asarray(L)) == float(p.loads(g).max())


# ---------------------------------------------------------------------------
# migration


def test_migration_zero_and_symmetric(rng):
    frames = stream.drifting_hotspot(3, 24, 24, seed=7)
    a, b = _plans(frames)[:2]
    assert migrate.migration_volume(a, a) == 0.0
    assert migrate.migration_volume(b, b, weights=frames[1]) == 0.0
    v_ab = migrate.migration_volume(a, b, weights=frames[1])
    v_ba = migrate.migration_volume(b, a, weights=frames[1])
    assert v_ab == v_ba
    assert 0.0 <= v_ab <= frames[1].sum()


def test_migration_churn_consistency(rng):
    frames = stream.refinement_bursts(3, 20, 20, seed=9)
    a, b = _plans(frames)[:2]
    churn = migrate.per_processor_churn(a, b, weights=frames[1])
    vol = migrate.migration_volume(a, b, weights=frames[1])
    assert np.isclose(churn["outflow"].sum(), vol)
    assert np.isclose(churn["inflow"].sum(), vol)
    assert churn["max_link"] <= vol + 1e-9
    flow = migrate.migration_matrix(a, b, weights=frames[1])
    assert (np.diag(flow) == 0).all()


# ---------------------------------------------------------------------------
# policy + runtime


def test_hysteresis_never_triggers_on_static_stream():
    frames = stream.static(10, 24, 24)
    res = runtime.run_stream(frames, policy.HysteresisPolicy(),
                             P=P, m=M, alpha=0.25)
    assert res.n_replans == 0
    assert res.migration_cost == 0.0
    # ... even with a zero dead-band: the excess itself is exactly 0
    res0 = runtime.run_stream(frames, policy.HysteresisPolicy(band=0.0),
                              P=P, m=M, alpha=0.25)
    assert res0.n_replans == 0


def test_every_k_cadence():
    frames = stream.static(9, 16, 16)
    res = runtime.run_stream(frames, policy.EveryK(4), P=2, m=4)
    assert [r.step for r in res.records if r.replanned] == [0, 4, 8]


def test_hysteresis_beats_both_baselines():
    """Acceptance: strictly lower (migration + imbalance) total cost than
    never-rebalance and every-step-rebalance on the drifting hotspot."""
    frames = stream.drifting_hotspot(32, 48, 48, seed=0)
    res = runtime.compare_policies(
        frames,
        {"never": policy.NeverRebalance(),
         "always": policy.AlwaysRebalance(),
         "hyst": policy.HysteresisPolicy()},
        P=P, m=16, alpha=0.25, replan_overhead=1000.0)
    hyst = res["hyst"].total_cost
    assert hyst < res["never"].total_cost
    assert hyst < res["always"].total_cost
    # and it does so by replanning, but not every step
    assert 0 < res["hyst"].n_replans < len(frames) - 1


def test_run_stream_cost_accounting():
    frames = stream.drifting_hotspot(6, 24, 24, seed=4)
    res = runtime.run_stream(frames, policy.AlwaysRebalance(), P=2, m=6,
                             alpha=0.5, replan_overhead=10.0)
    assert len(res.records) == 6
    assert res.records[0].migration_cost == 0.0  # initial plan is free
    for r in res.records[1:]:
        assert r.replanned
        assert np.isclose(r.migration_cost, 10.0 + 0.5 * r.migration_volume)
    assert np.isclose(res.total_cost,
                      res.compute_cost + res.migration_cost)


# ---------------------------------------------------------------------------
# warm-started consumers


def test_batcher_replan_matches_scratch(rng):
    for _ in range(10):
        reqs = [batcher.Request(i, int(rng.integers(1, 2000)))
                for i in range(int(rng.integers(8, 60)))]
        assignments = batcher.plan(reqs, 4)
        new = [batcher.Request(1000 + i, int(rng.integers(1, 3000)))
               for i in range(int(rng.integers(0, 20)))]
        got, mode = batcher.replan(assignments, new)
        assert mode == "slow"  # unconditional optimal re-partition
        ref = batcher.plan(reqs + new, 4)
        assert [a.load for a in got] == [a.load for a in ref]
        assert sorted(r.rid for a in got for r in a.requests) == \
            sorted(r.rid for r in reqs + new)


def test_batcher_graded_replan_keeps_on_no_drift():
    """With no arrivals the keep-path IS the prior plan (excess exactly 0),
    so a graded replan never migrates queued requests."""
    reqs = [batcher.Request(i, 100 + 7 * i) for i in range(24)]
    assignments = batcher.plan(reqs, 4)
    got, mode = batcher.replan(assignments, [],
                               policy=policy.TwoPhaseHysteresis())
    assert mode == "keep"
    assert [a.load for a in got] == [a.load for a in assignments]
    assert sorted(r.rid for a in got for r in a.requests) == \
        sorted(r.rid for r in reqs)


def test_batcher_graded_replan_escalates_on_heavy_drift():
    """Unevenly drained queues (one replica still holds most of the work)
    push the keep-path far past the slow band; the escalated replan
    reaches the optimal bottleneck and every request keeps one home."""
    hot = batcher.Assignment(0, [batcher.Request(i, 1000)
                                 for i in range(10)])
    cold = batcher.Assignment(1, [batcher.Request(100, 100),
                                  batcher.Request(101, 100)])
    got, mode = batcher.replan([hot, cold], [],
                               policy=policy.TwoPhaseHysteresis(
                                   horizon=8, band=0.02, slow_band=0.10))
    assert mode == "slow"
    all_reqs = hot.requests + cold.requests
    ref = batcher.plan(all_reqs, 2)
    assert max(a.load for a in got) == max(a.load for a in ref)
    assert max(a.load for a in got) < hot.load
    assert sorted(r.rid for a in got for r in a.requests) == \
        sorted(r.rid for r in all_reqs)


def test_batcher_plain_policy_never_escalates():
    """A decide()-only policy grades through replan_mode as fast-or-keep."""
    reqs = [batcher.Request(i, 100) for i in range(12)]
    assignments = batcher.plan(reqs, 3)
    new = [batcher.Request(50 + i, 5000) for i in range(3)]
    got, mode = batcher.replan(assignments, new,
                               policy=policy.HysteresisPolicy(band=0.0))
    assert mode in ("keep", "fast")
    assert sorted(r.rid for a in got for r in a.requests) == \
        sorted(r.rid for r in reqs + new)


def test_replan_mode_grading():
    st = dict(step=1, total_load=1000.0, achieved_at_replan=100.0,
              total_at_replan=1000.0, steps_since_replan=1,
              last_migration_volume=0.0, alpha=0.0, replan_overhead=0.0)
    calm = policy.StepState(max_load=100.0, ideal=100.0, **st)
    hot = policy.StepState(max_load=130.0, ideal=100.0, **st)
    blazing = policy.StepState(max_load=200.0, ideal=100.0, **st)
    two = policy.TwoPhaseHysteresis(band=0.02, slow_band=0.5)
    assert policy.replan_mode(two, calm) == "keep"
    assert policy.replan_mode(two, hot) == "fast"
    assert policy.replan_mode(two, blazing) == "slow"
    plain = policy.HysteresisPolicy(band=0.02)
    assert policy.replan_mode(plain, calm) == "keep"
    assert policy.replan_mode(plain, blazing) == "fast"


def test_cp_replan_static_keeps_plan():
    cuts = cp_balance.balanced_plan(64, 8)
    out, replanned = cp_balance.replan_contiguous(cuts, 64)
    assert not replanned
    assert (out == cuts).all()


def test_cp_replan_grown_context_matches_scratch():
    cuts = cp_balance.balanced_plan(64, 8)
    out, replanned = cp_balance.replan_contiguous(cuts, 96)
    assert replanned
    assert (out == cp_balance.balanced_plan(96, 8)).all()


def test_cp_replan_chained_growth_tracks_optimum():
    """Feeding returned cuts back step-by-step (the decode loop) must keep
    tracking the fresh optimum, not silently stop replanning."""
    cuts = cp_balance.balanced_plan(64, 8)
    replans = 0
    for n in range(65, 1025):
        cuts, rp = cp_balance.replan_contiguous(cuts, n)
        replans += rp
    li = cp_balance.plan_imbalance(cuts, 1024, 8)
    ref = cp_balance.plan_imbalance(cp_balance.balanced_plan(1024, 8),
                                    1024, 8)
    assert 0 < replans < 1024 - 64
    assert li <= ref + 0.05  # within the hysteresis band of fresh-optimal
    # pricing migration thins the replans without losing tracking
    cuts2, costly = cp_balance.balanced_plan(64, 8), 0
    for n in range(65, 1025):
        cuts2, rp = cp_balance.replan_contiguous(
            cuts2, n, alpha=1.0, last_migration_volume=200.0)
        costly += rp
    assert costly < replans
    assert cp_balance.plan_imbalance(cuts2, 1024, 8) <= ref + 0.25


def test_oned_warm_start_equivalence(rng):
    from repro.core import oned
    for _ in range(20):
        n = int(rng.integers(5, 200))
        m = int(rng.integers(2, 12))
        a = rng.integers(1, 1000, n).astype(np.int64)
        p = np.concatenate([[0], np.cumsum(a)])
        ref = oned.probe_bisect_optimal(p, m)
        ref_L = oned.max_interval_load(p, ref)
        for warm in (ref_L, ref_L * 0.5, ref_L * 2.0, 1.0, float(p[-1])):
            got = oned.probe_bisect_optimal(p, m, warm=warm)
            assert oned.max_interval_load(p, got) == ref_L, warm
