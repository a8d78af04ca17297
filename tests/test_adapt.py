"""Online adaptive control plane: telemetry -> calibration -> drift
detection -> Preserver-gated replan -> DeftRuntime hot-swap.

The acceptance test at the bottom runs the whole loop against the real
fused runtime with a synthetic bandwidth drop injected mid-run and
asserts the final parameters BIT-MATCH a reference run that executes the
same effective phase sequence (old schedule up to the swap boundary, new
schedule after) — the hot-swap is semantically a pure re-planning, never
a perturbation of training state.
"""
import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.adapt import (
    AdaptConfig,
    AdaptiveController,
    BandwidthDrop,
    SyntheticTelemetrySource,
    Telemetry,
    TelemetryConfig,
    calibrate,
    fit_scales,
    run_control_loop,
    scale_times,
    schedule_plans,
    steady_phase_durations,
)
from repro.adapt.calibrate import fit_horizon
from repro.configs import get_config
from repro.core.bucket import BucketTimes
from repro.core.deft import feedback_solve
from repro.core.knapsack import (
    clear_knapsack_caches,
    knapsack_cache_info,
    set_knapsack_memoization,
)
from repro.core.preserver import WalkParams
from repro.core.scheduler import DeftScheduler, SchedulerConfig
from repro.core.simulator import simulate_deft
from repro.data.pipeline import make_batch
from repro.optim.optimizers import adamw
from repro.train import (
    DeftRuntime,
    assign_buckets,
    build_bucket_layout,
    leaf_bucket_times,
)
from repro.core.profiler import HardwareModel
from repro.models.model import init_params


WALK = WalkParams(s0=4.0, eta=0.01, mu=1.0, sigma=40.0, batch=256)


def _toy_times(n=8, cr=1.8, seed=0):
    import random

    rng = random.Random(seed)
    fwd = tuple(rng.uniform(0.002, 0.02) for _ in range(n))
    bwd = tuple(2 * f for f in fwd)
    comm = tuple(rng.uniform(0.005, 0.08) for _ in range(n))
    t = BucketTimes(fwd, bwd, comm)
    scale = cr * (t.fwd_total + t.bwd_total) / t.comm_total
    return BucketTimes(fwd, bwd, tuple(c * scale for c in comm))


# ---------------------------------------------------------------------------
# Telemetry
# ---------------------------------------------------------------------------
def test_telemetry_ring_bound_and_ema():
    tel = Telemetry(2, TelemetryConfig(ring_size=8, ema_alpha=0.5,
                                       warmup_steps=2))
    for i in range(20):
        tel.record(i, i % 2, 1.0 + (i % 2), loss=float(i))
    assert len(tel) == 8                      # ring bounded
    assert len(tel.losses()) == 8
    # EMA converged near the per-phase constant values
    assert tel.phase_time(0) == pytest.approx(1.0, abs=1e-6)
    assert tel.phase_time(1) == pytest.approx(2.0, abs=1e-6)
    assert tel.ready()


def test_telemetry_warmup_skip():
    tel = Telemetry(1, TelemetryConfig(warmup_steps=3))
    tel.record(0, 0, 100.0)   # compile-jitter samples must not pollute
    tel.record(1, 0, 100.0)
    tel.record(2, 0, 100.0)
    assert tel.phase_time(0) is None
    assert not tel.ready()
    tel.record(3, 0, 1.0)
    assert tel.phase_time(0) == pytest.approx(1.0)
    assert tel.ready()


def test_telemetry_rebase_keeps_losses_rearms_warmup():
    tel = Telemetry(2, TelemetryConfig(warmup_steps=1))
    for i in range(6):
        tel.record(i, i % 2, 1.0, loss=2.5)
    assert tel.ready()
    tel.rebase(3)
    assert tel.n_phases == 3
    assert not tel.ready()                    # EMAs re-keyed
    assert len(tel.losses()) == 6             # loss trace survives
    tel.record(6, 0, 1.0)                     # warm-up sample (skipped)
    tel.record(7, 1, 1.0)
    assert tel.phase_time(0) is None and tel.phase_time(1) is not None


# ---------------------------------------------------------------------------
# Calibration
# ---------------------------------------------------------------------------
def test_fit_scales_recovers_injected_degradation():
    times = _toy_times()
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    plans = schedule_plans(times, scfg, horizon=fit_horizon(schedule.period))
    for true_a, true_b in ((1.0, 3.0), (1.5, 1.0)):
        measured = steady_phase_durations(
            plans, scale_times(times, true_a, true_b), schedule.period,
            mu=scfg.mu, heterogeneous=scfg.heterogeneous,
        )
        a, b, resid = fit_scales(times, scfg, schedule.period, measured)
        assert a == pytest.approx(true_a, rel=0.15), (true_a, true_b)
        assert b == pytest.approx(true_b, rel=0.15), (true_a, true_b)


def test_fit_scales_faster_link_is_not_misread_as_drift():
    """A link FASTER than planned overlaps completely — (a, b) is only
    identifiable up to a plateau.  The fit must settle near (1, 1), not
    wander to a plateau corner that would trigger spurious replans."""
    times = _toy_times()
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    plans = schedule_plans(times, scfg, horizon=fit_horizon(schedule.period))
    measured = steady_phase_durations(
        plans, scale_times(times, 1.0, 0.5), schedule.period,
        mu=scfg.mu, heterogeneous=scfg.heterogeneous,
    )
    a, b, _ = fit_scales(times, scfg, schedule.period, measured)
    assert a == pytest.approx(1.0, rel=0.15)
    assert 0.3 <= b <= 1.1


def test_per_link_fit_recovers_secondary_only_degradation():
    """A secondary-only slowdown (slow host/DCN path congests, primary
    fabric holds) is exactly what the 2-D fit cannot express — its
    comm_scale moves both links.  The staged per-link fit (§14) must
    recover the multiplier on the secondary link and hand back
    LinkModels whose forward simulation matches the measurements at
    least as well as the 2-D fit alone."""
    from repro.core.links import LinkModel

    times = _toy_times()
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    plans = schedule_plans(times, scfg, horizon=fit_horizon(schedule.period))
    true_s = 3.0
    base = scfg.models()
    degraded = {
        lid: (m if lid == 0 else LinkModel(m.latency, m.inv_bw * true_s))
        for lid, m in base.items()
    }
    measured = steady_phase_durations(
        plans, times, schedule.period,
        mu=scfg.mu, heterogeneous=scfg.heterogeneous, link_models=degraded,
    )
    prof2d = calibrate(times, scfg, schedule.period, measured)
    prof = calibrate(times, scfg, schedule.period, measured, per_link=True)
    assert prof.link_models is not None
    # the degradation lands on the secondary link, not the joint scale:
    # comm_scale * sec_scale carries the true multiplier between them,
    # with the per-link stage providing the secondary-specific part
    assert prof.sec_scale > 1.2
    assert prof.comm_scale * prof.sec_scale == pytest.approx(
        true_s, rel=0.35
    )
    assert prof.comp_scale == pytest.approx(1.0, rel=0.15)
    # per-link forward model explains the data no worse than 2-D alone
    assert prof.residual <= prof2d.residual + 1e-12
    assert prof.drift > 0.2
    # fitted models are consumable: secondary inv_bw grew, primary fixed
    assert prof.link_models[0].inv_bw == pytest.approx(base[0].inv_bw)
    assert prof.link_models[1].inv_bw > base[1].inv_bw


def test_per_link_fit_noop_when_homogeneous_or_clean():
    times = _toy_times()
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    plans = schedule_plans(times, scfg, horizon=fit_horizon(schedule.period))
    measured = steady_phase_durations(
        plans, times, schedule.period,
        mu=scfg.mu, heterogeneous=scfg.heterogeneous,
    )
    # clean measurements: the regularized 1-D stage stays at 1.0
    prof = calibrate(times, scfg, schedule.period, measured, per_link=True)
    assert prof.sec_scale == pytest.approx(1.0, rel=0.1)
    # homogeneous config: the stage is skipped entirely
    homo = dataclasses.replace(scfg, heterogeneous=False)
    prof_h = calibrate(times, homo, schedule.period, measured, per_link=True)
    assert prof_h.sec_scale == 1.0 and prof_h.link_models is None


def test_calibrate_rebases_times_and_hardware_model():
    times = _toy_times()
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    plans = schedule_plans(times, scfg, horizon=fit_horizon(schedule.period))
    measured = steady_phase_durations(
        plans, scale_times(times, 1.0, 2.0), schedule.period,
        mu=scfg.mu, heterogeneous=scfg.heterogeneous,
    )
    hw = HardwareModel()
    prof = calibrate(times, scfg, schedule.period, measured, hw=hw)
    assert prof.drift > 0.5
    # comm times re-based up, effective bandwidth re-based down
    assert prof.times.comm_total == pytest.approx(
        times.comm_total * prof.comm_scale
    )
    assert prof.hw.ici_bw == pytest.approx(hw.ici_bw / prof.comm_scale)
    assert prof.times.coverage_rate > times.coverage_rate


def test_calibrate_no_drift_when_measurements_match_plan():
    times = _toy_times()
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    plans = schedule_plans(times, scfg, horizon=fit_horizon(schedule.period))
    measured = steady_phase_durations(
        plans, times, schedule.period,
        mu=scfg.mu, heterogeneous=scfg.heterogeneous,
    )
    prof = calibrate(times, scfg, schedule.period, measured)
    assert prof.drift < 0.05


# ---------------------------------------------------------------------------
# Controller: drift detection and replanning (pure Python, deterministic)
# ---------------------------------------------------------------------------
def _drive(ctrl, src, steps, losses=None):
    """Run the shared synthetic control loop; returns the event list."""
    return run_control_loop(ctrl, src, steps, losses=losses)


def test_controller_detects_bandwidth_drop_and_replans():
    times = _toy_times()
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    drop = BandwidthDrop(step=40, comm_scale=3.0)
    ctrl = AdaptiveController(times, schedule, scfg, walk=WALK)
    events = _drive(ctrl, SyntheticTelemetrySource(times, drop), 120)
    assert events, "no replan despite a 3x bandwidth drop"
    assert all(e.step >= drop.step for e in events), "replanned before drop"
    assert events[0].trigger == "timing-drift"
    assert events[0].profile.comm_scale > 1.2
    assert events[0].coverage_delta > 0      # degraded link -> higher CR
    # cumulative calibration converges on the injected degradation
    cum = 1.0
    for e in events:
        cum *= e.profile.comm_scale
    assert cum == pytest.approx(drop.comm_scale, rel=0.2)
    # the replanned schedule beats the stale one on the degraded link
    degraded = scale_times(times, 1.0, drop.comm_scale)
    stale = simulate_deft(
        degraded, DeftScheduler(times, scfg).run(48),
        mu=scfg.mu, heterogeneous=scfg.heterogeneous,
    )
    final = ctrl.scheduler_cfg
    adapted = simulate_deft(
        degraded, DeftScheduler(ctrl.times, final).run(48),
        mu=final.mu, heterogeneous=final.heterogeneous,
    )
    assert adapted.iteration_time <= stale.iteration_time * 1.001


def test_controller_quiet_without_drift():
    times = _toy_times()
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    src = SyntheticTelemetrySource(
        times, BandwidthDrop(step=10**9, comm_scale=3.0)
    )
    ctrl = AdaptiveController(times, schedule, scfg, walk=WALK)
    assert _drive(ctrl, src, 80) == []


def test_controller_preserver_flip_on_measured_walk():
    """Timing steady, but the measured loss trace makes the Preserver
    reject the installed merged schedule -> 'preserver-flip' replan with
    a higher update frequency."""
    times = _toy_times(cr=2.5)
    schedule, verdict, scfg, _ = feedback_solve(times, WALK, eps=1e9)
    assert schedule.updates_per_period < schedule.period
    src = SyntheticTelemetrySource(
        times, BandwidthDrop(step=10**9, comm_scale=1.0)
    )
    # near-converged noisy trace: batch-size sensitivity is maximal near
    # S*, so the merged k-sequence fails a tight eps under measured walk
    import random

    rng = random.Random(3)
    losses = [abs(rng.gauss(0.02, 0.02)) for _ in range(200)]
    ctrl = AdaptiveController(
        times, schedule, scfg, walk=WALK,
        cfg=AdaptConfig(eps=1e-4, eta=0.05, base_batch=16),
    )
    events = _drive(ctrl, src, 200, losses=losses)
    assert any(e.trigger == "preserver-flip" for e in events)
    ev = next(e for e in events if e.trigger == "preserver-flip")
    new_freq = len(ev.new_batch_seq) / ev.new_period
    old_freq = len(ev.old_batch_seq) / ev.old_period
    assert new_freq >= old_freq


def test_knapsack_memo_cache_reused_across_consecutive_replans():
    """Consecutive replans over a similar profile re-solve mostly
    cache-hit knapsack instances (the solver fast path the control plane
    leans on to stay off the hot path)."""
    times = _toy_times()
    prev = set_knapsack_memoization(True)
    try:
        clear_knapsack_caches()
        feedback_solve(times, WALK)
        first = knapsack_cache_info()
        feedback_solve(times, WALK)           # identical replan: all hits
        second = knapsack_cache_info()
        assert second.misses == first.misses
        assert second.hits > first.hits
        # a *calibrated* (scaled-comm) replan still reuses the identical
        # compute-capacity instances solved during forward stages
        feedback_solve(scale_times(times, 1.0, 1.3), WALK)
        third = knapsack_cache_info()
        assert third.hits > second.hits
    finally:
        set_knapsack_memoization(prev)


# ---------------------------------------------------------------------------
# Candidate-partition path (pure Python; the runtime side lives in
# tests/test_repack.py)
# ---------------------------------------------------------------------------
def _leaf_model_setup(pe=20_000, cr=1.8):
    from repro.train import build_leaf_time_model

    cfg = _tiny_cfg()
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    model = build_leaf_time_model(params, cfg, HardwareModel(dp_degree=4),
                                  32, 4)
    bo, nb = model.partition(pe)
    model = model.with_coverage_rate(bo, nb, cr)
    return model, bo, nb, model.bucket_times(bo, nb)


def _synthetic_leaf_model(fwd, elems, comm_scale=1.0):
    from repro.train.bucketing import LeafTimeModel

    return LeafTimeModel(
        order=tuple(range(len(fwd))),
        fwd_s=tuple(fwd),
        elems=tuple(int(e) for e in elems),
        hw=HardwareModel(dp_degree=4),
        comm_scale=comm_scale,
    )


leaf_atoms = st.integers(min_value=2, max_value=24).flatmap(
    lambda n: st.tuples(
        st.lists(st.floats(1e-5, 5e-3), min_size=n, max_size=n),
        st.lists(st.integers(1_000, 2_000_000), min_size=n, max_size=n),
    )
)


@settings(max_examples=40)
@given(leaf_atoms, st.floats(0.3, 3.0), st.floats(0.3, 3.0))
def test_dp_partition_no_worse_than_greedy_on_surrogate(atoms, a, b):
    """The boundary DP is exact over ALL contiguous partitions, so under
    its own objective it can never lose to the greedy size-targeted fill
    — at any calibrated (comp, comm) scale and any grid factor."""
    from repro.adapt import dp_partition, exposed_makespan

    fwd, elems = atoms
    model = _synthetic_leaf_model(fwd, elems)
    dp_bo, dp_nb = dp_partition(model, comp_scale=a, comm_scale=b)
    assert dp_nb >= 1 and len(dp_bo) == len(fwd)
    dp_cost = exposed_makespan(model, dp_bo, dp_nb,
                               comp_scale=a, comm_scale=b)
    total = sum(elems)
    for frac in (0.05, 0.25, 1.0):
        g_bo, g_nb = model.partition(max(int(total * frac), 1))
        g_cost = exposed_makespan(model, g_bo, g_nb,
                                  comp_scale=a, comm_scale=b)
        assert dp_cost <= g_cost + 1e-12


def test_dp_partition_shape_and_bounded_variant():
    """DP output is a valid ascending contiguous model-order partition;
    the ``max_buckets`` layered variant respects its cap and can only be
    costlier than the unbounded optimum."""
    from repro.adapt import dp_partition, exposed_makespan

    model, bo, nb, _ = _leaf_model_setup()
    dp_bo, dp_nb = dp_partition(model)
    # every leaf assigned; buckets ascending+contiguous along model order
    seq = [dp_bo[i] for i in model.order]
    assert seq[0] == 0 and seq[-1] == dp_nb - 1
    assert all(s2 - s1 in (0, 1) for s1, s2 in zip(seq, seq[1:]))
    free = exposed_makespan(model, dp_bo, dp_nb)
    for cap in (1, 2, max(dp_nb - 1, 1)):
        c_bo, c_nb = dp_partition(model, max_buckets=cap)
        assert 1 <= c_nb <= cap
        assert exposed_makespan(model, c_bo, c_nb) >= free - 1e-12
    # empty tree degenerates cleanly
    empty = _synthetic_leaf_model((), ())
    assert dp_partition(empty) == ((), 0)


def test_repartitioner_candidate_superset_includes_dp():
    """The candidate grid is {current} ∪ factor grid ∪ DP; ``use_dp``
    gates the DP member and the DP candidate reprices with the
    cumulative drift scales."""
    from repro.adapt import RepartitionConfig, Repartitioner

    model, bo, nb, _ = _leaf_model_setup()
    rp = Repartitioner(model, RepartitionConfig(base_partition_elems=20_000))
    cands = rp.candidates(bo, nb, comm_scale=3.0)
    tags = [c.tag for c in cands]
    assert tags[0] == "current"
    assert "dp" in tags
    dp_cand = next(c for c in cands if c.tag == "dp")
    assert dp_cand.n_buckets >= 1
    assert rp.times_for(dp_cand).n == dp_cand.n_buckets
    off = Repartitioner(model, RepartitionConfig(
        base_partition_elems=20_000, use_dp=False))
    assert "dp" not in [c.tag for c in off.candidates(bo, nb)]


def test_feedback_solve_candidates_gate_and_hysteresis():
    """The winner is Preserver-ok (or the baseline itself), and an
    impossible min_gain pins the choice to the baseline — near-ties
    must never pay a re-pack."""
    from repro.adapt import RepartitionConfig, Repartitioner
    from repro.core.deft import feedback_solve_candidates

    model, bo, nb, times = _leaf_model_setup()
    rp = Repartitioner(model, RepartitionConfig(base_partition_elems=20_000))
    pairs = [(c.tag, rp.times_for(c, comm_scale=3.0))
             for c in rp.candidates(bo, nb)]
    best, solves = feedback_solve_candidates(
        pairs, WALK, baseline_tag="current", min_gain=0.02
    )
    assert len(solves) == len(pairs)
    assert best.verdict.ok or best.tag == "current"
    assert all(s.iteration_time > 0 for s in solves)
    # the winner actually wins on simulated iteration time
    ok = [s for s in solves if s.verdict.ok]
    assert best.iteration_time == min(s.iteration_time for s in ok)
    pinned, _ = feedback_solve_candidates(
        pairs, WALK, baseline_tag="current", min_gain=10.0
    )
    assert pinned.tag == "current"


def test_controller_repartitions_on_bandwidth_drop():
    """A 3x bandwidth drop calibrates to a profile under which a
    different partition wins -> the replan is partition-changing, the
    adopted candidate is Preserver-gated, and the controller's installed
    view (times.n, bucket_of) follows the new partition."""
    from repro.adapt import RepartitionConfig, Repartitioner

    model, bo, nb, times = _leaf_model_setup()
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    rp = Repartitioner(model, RepartitionConfig(base_partition_elems=20_000))
    drop = BandwidthDrop(step=40, comm_scale=3.0)
    ctrl = AdaptiveController(times, schedule, scfg, walk=WALK,
                              repartitioner=rp, bucket_of=bo)
    events = run_control_loop(
        ctrl, SyntheticTelemetrySource(times, drop), 140,
        run_base_fn=lambda e: rp.base_times_for(e.partition),
    )
    assert events and all(e.step >= drop.step for e in events)
    reparts = [e for e in events if e.partition_changed]
    assert reparts, "calibrated drop profile favored no other partition"
    ev = reparts[0]
    assert ev.verdict.ok
    assert ev.new_n_buckets == ev.partition.n_buckets != ev.old_n_buckets
    assert ev.changed and "REPARTITION" in ev.describe()
    assert len(ev.candidate_solves) >= 2
    assert ctrl.stats()["repartitions"] == len(reparts)
    assert ctrl.bucket_of == reparts[-1].partition.bucket_of
    assert ctrl.times.n == reparts[-1].new_n_buckets


def test_controller_without_repartitioner_never_repartitions():
    times = _toy_times()
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    ctrl = AdaptiveController(times, schedule, scfg, walk=WALK)
    events = run_control_loop(
        ctrl, SyntheticTelemetrySource(
            times, BandwidthDrop(step=40, comm_scale=3.0)), 120,
    )
    assert events
    assert all(not e.partition_changed for e in events)
    assert ctrl.stats()["repartitions"] == 0


def test_controller_repartitioner_requires_bucket_of():
    from repro.adapt import RepartitionConfig, Repartitioner

    model, bo, nb, times = _leaf_model_setup()
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    rp = Repartitioner(model, RepartitionConfig(base_partition_elems=20_000))
    with pytest.raises(ValueError, match="bucket_of"):
        AdaptiveController(times, schedule, scfg, walk=WALK,
                           repartitioner=rp)


# ---------------------------------------------------------------------------
# The acceptance test: detect -> replan -> hot-swap on the real runtime,
# bit-matching a reference run of the same effective phase sequence.
# ---------------------------------------------------------------------------
B, S = 4, 32


def _tiny_cfg():
    base = get_config("qwen3-4b")
    return dataclasses.replace(
        base, name="qwen3-tiny", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512,
    )


def test_adaptive_loop_hot_swap_bit_matches_reference(single_mesh):
    cfg = _tiny_cfg()
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    bucket_of, nb = assign_buckets(params, cfg, partition_elems=20_000)
    hw = HardwareModel(dp_degree=2)
    times = leaf_bucket_times(params, cfg, bucket_of, nb, hw, S, B)
    scale = 1.8 * (times.fwd_total + times.bwd_total) / times.comm_total
    times = BucketTimes(times.fwd, times.bwd,
                        tuple(c * scale for c in times.comm))
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    layout = build_bucket_layout(params, bucket_of, nb)

    drop = BandwidthDrop(step=4, comm_scale=3.0)
    src = SyntheticTelemetrySource(times, drop)
    ctrl = AdaptiveController(
        times, schedule, scfg, walk=WALK,
        cfg=AdaptConfig(warmup_steps=2, check_every=2, cooldown_steps=100,
                        min_loss_samples=10**9),  # timing trigger only
    )

    n_steps = 6 * schedule.period + 8
    runtime = DeftRuntime(cfg, opt, schedule, layout, single_mesh)
    state = runtime.init_state(key)
    swap_info = None
    new_schedule = None
    with jax.set_mesh(single_mesh):
        for step in range(n_steps):
            batch = make_batch(cfg, 0, step, B, S)
            state, m = runtime.step(step, state, batch)
            wall = src.wall_time(
                step, ctrl.schedule, ctrl.scheduler_cfg,
                runtime.last_phase, solve_times=ctrl.times,
            )
            event = ctrl.observe(step, runtime.last_phase, wall)
            if event is not None and event.changed:
                assert new_schedule is None, "cooldown should allow 1 swap"
                new_schedule = event.schedule
                swap_info = runtime.prepare_swap(
                    new_schedule, state, batch, background=False
                )

    # the controller detected the drop and the runtime swapped once, at a
    # cycle boundary of the old schedule
    assert new_schedule is not None, "no replan despite 3x bandwidth drop"
    assert new_schedule.phases != schedule.phases
    st = runtime.stats()
    assert st["replans"] == 1 and st["hot_swaps"] == 1
    swap_step = runtime.swap_log[0]["step"]
    assert swap_step % schedule.period == 0
    assert runtime.period == new_schedule.period
    assert st["steps_dispatched"] == n_steps
    assert st["dispatch_s_total"] > 0

    # staging the same schedule again is a pure cache hit
    re_info = runtime.prepare_swap(
        new_schedule, state, make_batch(cfg, 0, 0, B, S), background=False
    )
    assert re_info["new_phases"] == 0
    assert swap_info["new_phases"] + swap_info["reused_phases"] == len(
        new_schedule.phases
    )

    # ---- reference: the same effective update sequence, run explicitly
    rt_a = DeftRuntime(cfg, opt, schedule, layout, single_mesh)
    ref_state = rt_a.init_state(key)
    rt_b = DeftRuntime(cfg, opt, new_schedule, layout, single_mesh)
    with jax.set_mesh(single_mesh):
        for step in range(swap_step):
            ref_state, _ = rt_a.step(step, ref_state,
                                     make_batch(cfg, 0, step, B, S))
        for step in range(swap_step, n_steps):
            ref_state, _ = rt_b.step(step - swap_step, ref_state,
                                     make_batch(cfg, 0, step, B, S))

    for a, b in zip(jax.tree.leaves(runtime.params_tree(state)),
                    jax.tree.leaves(rt_b.params_tree(ref_state))):
        assert jnp.array_equal(a, b), "hot-swapped run diverged bitwise"


# ---------------------------------------------------------------------------
# The benchmark's acceptance claim, exercised as a test
# ---------------------------------------------------------------------------
def test_adapt_bench_adaptive_at_least_static(tmp_path, monkeypatch):
    monkeypatch.setenv("BENCH_ADAPT_OUT", str(tmp_path / "BENCH_adapt.json"))
    monkeypatch.setenv("BENCH_ADAPT_STEPS", "120")
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    try:
        import importlib

        import benchmarks.adapt_bench as ab

        importlib.reload(ab)
        ab.run()
    finally:
        sys.path.pop(0)
    import json

    out = json.load(open(tmp_path / "BENCH_adapt.json"))
    assert out["replan_events"], "bench scenario produced no replans"
    assert (
        out["steps_per_s_adaptive_after_drop"]
        >= out["steps_per_s_static_after_drop"]
    )
    # cache trail shows the memoized solver absorbing consecutive replans
    assert out["knapsack_cache_trail"][-1]["hits"] > 0
