"""Observability-layer benchmark: attribution fidelity and tracing cost.

Three questions, all deterministic except the overhead timing:

* **closure** — do spans reconstructed from the discrete-event timeline
  reproduce the simulator's own iteration time / bubble fraction /
  coverage rate (the §11 alignment rules, end to end)?
* **divergence lead** — on the BENCH_adapt bandwidth-drop scenario, how
  many steps earlier does the per-phase divergence drift source replan
  than the legacy EMA screen?
* **tracing overhead** — what does attaching per-step span recording to
  the fused smoke dispatch cost, paired traced-vs-plain min-of-reps?
  The acceptance bound is <2%; tests/test_bench_schema.py floors it.

Emits ``BENCH_obs.json``.
"""
from __future__ import annotations

import json
import os
import time

_OUT = os.environ.get("BENCH_OBS_OUT", "BENCH_obs.json")
_STEPS = int(os.environ.get("BENCH_OBS_STEPS", "40"))
DROP_STEP = 60
DROP_SCALE = 3.0
CR = 1.8


def _profile():
    """Paper-scale bucket profile (gemma2-2b leaf-free analytic) — the
    same scenario BENCH_adapt tracks, so the lead metric is comparable."""
    from repro.configs import get_config
    from repro.core.bucket import BucketTimes
    from repro.core.profiler import HardwareModel, profile_arch

    hw = HardwareModel(dp_degree=16)
    prof = profile_arch(get_config("gemma2-2b"), hw=hw, seq_len=4096)
    t = prof.times
    scale = CR * (t.fwd_total + t.bwd_total) / max(t.comm_total, 1e-12)
    return BucketTimes(t.fwd, t.bwd, tuple(c * scale for c in t.comm))


def _closure(times, schedule, scfg):
    """Timeline -> spans -> the simulator's own numbers."""
    from repro.core.scheduler import DeftScheduler
    from repro.core.simulator import simulate_deft
    from repro.obs import sim_metrics_from_spans, spans_from_sim

    plans = DeftScheduler(times, scfg).run(24)
    sim = simulate_deft(times, plans, mu=scfg.mu,
                        heterogeneous=scfg.heterogeneous,
                        keep_timeline=True)
    m = sim_metrics_from_spans(spans_from_sim(sim), mu=scfg.mu)
    return {
        "sim_iteration_time": sim.iteration_time,
        "span_iteration_time": m.iteration_time,
        "iteration_time_exact": m.iteration_time == sim.iteration_time,
        "sim_bubble_fraction": sim.bubble_fraction,
        "span_bubble_fraction": m.bubble_fraction,
        "bubble_abs_error": abs(m.bubble_fraction - sim.bubble_fraction),
        "planned_cr": times.coverage_rate,
        "measured_cr": m.coverage_rate,
        "cr_error": abs(m.coverage_rate - times.coverage_rate)
        / max(times.coverage_rate, 1e-12),
        "n_spans": len(spans_from_sim(sim)),
    }


def _attribution(times, schedule, scfg):
    """Undisturbed run: measured == plan must read back identity."""
    from repro.adapt.calibrate import planned_phase_durations
    from repro.obs import attribute

    planned = planned_phase_durations(times, scfg, schedule.period)
    att = attribute(planned, times, scfg, schedule)
    return {
        "comp_scale": att.comp_scale,
        "comm_scale": att.comm_scale,
        "max_divergence": att.max_divergence,
        "cr_error": att.cr_error,
        "bubble_fraction": att.bubble_fraction,
        "capacity_utilization": dict(att.capacity_utilization),
    }


def _divergence_lead(times, schedule, scfg, walk):
    """First replan step, EMA drift source vs per-phase divergence.

    Per-check detection (``check_every=1`` — a coarser cadence would
    quantize both sources onto the same check step) on a drop sized in
    the (threshold, EMA-instant) band: the latest-sample divergence
    crosses the threshold on the first degraded sample, the EMA needs
    ``(1-(1-alpha)^k) * delta`` to accumulate across k of them."""
    from repro.adapt import (
        AdaptConfig,
        AdaptiveController,
        BandwidthDrop,
        SyntheticTelemetrySource,
        run_control_loop,
    )

    lead_drop = 1.9

    def first_replan(drift_source):
        src = SyntheticTelemetrySource(
            times, BandwidthDrop(step=DROP_STEP, comm_scale=lead_drop)
        )
        ctrl = AdaptiveController(
            times, schedule, scfg, walk=walk,
            cfg=AdaptConfig(drift_source=drift_source, check_every=1),
        )
        run_control_loop(ctrl, src, 3 * DROP_STEP)
        return ctrl.events[0].step if ctrl.events else None

    ema = first_replan("ema")
    div = first_replan("divergence")
    lead = (ema - div) if (ema is not None and div is not None) else None
    return {
        "drop_scale": lead_drop,
        "ema_replan_step": ema,
        "divergence_replan_step": div,
        "lead_steps": lead,
    }


def _tracing_overhead():
    """Paired traced-vs-plain fused smoke dispatch (single device)."""
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.core.bucket import BucketTimes
    from repro.core.deft import feedback_solve
    from repro.core.preserver import WalkParams
    from repro.core.profiler import HardwareModel
    from repro.data.pipeline import make_batch
    from repro.models.model import init_params
    from repro.obs import Tracer
    from repro.optim.optimizers import adamw
    from repro.train import (
        DeftRuntime,
        assign_buckets,
        build_bucket_layout,
        leaf_bucket_times,
    )

    b, s = 4, 32
    cfg = dataclasses.replace(
        get_config("qwen3-4b"), name="qwen3-tiny", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512,
    )
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    bucket_of, nb = assign_buckets(params, cfg, partition_elems=20_000)
    hw = HardwareModel(dp_degree=2)
    times = leaf_bucket_times(params, cfg, bucket_of, nb, hw, s, b)
    scale = CR * (times.fwd_total + times.bwd_total) / times.comm_total
    times = BucketTimes(times.fwd, times.bwd,
                        tuple(c * scale for c in times.comm))
    walk = WalkParams(s0=4.0, eta=0.01, mu=1.0, sigma=40.0, batch=256)
    schedule, _, _, _ = feedback_solve(times, walk)
    layout = build_bucket_layout(params, bucket_of, nb)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

    opt = adamw(1e-3)
    tracer = Tracer(capacity=1 << 16)
    rt_plain = DeftRuntime(cfg, opt, schedule, layout, mesh)
    rt_traced = DeftRuntime(cfg, opt, schedule, layout, mesh, tracer=tracer)
    batch = make_batch(cfg, 0, 0, b, s)
    with jax.set_mesh(mesh):
        s_plain = rt_plain.init_state(key)
        s_traced = rt_traced.init_state(key)
        rt_plain.compile(s_plain, batch)
        rt_traced.compile(s_traced, batch)

        def timed(rt, state, n):
            t0 = time.perf_counter()
            for i in range(n):
                state, m = rt.step(i, state, batch)
            jax.block_until_ready(m["loss"])
            return (time.perf_counter() - t0) / n, state

        # chunks align to the period so every rep times the same phase
        # mix; paired order + min-of-reps absorbs ambient load spikes
        chunk = max(1, round(_STEPS / schedule.period)) * schedule.period
        _, s_plain = timed(rt_plain, s_plain, 10)       # warm past compiles
        _, s_traced = timed(rt_traced, s_traced, 10)
        best_plain = best_traced = float("inf")
        for _ in range(9):
            dt, s_plain = timed(rt_plain, s_plain, chunk)
            best_plain = min(best_plain, dt)
            dt, s_traced = timed(rt_traced, s_traced, chunk)
            best_traced = min(best_traced, dt)

    by_kind = tracer.stats()["by_kind"]
    return {
        "steps_timed": chunk,
        "steps_per_s_plain": 1.0 / best_plain,
        "steps_per_s_traced": 1.0 / best_traced,
        "overhead_pct": (best_traced / best_plain - 1.0) * 100.0,
        "spans_recorded": tracer.n_recorded,
        "span_kinds": by_kind,
    }


def run() -> None:
    """Benchmark section entry point (benchmarks/run.py)."""
    from repro.core.deft import feedback_solve
    from repro.core.preserver import WalkParams

    t0 = time.time()
    times = _profile()
    walk = WalkParams(s0=4.0, eta=0.01, mu=1.0, sigma=40.0, batch=256)
    schedule, _, scfg, _ = feedback_solve(times, walk)

    result = {
        "scenario": {"drop_step": DROP_STEP, "drop_scale": DROP_SCALE,
                     "coverage_rate": CR, "steps": _STEPS},
        "closure": _closure(times, schedule, scfg),
        "attribution": _attribution(times, schedule, scfg),
        "divergence_lead": _divergence_lead(times, schedule, scfg, walk),
        "tracing": _tracing_overhead(),
    }
    tmp = _OUT + ".tmp"
    json.dump(result, open(tmp, "w"), indent=1)
    os.replace(tmp, _OUT)

    c, a, d, tr = (result["closure"], result["attribution"],
                   result["divergence_lead"], result["tracing"])
    print(f"obs_closure_cr_error,{c['cr_error'] * 1e6:.0f},"
          f"measured CR {c['measured_cr']:.3f} vs planned "
          f"{c['planned_cr']:.3f} (iteration_time_exact="
          f"{c['iteration_time_exact']})")
    print(f"obs_attribution_max_divergence,{a['max_divergence'] * 1e6:.0f},"
          f"undisturbed run: comp x{a['comp_scale']:.2f} "
          f"comm x{a['comm_scale']:.2f}")
    print(f"obs_divergence_lead_steps,{d['lead_steps'] or 0},"
          f"divergence replans step {d['divergence_replan_step']} vs "
          f"EMA step {d['ema_replan_step']}")
    print(f"obs_tracing_overhead_pct,{tr['overhead_pct'] * 100:.0f},"
          f"{tr['overhead_pct']:.2f}% ({tr['steps_per_s_traced']:.1f} vs "
          f"{tr['steps_per_s_plain']:.1f} steps/s, "
          f"{tr['spans_recorded']} spans)")
    print(f"# BENCH_obs.json written in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
