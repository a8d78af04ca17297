"""Elastic control-plane benchmark: fault -> detection -> Preserver-gated
scale-down -> cycle-boundary repack, plus the symmetric scale-up.

Two deterministic fault scenarios replay through the SAME
:class:`repro.elastic.FaultScenario` / :class:`HealthMonitor` /
:class:`ElasticController` objects the chaos tests drive:

* a **device drop** (2 of 4 data shards vanish) — measures the
  heartbeat-timeout detection latency and the re-priced 2-shard plan;
* a **straggler** (one shard runs ``STRAGGLER_FACTOR`` x slow) — measures
  the EWMA-ratio detection latency and the throughput recovered by
  planning the slow shard out of the mesh.

Per-step wall times come from the same steady-state timeline model the
adapt bench uses (this container has no device that can actually die),
so detection latencies and steps/s are bit-for-bit reproducible.  The
migration cost is NOT modeled: a miniature smoke-config runtime pair
runs a real ``migrate_state`` (accumulator fold -> device transfer ->
``repack_state``) on the local device set and reports measured
milliseconds.  Emits ``BENCH_elastic.json`` (schema: bench_schema.py).
"""
from __future__ import annotations

import json
import os
import time

_OUT = os.environ.get("BENCH_ELASTIC_OUT", "BENCH_elastic.json")
_STEPS = int(os.environ.get("BENCH_ELASTIC_STEPS", "64"))
N_SHARDS = 4
DROP_STEP = 12
DROP_SHARDS = (2, 3)
STRAGGLER_SHARD = 1
STRAGGLER_FACTOR = 3.0
CR = 1.8
GLOBAL_BATCH = 16
SEQ = 512
PARTITION_ELEMS = 6_500_000


def _measure_migrate() -> dict:
    """Real measured migration between two smoke-config runtimes on the
    local device set: fold (no-op at equal width) + device_put +
    ``repack_state`` across a partition change, both directions."""
    import jax

    from repro.configs import get_config, reduce_for_smoke
    from repro.core.deft import feedback_solve
    from repro.core.preserver import WalkParams
    from repro.core.profiler import HardwareModel
    from repro.elastic import migrate_state
    from repro.launch.mesh import make_debug_mesh
    from repro.models.model import init_params
    from repro.optim.optimizers import adamw
    from repro.train.bucketing import (
        assign_buckets,
        build_bucket_layout,
        leaf_bucket_times,
    )
    from repro.train.runtime import DeftRuntime

    cfg = reduce_for_smoke(get_config("gemma2-2b"))
    mesh = make_debug_mesh(data=1, model=1)
    params_abs = jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0)
    )
    walk = WalkParams(s0=4.0, eta=0.01, mu=1.0, sigma=40.0, batch=256)

    def plan(partition_elems):
        bo, nb = assign_buckets(params_abs, cfg, partition_elems)
        times = leaf_bucket_times(
            params_abs, cfg, bo, nb, HardwareModel(dp_degree=1), 64, 8
        )
        schedule, _, _, _ = feedback_solve(times, walk)
        return bo, nb, schedule

    bo_a, nb_a, sched_a = plan(200_000)
    bo_b, nb_b, sched_b = plan(420_000)
    with jax.set_mesh(mesh):
        layout_a = build_bucket_layout(params_abs, bo_a, nb_a, shard_count=1)
        layout_b = build_bucket_layout(params_abs, bo_b, nb_b, shard_count=1)
        rt_a = DeftRuntime(cfg, adamw(1e-3), sched_a, layout_a, mesh)
        rt_b = rt_a.spawn(schedule=sched_b, layout=layout_b)
        state = rt_a.init_state(jax.random.PRNGKey(0))

        def timed_roundtrip():
            nonlocal state
            t0 = time.perf_counter()
            state = migrate_state(rt_a, rt_b, state)
            jax.block_until_ready(jax.tree_util.tree_leaves(state))
            ab = time.perf_counter() - t0
            t0 = time.perf_counter()
            state = migrate_state(rt_b, rt_a, state)
            jax.block_until_ready(jax.tree_util.tree_leaves(state))
            return ab, time.perf_counter() - t0

        # rep 1 pays the repack jit; rep 2 is the steady cost a live
        # migration would re-pay only on a never-seen transition
        first_ab, first_ba = timed_roundtrip()
        warm_ab, warm_ba = timed_roundtrip()
    return {
        "n_buckets_a": nb_a,
        "n_buckets_b": nb_b,
        "total_elems": layout_a.total_elems,
        "migrate_ms_a_to_b": warm_ab * 1e3,
        "migrate_ms_b_to_a": warm_ba * 1e3,
        "migrate_ms_first_call": first_ab * 1e3,
        "first_ba_ms": first_ba * 1e3,
    }


def run() -> None:
    """Benchmark section entry point (benchmarks/run.py)."""
    import jax

    from repro.adapt.calibrate import schedule_plans, steady_phase_durations
    from repro.configs import get_config
    from repro.core.preserver import WalkParams
    from repro.core.profiler import HardwareModel
    from repro.elastic import (
        DeviceDrop,
        ElasticController,
        FaultScenario,
        HealthMonitor,
        StragglerSlowdown,
    )
    from repro.models.model import init_params
    from repro.train.bucketing import assign_buckets, build_leaf_time_model

    t0 = time.time()
    cfg = get_config("gemma2-2b")
    params_abs = jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0)
    )
    bucket_of, nb = assign_buckets(params_abs, cfg, PARTITION_ELEMS)

    def model_for(width):
        m = build_leaf_time_model(
            params_abs, cfg, HardwareModel(dp_degree=width), SEQ,
            max(GLOBAL_BATCH // width, 1),
        )
        return m.with_coverage_rate(bucket_of, nb, CR)

    walk = WalkParams(s0=4.0, eta=0.01, mu=1.0, sigma=40.0, batch=256)
    controller = ElasticController(model_for, bucket_of, nb, walk=walk)

    def steps_per_s(plan, wall_factor=1.0):
        sc = plan.scheduler_cfg
        durs = steady_phase_durations(
            schedule_plans(plan.times, sc), plan.times, plan.schedule.period,
            mu=sc.mu, heterogeneous=sc.heterogeneous,
        )
        return plan.schedule.period / max(sum(durs) * wall_factor, 1e-12)

    # the healthy 4-shard plan every scenario starts from
    plan4 = controller.propose(0, N_SHARDS, "initial")
    base_wall = 1.0 / steps_per_s(plan4)

    def detect(scenario, kind):
        mon = HealthMonitor(N_SHARDS)
        for step in range(_STEPS):
            obs = scenario.observe(step, base_wall)
            for ev in mon.observe(step, list(obs.walls)):
                if ev.kind == kind:
                    return step
        return None

    drop = FaultScenario(N_SHARDS, (DeviceDrop(DROP_STEP, DROP_SHARDS),))
    straggle = FaultScenario(
        N_SHARDS,
        (StragglerSlowdown(DROP_STEP, STRAGGLER_SHARD, STRAGGLER_FACTOR),),
    )
    drop_detected = detect(drop, "dead")
    straggler_detected = detect(straggle, "straggler")

    # the Preserver-gated survival plans (what the coordinator executes)
    plan_down = controller.propose(drop_detected or DROP_STEP, 2, "dead")
    controller.adopt(plan_down)
    plan_up = controller.propose(_STEPS, N_SHARDS, "scale-up")

    sps_before = steps_per_s(plan4)
    # the fault window: the straggler gates every step's critical path
    # until its removal executes at the cycle boundary
    sps_during = steps_per_s(plan4, wall_factor=STRAGGLER_FACTOR)
    sps_after = steps_per_s(plan_down)
    migrate = _measure_migrate()

    def plan_dict(p):
        return {
            "n_shards": p.n_shards,
            "action": p.action,
            "period": p.schedule.period,
            "updates_per_period": p.schedule.updates_per_period,
            "preserver_ratio": p.verdict.ratio,
            "preserver_ok": p.verdict.ok,
            "plan_s": p.plan_s,
        }

    result = {
        "scenario": {
            "n_shards": N_SHARDS,
            "drop_step": DROP_STEP,
            "drop_shards": list(DROP_SHARDS),
            "straggler_shard": STRAGGLER_SHARD,
            "straggler_factor": STRAGGLER_FACTOR,
            "coverage_rate": CR,
            "steps": _STEPS,
        },
        "initial_plan": plan_dict(plan4),
        "detection": {
            "device_drop_step": drop_detected,
            "device_drop_latency_steps":
                None if drop_detected is None else drop_detected - DROP_STEP,
            "straggler_step": straggler_detected,
            "straggler_latency_steps":
                None if straggler_detected is None
                else straggler_detected - DROP_STEP,
        },
        "steps_per_s_before_fault": sps_before,
        "steps_per_s_during_fault": sps_during,
        "steps_per_s_after_repack": sps_after,
        "after_over_during_fault": sps_after / max(sps_during, 1e-12),
        "scale_down_plan": plan_dict(plan_down),
        "scale_up_plan": plan_dict(plan_up),
        "repack": migrate,
    }
    tmp = _OUT + ".tmp"
    json.dump(result, open(tmp, "w"), indent=1)
    os.replace(tmp, _OUT)

    print(f"elastic_detect_drop_steps,{(drop_detected or 0) - DROP_STEP},"
          f"heartbeat-timeout latency (4 shards, 2 dead)")
    print(f"elastic_detect_straggler_steps,"
          f"{(straggler_detected or 0) - DROP_STEP},"
          f"EWMA-ratio latency ({STRAGGLER_FACTOR}x slow shard)")
    print(f"elastic_steps_per_s_before,{1e6 / max(sps_before, 1e-12):.0f},"
          f"{sps_before:.3f} steps/s (healthy 4-shard plan)")
    print(f"elastic_steps_per_s_during,{1e6 / max(sps_during, 1e-12):.0f},"
          f"{sps_during:.3f} steps/s (straggler-gated)")
    print(f"elastic_steps_per_s_after,{1e6 / max(sps_after, 1e-12):.0f},"
          f"{sps_after:.3f} steps/s (repacked 2-shard plan, "
          f"preserver ratio {plan_down.verdict.ratio:.4f})")
    print(f"elastic_migrate_us,{migrate['migrate_ms_a_to_b'] * 1e3:.0f},"
          f"measured fold+transfer+repack "
          f"{migrate['migrate_ms_a_to_b']:.1f}ms "
          f"({migrate['n_buckets_a']}->{migrate['n_buckets_b']} buckets, "
          f"{migrate['total_elems']:,} elems)")
    print(f"# BENCH_elastic.json written in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
