"""DeftRuntime perf benchmark: fused-bucket runtime vs the seed per-leaf
implementation, plus solver planning time with/without memoization.

Emits machine-readable ``BENCH_runtime.json`` (steps/s, compile time,
solver planning time, collectives-per-phase) so the perf trajectory is
tracked across PRs.  Every JAX section runs in a subprocess of its own,
one after another, and the parent never imports JAX, so on an
accelerator each child holds the device alone.  Two of the train-loop
scenarios:

* ``smoke`` — the smoke DeFT train loop exactly as ``repro.launch.train
  --smoke --scheduler deft`` runs it on this host (single device).  The
  fused runtime wins on graph leanness (per-bucket buffers instead of
  per-leaf accumulator ops) and buffer donation (params/opt/accumulators
  update in place instead of being copied every step).
* ``dp4`` — 4 forced host devices so the per-bucket vs per-leaf gradient
  collectives are real inter-device operations.

The solver benchmark runs in-process on a paper-scale bucket profile
(comm times in the 10..300 ms range — the regime the production planner
faces; microsecond toy instances make the DP trivially cheap and would
understate the memoization win).
"""
from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import time

_STEPS = int(os.environ.get("BENCH_RUNTIME_STEPS", "30"))
_OUT = os.environ.get("BENCH_RUNTIME_OUT", "BENCH_runtime.json")


def _paired_min_of_reps(engines, *, warmup, chunk, reps):
    """Paired interleaved min-of-reps over engine step callables.

    ``engines`` maps name -> ``[fn, state]`` with ``fn(i, state) ->
    (state, metrics)``; states advance in place (donated engines must
    keep stepping their own returned state).  Every rep times one
    ``chunk``-step block per engine back to back, so an ambient load
    spike on a shared host hits every engine of that rep instead of
    whichever happened to run second; the per-engine minimum over reps
    is the reported average step time.  Callers align ``chunk`` to the
    schedule period — a fixed-length window would rotate through the
    cycle and the min would pick the cheapest phase mix rather than a
    steady-state period.  Returns ({name: best_avg_step_s},
    {name: warmup_wall_s}, {name: steps_run})."""
    import jax

    steps_done = {k: 0 for k in engines}

    def run_chunk(name, n):
        fn, state = engines[name]
        i0 = steps_done[name]
        t0 = time.perf_counter()
        for i in range(i0, i0 + n):
            state, m = fn(i, state)
        jax.block_until_ready(m["loss"])
        engines[name][1] = state
        steps_done[name] = i0 + n
        return (time.perf_counter() - t0) / n

    warmup_s = {}
    for name in engines:
        t0 = time.perf_counter()
        run_chunk(name, warmup)
        warmup_s[name] = time.perf_counter() - t0
    best = {k: float("inf") for k in engines}
    for _ in range(reps):
        for name in engines:
            best[name] = min(best[name], run_chunk(name, chunk))
    return best, warmup_s, steps_done


def _paper_tree(n_leaves: int = 256, leaf_elems: int = 8192):
    """Synthetic paper-regime parameter tree — a few hundred tensors,
    as in the paper's DNN/LLM profiles."""
    import jax

    key = jax.random.PRNGKey(1)
    tree = {
        f"l{i:03d}": jax.random.normal(jax.random.fold_in(key, i),
                                       (leaf_elems,))
        for i in range(n_leaves)
    }
    return tree


def _timed_apply_pair(f_flat, flat_args, f_leaf, leaf_args,
                      *, reps: int = 9, n: int = 20):
    """Paired interleaved min-of-reps over the two jitted apply fns.
    Returns (ms_flat, ms_leaf)."""
    import jax

    def timed(f, args):
        t0 = time.perf_counter()
        for _ in range(n):
            out = f(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n

    jax.block_until_ready(f_flat(*flat_args))     # compile outside timing
    jax.block_until_ready(f_leaf(*leaf_args))
    ms_flat = ms_leaf = float("inf")
    for _ in range(reps):
        ms_flat = min(ms_flat, timed(f_flat, flat_args) * 1e3)
        ms_leaf = min(ms_leaf, timed(f_leaf, leaf_args) * 1e3)
    return ms_flat, ms_leaf


def _inner(devices: int) -> dict:
    if devices > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}"
        )
    import jax

    from repro.configs import get_config, reduce_for_smoke
    from repro.core.bucket import BucketTimes
    from repro.core.deft import solve_schedule
    from repro.core.profiler import HardwareModel
    from repro.core.scheduler import SchedulerConfig
    from repro.data.pipeline import make_batch
    from repro.optim.optimizers import adamw
    from repro.train import (
        DeftRuntime,
        assign_buckets,
        build_bucket_layout,
        init_train_state,
        leaf_bucket_times,
        make_deft_step_fns,
    )

    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(0)
    dp = jax.device_count()
    mesh = jax.make_mesh((dp, 1), ("data", "model"))
    B, S = max(4, dp), 32

    probe = init_train_state(key, cfg, opt)
    bucket_of, nb = assign_buckets(probe["params"], cfg,
                                   partition_elems=150_000)
    times = leaf_bucket_times(probe["params"], cfg, bucket_of, nb,
                              HardwareModel(dp_degree=max(dp, 2)), S,
                              max(B // dp, 1))
    scale = 1.8 * (times.fwd_total + times.bwd_total) / max(
        times.comm_total, 1e-12
    )
    times = BucketTimes(times.fwd, times.bwd,
                        tuple(c * scale for c in times.comm))
    sched = solve_schedule(times, SchedulerConfig())
    layout = build_bucket_layout(probe["params"], bucket_of, nb)
    batch = make_batch(cfg, 0, 0, B, S)

    # the phase whose executable applies the (delayed) optimizer update —
    # the update-path comparison times this one phase across engines
    upd = next(i for i, ph in enumerate(sched.phases) if ph.do_update)

    with mesh:
        # ---- build every engine up front, then time them INTERLEAVED:
        # a load spike on a shared CPU host hits all engines of the rep,
        # not whichever happened to run second (same paired min-of-reps
        # harness as _bench_update_path — whole-phase wall times used to
        # be single-shot and load-noisy enough to invert orderings) -----
        t0 = time.perf_counter()
        fns = make_deft_step_fns(cfg, opt, sched, bucket_of, mesh)
        state_l = init_train_state(key, cfg, opt, deft=True,
                                   accum_devices=dp)
        legacy_build = time.perf_counter() - t0

        rt_tree = DeftRuntime(cfg, opt, sched, layout, mesh,
                              flat_state=False)
        state_t = rt_tree.init_state(key)
        rt_tree.compile(state_t, batch)

        t0 = time.perf_counter()
        rt = DeftRuntime(cfg, opt, sched, layout, mesh)
        state_f = rt.init_state(key)
        compile_s = sum(rt.compile(state_f, batch).values())
        fused_build = time.perf_counter() - t0

        engines = {
            "legacy": [lambda i, s: fns[i % sched.period](s, batch),
                       state_l],
            "tree":   [lambda i, s: rt_tree.step(i, s, batch), state_t],
            "flat":   [lambda i, s: rt.step(i, s, batch), state_f],
        }
        chunk = sched.period                 # period-aligned windows
        reps = max(_STEPS // chunk, 1)
        best, warmup_s, steps_done = _paired_min_of_reps(
            engines, warmup=sched.period, chunk=chunk, reps=reps
        )
        sps_legacy = 1.0 / best["legacy"]
        sps_tree = 1.0 / best["tree"]
        sps_fused = 1.0 / best["flat"]
        # comparable wall totals: build (the fused engine pays its AOT
        # compile there) + warmup (where the legacy path pays its lazy
        # first-dispatch compiles) + the timed steady-state steps
        timed = reps * chunk
        legacy_wall = (legacy_build + warmup_s["legacy"]
                       + timed * best["legacy"])
        fused_wall = fused_build + warmup_s["flat"] + timed * best["flat"]

        # ---- isolated update phase, same interleaved harness ----------
        phase_engines = {
            "legacy": [lambda i, s: fns[upd](s, batch),
                       engines["legacy"][1]],
            "tree": [lambda i, s: rt_tree.phase_executable(upd)(s, batch),
                     engines["tree"][1]],
            "flat": [lambda i, s: rt.phase_executable(upd)(s, batch),
                     engines["flat"][1]],
        }
        ph_best, _, _ = _paired_min_of_reps(
            phase_engines, warmup=2, chunk=chunk, reps=reps
        )
        upd_s_legacy = ph_best["legacy"]
        upd_s_tree = ph_best["tree"]
        upd_s_flat = ph_best["flat"]

    coll = rt.collectives_per_phase()
    per_leaf = [
        sum(
            len(layout.leaves[b]) for b in range(nb)
            if (ph.route_new[b] == "sync" and ph.rotate) or ph.sync_cur[b]
        )
        for ph in sched.phases
    ]
    return {
        "host_devices": dp,
        "model": {"name": cfg.name, "params": int(cfg.total_params()),
                  "n_leaves": layout.n_leaves, "n_buckets": nb},
        "schedule": {"period": sched.period,
                     "updates_per_period": sched.updates_per_period},
        "engine": {"flat_state": rt.flat_state,
                   "update_impl": rt.stats()["update_impl"]},
        "timing": "paired-interleaved-min-of-reps",
        "steps_timed": reps * chunk,
        "steps_per_s_fused": sps_fused,
        "steps_per_s_fused_tree": sps_tree,
        "steps_per_s_legacy": sps_legacy,
        "speedup_fused_vs_legacy": sps_fused / sps_legacy,
        "compile_s_fused_aot": compile_s,
        "wall_s_fused_total": fused_wall,
        "wall_s_legacy_total": legacy_wall,
        # wall time of the do_update phase across the three update paths:
        # flat fused-kernel engine vs PR-1 tree-state (per-leaf
        # apply_updates on fused buffers) vs the seed per-leaf step
        "update_phase_ms_flat": upd_s_flat * 1e3,
        "update_phase_ms_tree": upd_s_tree * 1e3,
        "update_phase_ms_legacy_per_leaf": upd_s_legacy * 1e3,
        "update_phase_speedup_flat_vs_per_leaf": upd_s_legacy / upd_s_flat,
        "update_phase_speedup_flat_vs_tree": upd_s_tree / upd_s_flat,
        "collectives_per_phase_fused": [
            c["primary"] + c["secondary"] for c in coll
        ],
        "collectives_per_phase_legacy_per_leaf": per_leaf,
    }


def _inner_fsdp() -> dict:
    """fsdp_flat scenario: the sharded flat-state engine (PR 4) on 4
    forced host devices — mesh (pod=2, data=2), param/moment buffers
    1/2-resident over 'data' — against the replicated flat engine on the
    same mesh, plus the ISOLATED sharded update-path comparison at the
    paper-regime leaf count (the stable signal; whole-phase CPU wall
    times stay load-noisy even interleaved).

    The per-leaf comparison is ZeRO-honest: the per-leaf reference
    updates the same 1/N-sized state, one op sequence per leaf — exactly
    what the tree-state RS path pays per shard — vs one fused kernel per
    bucket span."""
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduce_for_smoke
    from repro.core.bucket import BucketTimes
    from repro.core.deft import solve_schedule
    from repro.core.profiler import HardwareModel
    from repro.core.scheduler import SchedulerConfig
    from repro.data.pipeline import make_batch
    from repro.kernels.bucket_update import (
        apply_bucket_updates,
        build_segments,
        init_flat_opt_state,
    )
    from repro.optim.optimizers import adamw, apply_updates, init_opt_state
    from repro.train import (
        DeftRuntime,
        assign_buckets,
        build_bucket_layout,
        flatten_buckets,
        init_train_state,
        leaf_bucket_times,
    )

    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(0)
    mesh = jax.make_mesh((2, 2, 1), ("pod", "data", "model"))
    B, S = 8, 32

    probe = init_train_state(key, cfg, opt)
    bucket_of, nb = assign_buckets(probe["params"], cfg,
                                   partition_elems=150_000)
    times = leaf_bucket_times(probe["params"], cfg, bucket_of, nb,
                              HardwareModel(dp_degree=4), S, 2)
    scale = 1.8 * (times.fwd_total + times.bwd_total) / max(
        times.comm_total, 1e-12
    )
    times = BucketTimes(times.fwd, times.bwd,
                        tuple(c * scale for c in times.comm))
    sched = solve_schedule(times, SchedulerConfig())
    lay_sh = build_bucket_layout(probe["params"], bucket_of, nb,
                                 shard_count=2)
    lay_rep = build_bucket_layout(probe["params"], bucket_of, nb)
    batch = make_batch(cfg, 0, 0, B, S)
    upd = next(i for i, ph in enumerate(sched.phases) if ph.do_update)

    with mesh:
        rt_sh = DeftRuntime(cfg, opt, sched, lay_sh, mesh, fsdp=True)
        state_sh = rt_sh.init_state(key)
        compile_s = sum(rt_sh.compile(state_sh, batch).values())
        rt_rep = DeftRuntime(cfg, opt, sched, lay_rep, mesh,
                             multi_pod=True)
        state_rep = rt_rep.init_state(key)
        rt_rep.compile(state_rep, batch)

        engines = {
            "sharded": [lambda i, s: rt_sh.step(i, s, batch), state_sh],
            "replicated": [lambda i, s: rt_rep.step(i, s, batch),
                           state_rep],
        }
        chunk = sched.period                 # period-aligned windows
        reps = max(_STEPS // chunk, 1)
        best, _, _ = _paired_min_of_reps(
            engines, warmup=sched.period, chunk=chunk, reps=reps
        )

        phase_engines = {
            "sharded": [lambda i, s: rt_sh.phase_executable(upd)(s, batch),
                        engines["sharded"][1]],
            "replicated": [
                lambda i, s: rt_rep.phase_executable(upd)(s, batch),
                engines["replicated"][1],
            ],
        }
        ph_best, _, _ = _paired_min_of_reps(
            phase_engines, warmup=2, chunk=chunk, reps=reps
        )

    # ---- isolated sharded update path, paper-regime leaf count --------
    n_leaves, leaf_elems, n_buckets, n_shards = 256, 8192, 8, 4
    tree = _paper_tree(n_leaves, leaf_elems)
    grads = jax.tree.map(lambda p: p * 0.01, tree)
    bo = tuple(i * n_buckets // n_leaves for i in range(n_leaves))
    lay = build_bucket_layout(tree, bo, n_buckets, shard_count=n_shards)
    seg = build_segments(lay, opt)
    spans = lay.shard_sizes
    shard = lambda bufs: tuple(
        x[: spans[b]] for b, x in enumerate(bufs)
    )
    pbuf = shard(flatten_buckets(lay, jax.tree.leaves(tree)))
    gbuf = shard(flatten_buckets(lay, jax.tree.leaves(grads)))
    opt_full = init_flat_opt_state(opt, lay.buf_sizes)
    opt_sh = {"step": opt_full["step"], "m": shard(opt_full["m"]),
              "v": shard(opt_full["v"])}
    # ZeRO per-leaf twin: the same 1/N elements as one shard per leaf
    tree_sh = jax.tree.map(lambda x: x[: x.size // n_shards], tree)
    grads_sh = jax.tree.map(lambda x: x[: x.size // n_shards], grads)
    opt_leaf = init_opt_state(opt, tree_sh)

    sid = jnp.int32(0)
    f_flat = jax.jit(lambda p, g, o: apply_bucket_updates(
        opt, seg, p, g, o, grad_scale=0.1, shard_id=sid,
        norm_psum=lambda t: t)[:2])
    f_leaf = jax.jit(lambda p, g, o: apply_updates(
        opt, p, g, o, grad_scale=0.1))
    ms_flat, ms_leaf = _timed_apply_pair(
        f_flat, (pbuf, gbuf, opt_sh), f_leaf, (tree_sh, grads_sh, opt_leaf)
    )

    st = rt_sh.stats()
    return {
        "host_devices": jax.device_count(),
        "mesh": {"pod": 2, "data": 2, "model": 1},
        "model": {"name": cfg.name, "params": int(cfg.total_params()),
                  "n_leaves": lay_sh.n_leaves, "n_buckets": nb},
        "schedule": {"period": sched.period,
                     "updates_per_period": sched.updates_per_period},
        "engine": {"flat_state": True, "sharded_state": True,
                   "shards": lay_sh.shards,
                   "update_impl": st["update_impl"]},
        "timing": "paired-interleaved-min-of-reps",
        "steps_timed": reps * chunk,
        "compile_s_fused_aot": compile_s,
        "steps_per_s_sharded": 1.0 / best["sharded"],
        "steps_per_s_replicated_flat": 1.0 / best["replicated"],
        "update_phase_ms_sharded": ph_best["sharded"] * 1e3,
        "update_phase_ms_replicated_flat": ph_best["replicated"] * 1e3,
        "update_path_sharded": {
            "n_leaves": n_leaves,
            "n_buckets": n_buckets,
            "shard_count": n_shards,
            "total_elems": lay.total_elems,
            "apply_ms_flat_shard": ms_flat,
            "apply_ms_per_leaf_shard": ms_leaf,
            "speedup_flat_vs_per_leaf": ms_leaf / ms_flat,
        },
    }


def _inner_decoupled() -> dict:
    """decoupled scenario (DESIGN.md §12): the streamed-AG engine vs the
    fused-chain engine on 4 forced host devices, both running the SAME
    schedule (solved on the RS-side profile through the Planner) so the
    steps/s ratio isolates the all-gather placement.  Alongside the
    measured ratio:

    * simulated steady-state comparison — the fused plan prices the
      whole sync on backward capacity, the split plan prices the RS half
      there and streams the AG half against forward (coverage = the
      compute fraction of the simulated iteration; streaming can only
      add scheduling freedom, so decoupled >= fused);
    * the AG-burst static delta — bytes of full parameter buffers the
      fused engine gathers before the first forward block (every bucket
      at once) vs the decoupled peak (one bucket); the jaxpr census in
      tests/test_decoupled.py pins the same fact structurally.
    """
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax

    from repro.configs import get_config, reduce_for_smoke
    from repro.core.bucket import BucketTimes
    from repro.core.deft import Planner, PlanRequest, ag_times, rs_times
    from repro.core.profiler import HardwareModel
    from repro.core.scheduler import DeftScheduler
    from repro.core.simulator import simulate_deft
    from repro.data.pipeline import make_batch
    from repro.optim.optimizers import adamw
    from repro.train import (
        DeftRuntime,
        RuntimeConfig,
        assign_buckets,
        build_bucket_layout,
        init_train_state,
        leaf_bucket_times,
    )

    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(0)
    mesh = jax.make_mesh((2, 2, 1), ("pod", "data", "model"))
    B, S = 8, 32

    probe = init_train_state(key, cfg, opt)
    bucket_of, nb = assign_buckets(probe["params"], cfg,
                                   partition_elems=150_000)
    times = leaf_bucket_times(probe["params"], cfg, bucket_of, nb,
                              HardwareModel(dp_degree=4), S, 2)
    scale = 1.8 * (times.fwd_total + times.bwd_total) / max(
        times.comm_total, 1e-12
    )
    times = BucketTimes(times.fwd, times.bwd,
                        tuple(c * scale for c in times.comm))
    res = Planner().plan(PlanRequest(times=times, preserve=False,
                                     decoupled=True))
    sched, scfg, ag_plan = res.schedule, res.scheduler_cfg, res.ag_plan

    # ---- simulated steady state: both engines reduce-scatter on
    # backward capacity and all-gather around the forward; the fused
    # chain BURSTS the gathers before forward block 0 (WaitAll), the
    # decoupled plan STREAMS them against the forward window under
    # per-bucket deadlines — same wire bytes, different placement
    kw = dict(mu=scfg.mu, heterogeneous=scfg.heterogeneous)
    rs = rs_times(times)
    split = ag_times(times)
    plans_rs = DeftScheduler(rs, scfg).run(48)
    sim_d = simulate_deft(rs, plans_rs, ag_times=split,
                          ag_mode="streamed", **kw)
    sim_b = simulate_deft(rs, plans_rs, ag_times=split,
                          ag_mode="burst", **kw)

    lay = build_bucket_layout(probe["params"], bucket_of, nb,
                              shard_count=2)
    batch = make_batch(cfg, 0, 0, B, S)
    with mesh:
        rt_f = DeftRuntime(cfg, opt, sched, lay, mesh, fsdp=True)
        state_f = rt_f.init_state(key)
        rt_f.compile(state_f, batch)
        rt_d = DeftRuntime(cfg, opt, sched, lay, mesh,
                           config=RuntimeConfig(fsdp=True, decoupled=True))
        state_d = rt_d.init_state(key)
        compile_s = sum(rt_d.compile(state_d, batch).values())

        engines = {
            "fused": [lambda i, s: rt_f.step(i, s, batch), state_f],
            "decoupled": [lambda i, s: rt_d.step(i, s, batch), state_d],
        }
        chunk = sched.period                 # period-aligned windows
        # 3x the default step budget: the floor test pins the ratio of
        # two near-identical engines at >= 1.0, which needs tighter
        # min-of-reps convergence than the cross-engine scenarios
        reps = max(3 * _STEPS // chunk, 2)
        best, _, _ = _paired_min_of_reps(
            engines, warmup=sched.period, chunk=chunk, reps=reps
        )

    # static AG-burst accounting: full f32 param buffers gathered before
    # the first forward block — fused bursts every bucket, decoupled
    # peaks at its largest single bucket
    bytes_per = [s * 4 for s in lay.buf_sizes]
    burst_fused = sum(bytes_per)
    burst_dec = max(bytes_per)
    return {
        "host_devices": jax.device_count(),
        "mesh": {"pod": 2, "data": 2, "model": 1},
        "model": {"name": cfg.name, "params": int(cfg.total_params()),
                  "n_leaves": lay.n_leaves, "n_buckets": nb},
        "schedule": {"period": sched.period,
                     "updates_per_period": sched.updates_per_period},
        "engine": {"flat_state": True, "sharded_state": True,
                   "shards": lay.shards, "decoupled": True},
        "timing": "paired-interleaved-min-of-reps",
        "steps_timed": reps * chunk,
        "compile_s_decoupled_aot": compile_s,
        "steps_per_s_fused": 1.0 / best["fused"],
        "steps_per_s_decoupled": 1.0 / best["decoupled"],
        "steps_per_s_ratio_decoupled_vs_fused": (
            best["fused"] / best["decoupled"]
        ),
        "sim": {
            "iteration_time_fused_burst": sim_b.iteration_time,
            "iteration_time_decoupled_streamed": sim_d.iteration_time,
            "coverage_fused": 1.0 - sim_b.bubble_fraction,
            "coverage_decoupled": 1.0 - sim_d.bubble_fraction,
            "ag_stall_s_streamed": sim_d.ag_stall_s,
            "ag_plan_coverage": ag_plan.coverage,
            "ag_plan_items": len(ag_plan.items),
        },
        "ag_burst_bytes_fused": burst_fused,
        "ag_burst_bytes_decoupled_peak": burst_dec,
        "ag_burst_bytes_delta": burst_fused - burst_dec,
    }


def _inner_precision() -> dict:
    """precision scenario (DESIGN.md §13): planner-chosen mixed wire
    precision vs all-f32 under constrained bandwidth, on 4 forced host
    devices.  The comm profile is scaled so the f32 wire time is ~1.8x
    the compute window — the regime where the §13 ladder has headroom —
    and the Planner prices the full per-bucket ladder.  Reported
    side by side:

    * simulated steady state from the planner's own priced candidates —
      the adopted mixed policy's coverage must be >= the all-f32 row's
      (downgrading wire bytes can only relieve the comm capacity; the
      floor test pins this on the checked-in file);
    * measured steps/s of the SAME schedule executed with the f32
      layout vs the precision layout.  On CPU hosts the collectives are
      local memcpys while the quantize kernels are real work, so the
      measured ratio is reported, not floored — the wire-byte win needs
      a real interconnect to show up in wall time (the exact
      plan-vs-measured byte accounting is pinned by
      tests/test_precision.py::test_runtime_wire_bytes_match_plan).
    """
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax

    from repro.configs import get_config, reduce_for_smoke
    from repro.core.bucket import BucketTimes
    from repro.core.deft import Planner, PlanRequest
    from repro.core.preserver import WalkParams
    from repro.core.profiler import HardwareModel
    from repro.data.pipeline import make_batch
    from repro.optim.optimizers import adamw
    from repro.train import (
        DeftRuntime,
        assign_buckets,
        build_bucket_layout,
        init_train_state,
        leaf_bucket_times,
    )

    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(0)
    dp = jax.device_count()
    mesh = jax.make_mesh((dp, 1), ("data", "model"))
    B, S = max(4, dp), 32

    probe = init_train_state(key, cfg, opt)
    bucket_of, nb = assign_buckets(probe["params"], cfg,
                                   partition_elems=150_000)
    times = leaf_bucket_times(probe["params"], cfg, bucket_of, nb,
                              HardwareModel(dp_degree=max(dp, 2)), S,
                              max(B // dp, 1))
    # constrained bandwidth: f32 wire time ~1.8x the compute window
    # (CR 1.8, same regime as the other scenarios) — compute cannot
    # cover the f32 wire, so the ladder has headroom.  The whole
    # profile is then scaled into the paper regime (compute ~100 ms per
    # iteration): the smoke model's microsecond comm times sit BELOW
    # the 20 us collective-latency floor, where the §13 pricing rightly
    # refuses to downgrade — bandwidth-dominated times are the regime
    # the policy is for.  Only the schedule structure feeds the
    # measured engines, so the time unit is free to choose.
    scale = 1.8 * (times.fwd_total + times.bwd_total) / max(
        times.comm_total, 1e-12
    )
    ms = 0.1 / max(times.fwd_total + times.bwd_total, 1e-12)
    times = BucketTimes(tuple(f * ms for f in times.fwd),
                        tuple(b * ms for b in times.bwd),
                        tuple(c * scale * ms for c in times.comm))
    walk = WalkParams(s0=4.0, eta=0.01, mu=1.0, sigma=40.0, batch=256)
    res = Planner().plan(PlanRequest(times=times, walk=walk,
                                     wire_precision="auto"))
    sched = res.schedule
    f32 = next(s for s in res.precision_candidates
               if all(w == "f32" for w in s.policy.wire))
    mix = (next(s for s in res.precision_candidates
                if s.policy == res.precision)
           if res.precision is not None else f32)

    lay_f32 = build_bucket_layout(probe["params"], bucket_of, nb)
    lay_mix = (lay_f32.with_precision(res.precision)
               if res.precision is not None else lay_f32)
    batch = make_batch(cfg, 0, 0, B, S)
    with mesh:
        rt_f = DeftRuntime(cfg, opt, sched, lay_f32, mesh)
        state_f = rt_f.init_state(key)
        rt_f.compile(state_f, batch)
        rt_m = DeftRuntime(cfg, opt, sched, lay_mix, mesh)
        state_m = rt_m.init_state(key)
        compile_s = sum(rt_m.compile(state_m, batch).values())

        engines = {
            "f32": [lambda i, s: rt_f.step(i, s, batch), state_f],
            "mixed": [lambda i, s: rt_m.step(i, s, batch), state_m],
        }
        chunk = sched.period                 # period-aligned windows
        reps = max(_STEPS // chunk, 1)
        best, _, _ = _paired_min_of_reps(
            engines, warmup=sched.period, chunk=chunk, reps=reps
        )

    return {
        "host_devices": dp,
        "model": {"name": cfg.name, "params": int(cfg.total_params()),
                  "n_leaves": lay_f32.n_leaves, "n_buckets": nb},
        "schedule": {"period": sched.period,
                     "updates_per_period": sched.updates_per_period},
        "engine": {"flat_state": True,
                   "wire_precision": (res.precision.describe()
                                      if res.precision else "f32x%d" % nb),
                   "master_dtype": "f32"},
        "timing": "paired-interleaved-min-of-reps",
        "steps_timed": reps * chunk,
        "compile_s_mixed_aot": compile_s,
        "steps_per_s_f32": 1.0 / best["f32"],
        "steps_per_s_mixed": 1.0 / best["mixed"],
        "steps_per_s_ratio_mixed_vs_f32": best["f32"] / best["mixed"],
        "sim": {
            "iteration_time_f32": f32.iteration_time,
            "iteration_time_mixed": mix.iteration_time,
            "coverage_f32": f32.coverage,
            "coverage_mixed": mix.coverage,
            "wire_bytes_scale_mixed": mix.wire_bytes_scale,
            "gate_ok_mixed": bool(mix.verdict.ok),
            "ladder_candidates": len(res.precision_candidates),
        },
        "wire_bytes_per_cycle_f32": sum(rt_f.wire_bytes_per_phase),
        "wire_bytes_per_cycle_mixed": sum(rt_m.wire_bytes_per_phase),
    }


def _inner_two_link() -> dict:
    """two_link scenario (DESIGN.md §14): per-link ring-chain execution
    of the secondary RS/AG traffic vs the single-axis collectives, on 4
    forced host devices.  Reported side by side:

    * simulated steady state of the SAME profile solved with the
      secondary link priced (two-link knapsack) vs solved single-link —
      the secondary chain can only add communication capacity, so
      two-link coverage >= single-link (the floor test pins this on the
      checked-in file);
    * measured steps/s of the SAME schedule executed through the
      per-link chain collectives vs the single-axis originals.  Every
      synced bucket is forced onto the secondary link and every
      streamed AG item onto link 1 (maximal chain routing — the parity
      suite proves routing is bitwise-neutral), so the ratio isolates
      the chain's ppermute cost.  On CPU hosts the n-1 store-and-forward
      hops are real memcpys while XLA's fused collectives are one, so
      the ratio is reported, not floored — the chain wins only when the
      secondary link is real extra wire;
    * the per-link wire-byte audit: traced primary/secondary bytes per
      cycle must match the planned split exactly
      (``obs.wire_bytes_report`` with ``planned_split``).
    """
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses

    import jax

    from repro.configs import get_config, reduce_for_smoke
    from repro.core.bucket import BucketTimes
    from repro.core.deft import Planner, PlanRequest, ag_times, rs_times
    from repro.core.profiler import HardwareModel
    from repro.core.scheduler import DeftScheduler
    from repro.core.simulator import simulate_deft
    from repro.data.pipeline import make_batch
    from repro.launch.mesh import ring_chain
    from repro.obs import Tracer, wire_bytes_report
    from repro.optim.optimizers import adamw
    from repro.train import (
        DeftRuntime,
        RuntimeConfig,
        assign_buckets,
        build_bucket_layout,
        init_train_state,
        leaf_bucket_times,
    )

    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(0)
    mesh = jax.make_mesh((4, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    chain = ring_chain(4, 1)
    B, S = 8, 32

    probe = init_train_state(key, cfg, opt)
    bucket_of, nb = assign_buckets(probe["params"], cfg,
                                   partition_elems=150_000)
    times = leaf_bucket_times(probe["params"], cfg, bucket_of, nb,
                              HardwareModel(dp_degree=4), S, 2)
    scale = 1.8 * (times.fwd_total + times.bwd_total) / max(
        times.comm_total, 1e-12
    )
    times = BucketTimes(times.fwd, times.bwd,
                        tuple(c * scale for c in times.comm))
    res = Planner().plan(PlanRequest(times=times, preserve=False,
                                     decoupled=True))
    sched, scfg, ag_plan = res.schedule, res.scheduler_cfg, res.ag_plan

    # ---- simulated steady state: same RS-side profile solved with the
    # secondary link priced vs solved single-link — the two-link
    # knapsack sees strictly more capacity
    rs = rs_times(times)
    split = ag_times(times)
    scfg_one = dataclasses.replace(scfg, heterogeneous=False)
    plans_two = DeftScheduler(rs, scfg).run(48)
    plans_one = DeftScheduler(rs, scfg_one).run(48)
    sim_two = simulate_deft(rs, plans_two, mu=scfg.mu,
                            heterogeneous=True,
                            link_models=scfg.link_models,
                            ag_times=split, ag_mode="streamed")
    sim_one = simulate_deft(rs, plans_one, mu=scfg.mu,
                            heterogeneous=False,
                            ag_times=split, ag_mode="streamed")
    slots_planned = sum(sum(ph.secondary) for ph in sched.phases)
    ag_link1_planned = sum(1 for i in ag_plan.items if i.link == 1)

    # ---- forced maximal routing: every synced bucket on the secondary
    # link, every streamed AG item on link 1 — deterministic regardless
    # of what the knapsack picked for this profile, and bitwise-neutral
    # (tests/test_chain_parity.py), so the paired timing isolates the
    # chain collectives themselves
    phases = []
    for ph in sched.phases:
        sec = tuple(
            (ph.route_new[b] == "sync" and ph.rotate) or ph.sync_cur[b]
            for b in range(len(ph.route_new))
        )
        phases.append(dataclasses.replace(ph, secondary=sec))
    sched = dataclasses.replace(sched, phases=tuple(phases))
    slots_forced = sum(sum(ph.secondary) for ph in sched.phases)
    ag_plan = dataclasses.replace(
        ag_plan,
        items=tuple(dataclasses.replace(i, link=1) for i in ag_plan.items),
    )

    lay = build_bucket_layout(probe["params"], bucket_of, nb,
                              shard_count=4)
    batch = make_batch(cfg, 0, 0, B, S)
    base = RuntimeConfig(fsdp=True, decoupled=True)
    tracer = Tracer(capacity=1 << 16)
    with jax.set_mesh(mesh):
        rt_s = DeftRuntime(cfg, opt, sched, lay, mesh, config=base)
        state_s = rt_s.init_state(key)
        rt_s.compile(state_s, batch)
        rt_c = DeftRuntime(cfg, opt, sched, lay, mesh,
                           config=base.replace(secondary_chain=chain),
                           ag_plan=ag_plan, tracer=tracer)
        state_c = rt_c.init_state(key)
        compile_s = sum(rt_c.compile(state_c, batch).values())

        engines = {
            "single_axis": [lambda i, s: rt_s.step(i, s, batch), state_s],
            "chain": [lambda i, s: rt_c.step(i, s, batch), state_c],
        }
        chunk = sched.period                 # period-aligned windows
        reps = max(_STEPS // chunk, 1)
        best, _, _ = _paired_min_of_reps(
            engines, warmup=sched.period, chunk=chunk, reps=reps
        )

    # per-link wire-byte audit over the traced chain steps: totals AND
    # the primary/secondary split must match the plan exactly
    rep = wire_bytes_report(tracer, rt_c.wire_bytes_per_phase,
                            planned_split=rt_c.wire_bytes_split_per_phase)
    wire_split = rt_c.wire_bytes_split_per_phase
    return {
        "host_devices": jax.device_count(),
        "mesh": {"data": 4, "model": 1},
        "model": {"name": cfg.name, "params": int(cfg.total_params()),
                  "n_leaves": lay.n_leaves, "n_buckets": nb},
        "schedule": {"period": sched.period,
                     "updates_per_period": sched.updates_per_period,
                     "secondary_slots_planned": slots_planned,
                     "secondary_slots_forced": slots_forced,
                     "ag_items": len(ag_plan.items),
                     "ag_items_link1_planned": ag_link1_planned},
        "engine": {"flat_state": True, "sharded_state": True,
                   "shards": lay.shards, "decoupled": True,
                   "secondary_chain": list(chain)},
        "timing": "paired-interleaved-min-of-reps",
        "steps_timed": reps * chunk,
        "compile_s_chain_aot": compile_s,
        "steps_per_s_single_axis": 1.0 / best["single_axis"],
        "steps_per_s_chain": 1.0 / best["chain"],
        "steps_per_s_ratio_chain_vs_single_axis": (
            best["single_axis"] / best["chain"]
        ),
        "sim": {
            "mu": scfg.mu,
            "iteration_time_single_link": sim_one.iteration_time,
            "iteration_time_two_link": sim_two.iteration_time,
            "coverage_single_link": 1.0 - sim_one.bubble_fraction,
            "coverage_two_link": 1.0 - sim_two.bubble_fraction,
        },
        "wire_bytes_primary_per_cycle": sum(p for p, _ in wire_split),
        "wire_bytes_secondary_per_cycle": sum(s for _, s in wire_split),
        "wire_split_max_abs_error": rep.max_abs_split_error,
        "wire_split_ok": bool(rep.ok),
    }


def _bench_update_path() -> dict:
    """Isolated optimizer-apply wall time: fused flat bucket kernels
    (kernels/bucket_update) vs per-leaf apply_updates over the same
    values.  min-of-reps timing (robust to CPU load spikes — the
    whole-phase numbers in the scenario entries bury the update under
    fwd/bwd noise).  Two granularities:

    * ``smoke_config`` — the smoke model's real layout (few stacked
      leaves; memory-bound, so CPU parity is the expected result);
    * ``paper_leafcount`` — a few hundred tensors as in the paper's
      DNN/LLM profiles, where the per-tensor op overhead the engine
      removes (the MG-WFBP/DeAR motivation) actually shows.
    """
    import jax

    from repro.configs import get_config, reduce_for_smoke
    from repro.kernels.bucket_update import (
        apply_bucket_updates,
        build_segments,
        init_flat_opt_state,
    )
    from repro.optim.optimizers import adamw, apply_updates, init_opt_state
    from repro.train import (
        assign_buckets,
        build_bucket_layout,
        flatten_buckets,
        init_train_state,
    )

    opt = adamw(1e-3)

    def measure(params, layout) -> dict:
        grads = jax.tree.map(lambda p: p * 0.01, params)
        seg = build_segments(layout, opt)
        pbuf = tuple(flatten_buckets(layout, jax.tree.leaves(params)))
        gbuf = tuple(flatten_buckets(layout, jax.tree.leaves(grads)))
        opt_f = init_flat_opt_state(opt, layout.buf_sizes)
        opt_l = init_opt_state(opt, params)
        f_flat = jax.jit(lambda p, g, o: apply_bucket_updates(
            opt, seg, p, g, o, grad_scale=0.1)[:2])
        f_leaf = jax.jit(lambda p, g, o: apply_updates(
            opt, p, g, o, grad_scale=0.1))
        ms_flat, ms_leaf = _timed_apply_pair(
            f_flat, (pbuf, gbuf, opt_f), f_leaf, (params, grads, opt_l)
        )
        return {
            "n_leaves": layout.n_leaves,
            "n_buckets": layout.n_buckets,
            "total_elems": layout.total_elems,
            "apply_ms_flat": ms_flat,
            "apply_ms_per_leaf": ms_leaf,
            "speedup_flat_vs_per_leaf": ms_leaf / ms_flat,
        }

    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    probe = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    bucket_of, nb = assign_buckets(probe["params"], cfg,
                                   partition_elems=150_000)
    smoke = measure(probe["params"],
                    build_bucket_layout(probe["params"], bucket_of, nb))

    n_leaves, leaf_elems, n_buckets = 256, 8192, 8
    tree = _paper_tree(n_leaves, leaf_elems)
    bo = tuple(i * n_buckets // n_leaves for i in range(n_leaves))
    paper = measure(tree, build_bucket_layout(tree, bo, n_buckets))
    return {"smoke_config": smoke, "paper_leafcount": paper}


def _bench_repack(step_s_smoke: float) -> dict:
    """Cycle-boundary re-pack cost (DESIGN.md §9): the runtime's own
    jitted LayoutTransition pass between two partitions of the smoke
    model, alternated A->B->A (donated state stays live), min-of-reps —
    against the isolated flat update apply (the cheapest thing a phase
    does) and the smoke scenario's whole-step time (the amortization
    denominator: a repack happens once per adopted repartition, i.e.
    every O(100) steps at realistic replan cadence)."""
    import jax

    from repro.configs import get_config, reduce_for_smoke
    from repro.core.deft import solve_schedule
    from repro.core.scheduler import SchedulerConfig
    from repro.kernels.bucket_update import (
        apply_bucket_updates,
        build_segments,
        init_flat_opt_state,
    )
    from repro.optim.optimizers import adamw
    from repro.train import (
        DeftRuntime,
        assign_buckets,
        build_bucket_layout,
        build_layout_transition,
        flatten_buckets,
        init_train_state,
        leaf_bucket_times,
    )
    from repro.core.profiler import HardwareModel

    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(0)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    probe = init_train_state(key, cfg, opt)
    bo_a, nb_a = assign_buckets(probe["params"], cfg,
                                partition_elems=150_000)
    bo_b, nb_b = assign_buckets(probe["params"], cfg,
                                partition_elems=400_000)
    lay_a = build_bucket_layout(probe["params"], bo_a, nb_a)
    lay_b = build_bucket_layout(probe["params"], bo_b, nb_b)
    tr_ab = build_layout_transition(lay_a, lay_b)
    tr_ba = build_layout_transition(lay_b, lay_a)
    times = leaf_bucket_times(probe["params"], cfg, bo_a, nb_a,
                              HardwareModel(dp_degree=2), 32, 4)
    sched = solve_schedule(times, SchedulerConfig())
    with mesh:
        # construction only jits (no phase compiles): repack_state is
        # the runtime's real staged-swap executable
        rt = DeftRuntime(cfg, opt, sched, lay_a, mesh)
        state = rt.init_state(key)
        reps = 7
        best_ab = best_ba = float("inf")
        for _ in range(1 + reps):                 # first rep compiles
            t0 = time.perf_counter()
            state = rt.repack_state(state, tr_ab)
            jax.block_until_ready(jax.tree_util.tree_leaves(state))
            ab = time.perf_counter() - t0
            t0 = time.perf_counter()
            state = rt.repack_state(state, tr_ba)
            jax.block_until_ready(jax.tree_util.tree_leaves(state))
            ba = time.perf_counter() - t0
            if _ > 0:
                best_ab, best_ba = min(best_ab, ab), min(best_ba, ba)

        # isolated flat update apply under layout A (same harness as
        # _bench_update_path): the per-phase work a repack competes with
        grads = jax.tree.map(lambda p: p * 0.01, probe["params"])
        seg = build_segments(lay_a, opt)
        pbuf = tuple(flatten_buckets(lay_a, jax.tree.leaves(probe["params"])))
        gbuf = tuple(flatten_buckets(lay_a, jax.tree.leaves(grads)))
        opt_f = init_flat_opt_state(opt, lay_a.buf_sizes)
        f_flat = jax.jit(lambda p, g, o: apply_bucket_updates(
            opt, seg, p, g, o, grad_scale=0.1)[:2])
        jax.block_until_ready(f_flat(pbuf, gbuf, opt_f))
        apply_ms = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = f_flat(pbuf, gbuf, opt_f)
            jax.block_until_ready(out)
            apply_ms = min(apply_ms, (time.perf_counter() - t0) * 1e3)

    repack_ms = best_ab * 1e3
    return {
        "n_buckets_a": nb_a,
        "n_buckets_b": nb_b,
        "total_elems": lay_a.total_elems,
        "moved_elems_a_to_b": tr_ab.moved_elems,
        "repack_ms_a_to_b": repack_ms,
        "repack_ms_b_to_a": best_ba * 1e3,
        "update_phase_apply_ms": apply_ms,
        "repack_over_update_apply": repack_ms / max(apply_ms, 1e-9),
        "step_ms_smoke": step_s_smoke * 1e3,
        "amortized_overhead_at_replan_every_100_steps": (
            repack_ms / 1e3 / max(100.0 * step_s_smoke, 1e-12)
        ),
    }


def _bench_solver() -> dict:
    """Planning time of the two-stage Solver over the 96-iteration
    horizon, memoized vs unmemoized, on a paper-scale profile."""
    from repro.core.bucket import BucketTimes
    from repro.core.deft import solve_schedule
    from repro.core.knapsack import (
        clear_knapsack_caches,
        knapsack_cache_info,
        set_knapsack_memoization,
    )
    from repro.core.scheduler import SchedulerConfig

    rng = random.Random(0)
    n = 12
    fwd = tuple(rng.uniform(0.001, 0.05) for _ in range(n))
    bwd = tuple(2 * f for f in fwd)
    comm = tuple(rng.uniform(0.01, 0.3) for _ in range(n))
    times = BucketTimes(fwd, bwd, comm)
    scfg = SchedulerConfig()
    reps = 5

    prev = set_knapsack_memoization(False)
    t0 = time.perf_counter()
    for _ in range(reps):
        solve_schedule(times, scfg)
    plan_unmemo = (time.perf_counter() - t0) / reps

    set_knapsack_memoization(True)
    clear_knapsack_caches()
    t0 = time.perf_counter()
    for _ in range(reps):
        solve_schedule(times, scfg)
    plan_memo = (time.perf_counter() - t0) / reps
    cache = knapsack_cache_info()
    set_knapsack_memoization(prev)
    return {
        "n_buckets": n,
        "horizon_reps": reps,
        "plan_s_unmemoized": plan_unmemo,
        "plan_s_memoized": plan_memo,
        "speedup": plan_unmemo / max(plan_memo, 1e-12),
        "cache_hits": cache.hits,
        "cache_misses": cache.misses,
    }


def _child(name: str, *args: str) -> dict:
    """Run one JAX section in its own process and return its JSON line.
    The parent never imports JAX, so on an accelerator each child holds
    the device alone, one after another."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), *args],
        env=env, capture_output=True, text=True, timeout=1800,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"runtime bench ({name}) failed:\n{proc.stderr[-2000:]}"
        )
    return json.loads(proc.stdout.splitlines()[-1])


def run() -> None:
    """Benchmark section entry point (benchmarks/run.py)."""
    t0 = time.time()
    results: dict = {"solver": _bench_solver()}   # pure Python, no JAX
    for name, args in (("update_path", ["--inner-update-path"]),
                       ("smoke", ["--inner", "1"]),
                       ("dp4", ["--inner", "4"]),
                       ("fsdp_flat", ["--inner-fsdp"]),
                       ("decoupled", ["--inner-decoupled"]),
                       ("precision", ["--inner-precision"]),
                       ("two_link", ["--inner-two-link"])):
        results[name] = _child(name, *args)

    # repack runs AFTER the scenarios: the smoke scenario's steady-state
    # step time is its amortization denominator
    results["repack"] = _child(
        "repack", "--inner-repack",
        repr(1.0 / results["smoke"]["steps_per_s_fused"]),
    )

    tmp = _OUT + ".tmp"
    json.dump(results, open(tmp, "w"), indent=1)
    os.replace(tmp, _OUT)

    for name in ("smoke", "dp4"):
        r = results[name]
        print(f"runtime_{name}_steps_per_s_fused,"
              f"{1e6 / r['steps_per_s_fused']:.0f},"
              f"{r['steps_per_s_fused']:.3f} steps/s")
        print(f"runtime_{name}_steps_per_s_legacy,"
              f"{1e6 / r['steps_per_s_legacy']:.0f},"
              f"{r['steps_per_s_legacy']:.3f} steps/s")
        print(f"runtime_{name}_speedup,{r['speedup_fused_vs_legacy']:.2f},"
              f"fused vs per-leaf on {r['host_devices']} device(s)")
        print(f"runtime_{name}_collectives_per_phase,"
              f"{max(r['collectives_per_phase_fused'])},"
              f"legacy per-leaf "
              f"{max(r['collectives_per_phase_legacy_per_leaf'])}")
        print(f"runtime_{name}_update_phase_ms,"
              f"{r['update_phase_ms_flat'] * 1e3:.0f},"
              f"flat {r['update_phase_ms_flat']:.1f}ms vs per-leaf "
              f"{r['update_phase_ms_legacy_per_leaf']:.1f}ms "
              f"({r['update_phase_speedup_flat_vs_per_leaf']:.2f}x) / "
              f"tree {r['update_phase_ms_tree']:.1f}ms "
              f"({r['update_phase_speedup_flat_vs_tree']:.2f}x)")
    fs = results["fsdp_flat"]
    us = fs["update_path_sharded"]
    print(f"runtime_fsdp_flat_steps_per_s,"
          f"{1e6 / fs['steps_per_s_sharded']:.0f},"
          f"sharded {fs['steps_per_s_sharded']:.3f} vs replicated-flat "
          f"{fs['steps_per_s_replicated_flat']:.3f} steps/s "
          f"(1/{fs['engine']['shards']} resident opt state)")
    print(f"update_path_sharded_apply_ms,{us['apply_ms_flat_shard'] * 1e3:.0f},"
          f"shard-fused {us['apply_ms_flat_shard']:.2f}ms vs ZeRO per-leaf "
          f"{us['apply_ms_per_leaf_shard']:.2f}ms "
          f"({us['speedup_flat_vs_per_leaf']:.2f}x, {us['n_leaves']} leaves "
          f"-> {us['n_buckets']} buckets, {us['shard_count']} shards)")
    dc = results["decoupled"]
    print(f"runtime_decoupled_steps_per_s,"
          f"{1e6 / dc['steps_per_s_decoupled']:.0f},"
          f"streamed-AG {dc['steps_per_s_decoupled']:.3f} vs fused-chain "
          f"{dc['steps_per_s_fused']:.3f} steps/s "
          f"({dc['steps_per_s_ratio_decoupled_vs_fused']:.2f}x)")
    print(f"runtime_decoupled_sim_coverage,"
          f"{dc['sim']['coverage_decoupled'] * 1e4:.0f},"
          f"decoupled {dc['sim']['coverage_decoupled']:.3f} vs fused "
          f"{dc['sim']['coverage_fused']:.3f} "
          f"(AG plan coverage {dc['sim']['ag_plan_coverage']:.3f})")
    print(f"runtime_decoupled_ag_burst_bytes_delta,"
          f"{dc['ag_burst_bytes_delta']},"
          f"fused bursts {dc['ag_burst_bytes_fused'] / 1e6:.1f}MB "
          f"pre-forward vs decoupled peak "
          f"{dc['ag_burst_bytes_decoupled_peak'] / 1e6:.1f}MB")
    pc = results["precision"]
    print(f"runtime_precision_sim_coverage,"
          f"{pc['sim']['coverage_mixed'] * 1e4:.0f},"
          f"mixed {pc['sim']['coverage_mixed']:.3f} vs f32 "
          f"{pc['sim']['coverage_f32']:.3f} "
          f"({pc['engine']['wire_precision']}, wire bytes "
          f"x{pc['sim']['wire_bytes_scale_mixed']:.2f})")
    print(f"runtime_precision_steps_per_s,"
          f"{1e6 / pc['steps_per_s_mixed']:.0f},"
          f"mixed {pc['steps_per_s_mixed']:.3f} vs f32 "
          f"{pc['steps_per_s_f32']:.3f} steps/s "
          f"({pc['steps_per_s_ratio_mixed_vs_f32']:.2f}x)")
    print(f"runtime_precision_wire_bytes_per_cycle,"
          f"{pc['wire_bytes_per_cycle_mixed']},"
          f"mixed {pc['wire_bytes_per_cycle_mixed'] / 1e6:.1f}MB vs f32 "
          f"{pc['wire_bytes_per_cycle_f32'] / 1e6:.1f}MB")
    tl = results["two_link"]
    print(f"runtime_two_link_sim_coverage,"
          f"{tl['sim']['coverage_two_link'] * 1e4:.0f},"
          f"two-link {tl['sim']['coverage_two_link']:.3f} vs single-link "
          f"{tl['sim']['coverage_single_link']:.3f} (mu {tl['sim']['mu']})")
    print(f"runtime_two_link_steps_per_s,"
          f"{1e6 / tl['steps_per_s_chain']:.0f},"
          f"chain {tl['steps_per_s_chain']:.3f} vs single-axis "
          f"{tl['steps_per_s_single_axis']:.3f} steps/s "
          f"({tl['steps_per_s_ratio_chain_vs_single_axis']:.2f}x)")
    print(f"runtime_two_link_wire_bytes_secondary,"
          f"{tl['wire_bytes_secondary_per_cycle']},"
          f"secondary {tl['wire_bytes_secondary_per_cycle'] / 1e6:.1f}MB vs "
          f"primary {tl['wire_bytes_primary_per_cycle'] / 1e6:.1f}MB per "
          f"cycle (split audit ok={tl['wire_split_ok']})")
    for gran, u in results["update_path"].items():
        print(f"update_path_{gran}_apply_ms,"
              f"{u['apply_ms_flat'] * 1e3:.0f},"
              f"flat {u['apply_ms_flat']:.2f}ms vs per-leaf "
              f"{u['apply_ms_per_leaf']:.2f}ms "
              f"({u['speedup_flat_vs_per_leaf']:.2f}x, "
              f"{u['n_leaves']} leaves -> {u['n_buckets']} buckets)")
    rp = results["repack"]
    print(f"repack_us,{rp['repack_ms_a_to_b'] * 1e3:.0f},"
          f"{rp['n_buckets_a']}->{rp['n_buckets_b']} buckets "
          f"{rp['repack_ms_a_to_b']:.1f}ms "
          f"(vs update apply {rp['update_phase_apply_ms']:.1f}ms; "
          f"{rp['amortized_overhead_at_replan_every_100_steps'] * 100:.2f}% "
          f"overhead at a replan every 100 steps)")
    s = results["solver"]
    print(f"solver_plan_us_memoized,{s['plan_s_memoized'] * 1e6:.0f},"
          f"{s['speedup']:.1f}x vs unmemoized "
          f"({s['plan_s_unmemoized'] * 1e3:.0f} ms)")
    print(f"# BENCH_runtime.json written in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1].startswith("--inner"):
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
    if len(sys.argv) > 2 and sys.argv[1] == "--inner":
        json.dump(_inner(int(sys.argv[2])), sys.stdout)
        print()
    elif len(sys.argv) > 1 and sys.argv[1] == "--inner-update-path":
        json.dump(_bench_update_path(), sys.stdout)
        print()
    elif len(sys.argv) > 2 and sys.argv[1] == "--inner-repack":
        json.dump(_bench_repack(float(sys.argv[2])), sys.stdout)
        print()
    elif len(sys.argv) > 1 and sys.argv[1] == "--inner-fsdp":
        json.dump(_inner_fsdp(), sys.stdout)
        print()
    elif len(sys.argv) > 1 and sys.argv[1] == "--inner-decoupled":
        json.dump(_inner_decoupled(), sys.stdout)
        print()
    elif len(sys.argv) > 1 and sys.argv[1] == "--inner-precision":
        json.dump(_inner_precision(), sys.stdout)
        print()
    elif len(sys.argv) > 1 and sys.argv[1] == "--inner-two-link":
        json.dump(_inner_two_link(), sys.stdout)
        print()
    else:
        run()
