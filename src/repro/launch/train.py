"""Training driver: DDP baseline or the full DeFT pipeline
(Profiler -> Solver -> Preserver -> per-phase compiled steps).

On a CPU it drives reduced configs over the debug mesh (set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before launch for
a multi-device mesh); on a TPU it drives the same code over the attached
chips, or ``make_production_mesh()`` on a full slice.  ``--layers N``
keeps a config's published widths and cuts only its depth, which is how
a model larger than one chip's memory trains there.

    PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --smoke \
        --scheduler deft --steps 60
    python -m repro.launch.train --arch qwen3-4b --layers 2 \
        --compute-dtype bf16 --batch 8 --seq 512 --steps 8
"""
from __future__ import annotations

import argparse
import dataclasses
import signal
import time
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.adapt import (
    AdaptConfig,
    AdaptiveController,
    BandwidthDrop,
    RepartitionConfig,
    Repartitioner,
    SyntheticTelemetrySource,
)
from repro.checkpoint.checkpoint import (
    latest_step,
    load_layout_descriptor,
    restore as restore_ckpt,
    save as save_ckpt,
    save_layout_descriptor,
    saved_keys,
    schedule_digest,
    valid_steps,
)
from repro.configs import ARCH_NAMES, get_config, reduce_for_smoke
from repro.core.bucket import BucketTimes
from repro.core.deft import Planner, PlanRequest
from repro.core.preserver import WalkParams
from repro.core.profiler import HardwareModel
from repro.core.scheduler import SchedulerConfig
from repro.data.pipeline import SyntheticDataset, batch_spec
from repro.elastic import (
    CapacityReturn,
    DeviceDrop,
    ElasticController,
    ElasticCoordinator,
    ElasticHalt,
    FaultScenario,
    HealthMonitor,
    StragglerSlowdown,
)
from repro.models.model import init_params
from repro.models.moe import STAT_KEYS
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import Metrics, Tracer, format_event
from repro.optim.optimizers import adamw
from repro.sharding.specs import needs_fsdp
from repro.train.bucketing import (
    assign_buckets,
    build_bucket_layout,
    build_leaf_time_model,
    coverage_rescale,
    leaf_bucket_times,
)
from repro.train.runtime import DeftRuntime, RuntimeConfig, make_ddp_step
from repro.train.steps import init_train_state


# Largest f32 logits block the LM head may hold at once (the head and
# loss run as models.model.chunked_ce, by sequence chunks of this size).
LOGITS_BUDGET_BYTES = 256 * 2**20


def loss_chunk_for(vocab_size: int, per_device_batch: int,
                   seq_len: int) -> int:
    """Sequence chunk of the LM head + loss: the whole sequence when the
    ``[B, S, V]`` f32 logits fit :data:`LOGITS_BUDGET_BYTES`, else the
    largest power of two whose ``[B, chunk, V]`` block does."""
    row = 4 * vocab_size * per_device_batch
    if row * seq_len <= LOGITS_BUDGET_BYTES:
        return seq_len
    chunk = 1
    while chunk * 2 * row <= LOGITS_BUDGET_BYTES and chunk * 2 < seq_len:
        chunk *= 2
    return chunk


def build_schedule(
    params,
    cfg,
    *,
    hw: HardwareModel,
    dp: int,
    seq_len: int,
    per_device_batch: int,
    partition_elems: int,
    coverage_rate: float = 0.0,
    heterogeneous: bool = True,
    mu: float = 1.65,
    eps: float = 0.01,
    max_retries: int = 10,
    wire_precision: str = "f32",
    master_dtype: str = "f32",
):
    """Leaf-bucket profile -> Solver -> Preserver feedback loop.

    coverage_rate > 0 rescales the analytic comm times to that CR — used
    by examples/tests to reproduce a paper regime (VGG-like CR=2, GPT-2
    CR=1) on arbitrary model sizes.  ``wire_precision`` engages the §13
    per-bucket precision ladder ('auto') or forces a uniform wire dtype;
    the returned ``PlanResult`` carries the adopted policy.
    """
    bucket_of, nb = assign_buckets(params, cfg, partition_elems)
    times = leaf_bucket_times(params, cfg, bucket_of, nb, hw, seq_len,
                              per_device_batch)
    if coverage_rate > 0:
        scale = coverage_rescale(times, coverage_rate)
        times = BucketTimes(times.fwd, times.bwd,
                            tuple(c * scale for c in times.comm))
    walk = WalkParams(s0=4.0, eta=0.01, mu=1.0, sigma=40.0, batch=256)
    res = Planner().plan(PlanRequest(
        times=times, walk=walk, heterogeneous=heterogeneous, mu=mu,
        eps=eps, max_retries=max_retries,
        wire_precision=wire_precision, master_dtype=master_dtype,
    ))
    return bucket_of, nb, times, res


def restore_runtime_state(runtime, ckpt_dir: str, params_abs):
    """Restore the newest *usable* checkpoint into ``runtime``'s resident
    state.  Returns ``(state, start_step)`` or ``(None, 0)`` when nothing
    on disk restores.

    Hardened resume semantics (DESIGN.md §10):

    * incomplete/torn checkpoints (a writer killed mid-save) never appear
      — ``valid_steps`` admits only atomically-committed steps;
    * a step that still fails to restore (e.g. a stale sidecar naming a
      layout the arrays don't match) falls back to the previous valid
      step with a warning instead of aborting the run;
    * a schedule-digest mismatch in the sidecar means the saved
      mid-cycle accumulator position is meaningless under the running
      schedule: the gather cache is dropped and the cycle restarts at
      the checkpoint step (cycle-start restore) with a clear warning —
      never a crash, never a silent mid-cycle misread.
    """
    layout = runtime.layout
    run_digest = schedule_digest(runtime.schedule)
    for last in reversed(valid_steps(ckpt_dir)):
        try:
            src_layout, next_phase, src_digest = \
                load_layout_descriptor(ckpt_dir, last, params_abs)
            if src_layout is None:
                src_layout, next_phase, src_digest = layout, 0, ""
            digest_ok = (not src_digest) or src_digest == run_digest
            # read the gather cache only if the checkpoint has one AND
            # the layout + schedule both match (tree_to_state re-inits
            # it cold otherwise; a digest mismatch restarts the cycle,
            # which re-gathers anyway)
            has_pg = any(k.startswith("pgather")
                         for k in saved_keys(ckpt_dir, last))
            ts = restore_ckpt(
                ckpt_dir, last,
                runtime.checkpoint_struct(
                    src_layout,
                    with_pgather=(has_pg and src_layout == layout
                                  and digest_ok),
                ),
            )
            # cross-layout restores route cur/fut through the
            # LayoutTransition span remap inside tree_to_state
            state = runtime.tree_to_state(ts, src_layout=src_layout)
        except Exception as e:      # torn arrays, stale sidecar, ...
            print(f"resume: checkpoint step {last} unusable "
                  f"({type(e).__name__}: {e}); trying the previous one")
            continue
        # continue mid-cycle ONLY under the byte-identical schedule (a
        # phase sequence that merely shares the period would misread the
        # mid-generation accumulators), and only if the gather cache the
        # resumed position may read was actually saved
        same_cycle = (
            src_layout == layout
            and src_digest == run_digest
            and (not runtime.stats()["gather_skip"] or has_pg)
        )
        runtime.reset_cycle(last - next_phase if same_cycle else last)
        if src_digest and not digest_ok:
            print(f"resume: WARNING schedule digest mismatch at step "
                  f"{last} (saved {src_digest}, running {run_digest}) — "
                  f"gather cache dropped, cycle restarted at the "
                  f"checkpoint step")
        print(f"resumed checkpoint step {last}"
              + (" (re-packed from a different layout)"
                 if src_layout != layout else "")
              + ("" if same_cycle else " (cycle restarted)"))
        return state, last
    return None, 0


def main(argv: Optional[Sequence[str]] = None) -> Dict[str, Any]:
    """Parse ``argv`` (default: the command line), train, and return what
    the run measured: ``losses`` and blocked per-step wall times
    ``step_s`` (one each per step), ``updated`` flags, ``compile_s`` (the
    DeFT AOT compile), ``donation_held`` (every step consumed its input
    state), the DeFT ``runtime`` (None for DDP), and
    ``cfg``/``n_params``/``mesh``/``loss_chunk``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="gemma2-2b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--layers", type=int, default=0,
                    help="keep the published widths and cut the depth to "
                         "this many layers (0 = the config's own depth)")
    ap.add_argument("--scheduler", choices=["ddp", "deft"], default="deft")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3,
                    help="AdamW learning rate (the default suits the "
                         "smoke widths; published widths want less)")
    ap.add_argument("--coverage-rate", type=float, default=1.8,
                    help="synthetic CR for the DeFT schedule (0 = analytic)")
    ap.add_argument("--partition-elems", type=int, default=200_000)
    ap.add_argument("--adapt", action="store_true",
                    help="online control plane: telemetry -> drift "
                         "detection -> replan -> phase hot-swap")
    ap.add_argument("--adapt-drop-step", type=int, default=0,
                    help="with --adapt: inject a synthetic bandwidth drop "
                         "at this step (0 = use real measured wall times)")
    ap.add_argument("--adapt-drop-scale", type=float, default=3.0,
                    help="comm slowdown factor of the injected drop")
    ap.add_argument("--adapt-repartition", action="store_true",
                    help="with --adapt: replans may change the bucket "
                         "partition itself — the runtime re-packs the "
                         "flat state at a cycle boundary, no restart")
    ap.add_argument("--elastic", action="store_true",
                    help="fault-tolerant control plane: per-shard health "
                         "monitoring -> Preserver-gated mesh scale-down/up "
                         "via a cycle-boundary repack, zero restart")
    ap.add_argument("--elastic-drop-step", type=int, default=0,
                    help="with --elastic: inject a device-drop fault at "
                         "this step (0 = none)")
    ap.add_argument("--elastic-drop-shards", default="",
                    help="comma-separated origin shard ids the injected "
                         "drop kills (default: the last data row)")
    ap.add_argument("--elastic-return-step", type=int, default=0,
                    help="with --elastic: the dropped shards come back at "
                         "this step (scale-up trigger; 0 = never)")
    ap.add_argument("--elastic-straggler-step", type=int, default=0,
                    help="with --elastic: one shard starts running slow "
                         "at this step (0 = none)")
    ap.add_argument("--elastic-straggler-shard", type=int, default=0)
    ap.add_argument("--elastic-straggler-factor", type=float, default=3.0)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="auto-checkpoint cadence in steps (0 = only at "
                         "the end); with --elastic this bounds lost work "
                         "on an unsurvivable fault")
    ap.add_argument("--compute-dtype", choices=["f32", "bf16"],
                    default="f32",
                    help="forward/backward precision: the flat DeFT "
                         "engines keep an f32 master copy; DDP keeps its "
                         "whole state at this dtype")
    ap.add_argument("--wire-precision",
                    choices=["auto", "f32", "bf16", "int8"],
                    default="f32",
                    help="gradient wire precision (DESIGN.md §13): "
                         "'auto' lets the planner pick a per-bucket "
                         "policy from the knapsack-priced ladder, gated "
                         "by the precision-aware Preserver; a dtype "
                         "forces that uniform wire")
    ap.add_argument("--master-dtype", choices=["f32", "bf16sr"],
                    default="f32",
                    help="resident master-param dtype: 'bf16sr' keeps "
                         "params at bf16 with seeded stochastic-rounded "
                         "updates (flat engine only; moments stay f32)")
    ap.add_argument("--decoupled", action="store_true",
                    help="stream per-bucket all-gathers into the forward "
                         "instead of the phase-start burst (DESIGN.md §12; "
                         "needs an FSDP arch)")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--data", type=int, default=0, help="debug mesh data axis")
    ap.add_argument("--model", type=int, default=0, help="debug mesh model axis")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default="", metavar="OUT.json",
                    help="record step/phase/collective/control-plane "
                         "spans and export a Chrome-trace (Perfetto-"
                         "loadable) JSON to this path")
    ap.add_argument("--ckpt", default="", help="checkpoint dir (optional)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint from --ckpt "
                         "before training (a checkpoint written under a "
                         "different bucket layout is re-packed through "
                         "the LayoutTransition)")
    args = ap.parse_args(argv)
    if args.layers and args.smoke:
        ap.error("--layers cuts the depth at published widths; --smoke "
                 "already shrinks the whole config")
    if args.layers < 0:
        ap.error("--layers must be >= 0")
    if args.elastic and args.adapt:
        ap.error("--elastic and --adapt are mutually exclusive: the "
                 "elastic controller owns replanning while it owns the "
                 "mesh (DESIGN.md §10)")
    if args.elastic and args.scheduler != "deft":
        ap.error("--elastic needs --scheduler deft (the migration path "
                 "repacks the flat DeFT state)")

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    # one tracer for the whole run: runtime step/phase spans, controller
    # replans, elastic lifecycle — all in one clock domain (DESIGN.md §11)
    tracer = Tracer() if args.trace else None
    n_dev = jax.device_count()
    if args.production_mesh:
        mesh = make_production_mesh()
    else:
        data = args.data or max(n_dev // 2, 1)
        model = args.model or (n_dev // data)
        mesh = make_debug_mesh(data=data, model=model)
    dp = dict(zip(mesh.axis_names, mesh.devices.shape))["data"]
    fsdp = needs_fsdp(cfg.name)
    opt = adamw(args.lr)
    key = jax.random.PRNGKey(args.seed)
    # the planner prices the attached chip; an unknown TPU kind raises
    hw = HardwareModel.for_device(jax.devices()[0], dp_degree=dp)
    loss_chunk = loss_chunk_for(cfg.vocab_size, max(args.batch // dp, 1),
                                args.seq)

    print(f"arch={cfg.name} layers={cfg.n_layers} "
          f"params={cfg.total_params():,} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"loss_chunk={loss_chunk}")
    ds = SyntheticDataset(cfg, args.seed, args.batch, args.seq)

    compute_dtype = jnp.bfloat16 if args.compute_dtype == "bf16" else None
    compile_s = 0.0
    with jax.set_mesh(mesh):
        runtime = None
        start_step = 0
        if args.scheduler == "ddp":
            state = init_train_state(key, cfg, opt,
                                     dtype=compute_dtype or jnp.float32)
            # donated: params/opt update in place instead of copying
            step_fn = make_ddp_step(cfg, opt, fsdp=fsdp,
                                    loss_chunk=loss_chunk)
            if args.resume and args.ckpt:
                last = latest_step(args.ckpt)
                if last is not None:
                    state = restore_ckpt(args.ckpt, last, state)
                    start_step = last
                    print(f"resumed checkpoint step {last}")
        else:
            # shape-only probe: bucketing/layout never read values, so an
            # eval_shape tree avoids materializing a throwaway full state
            params_abs = jax.eval_shape(
                lambda k: init_params(k, cfg), jax.random.PRNGKey(0)
            )
            bucket_of, nb, times, plan = build_schedule(
                params_abs, cfg, hw=hw, dp=dp, seq_len=args.seq,
                per_device_batch=max(args.batch // dp, 1),
                partition_elems=args.partition_elems,
                coverage_rate=args.coverage_rate,
                wire_precision=args.wire_precision,
                master_dtype=args.master_dtype,
            )
            schedule, verdict, scfg = (
                plan.schedule, plan.verdict, plan.scheduler_cfg
            )
            print(f"deft: {nb} buckets, CR={times.coverage_rate:.2f}, "
                  f"period={schedule.period}, "
                  f"updates/period={schedule.updates_per_period}, "
                  f"batch-size seq={schedule.batch_size_sequence}, "
                  f"preserver ratio={verdict.ratio:.4f} "
                  f"(capacity x{scfg.capacity_factor:.2f})")
            if plan.precision is not None:
                print(f"precision: wire={plan.precision.describe()} "
                      f"master={plan.precision.master}")
            # FSDP archs run the sharded flat engine: the layout pads
            # every bucket so it splits into dp equal lane-aligned spans
            layout = build_bucket_layout(params_abs, bucket_of, nb,
                                         shard_count=dp if fsdp else 1)
            if plan.precision is not None:
                layout = layout.with_precision(plan.precision)
            rcfg = RuntimeConfig(
                fsdp=fsdp, compute_dtype=compute_dtype, loss_chunk=loss_chunk,
                decoupled=args.decoupled,
                master_dtype=(args.master_dtype
                              if args.master_dtype != "f32" else None),
            )
            runtime = DeftRuntime(cfg, opt, schedule, layout, mesh,
                                  config=rcfg, tracer=tracer)
            state = None
            if args.resume and args.ckpt:
                state, start_step = restore_runtime_state(
                    runtime, args.ckpt, params_abs
                )
            if state is None:
                state = runtime.init_state(
                    key, dtype=compute_dtype or jnp.float32
                )
            t_c = time.perf_counter()
            # AOT phase cache against abstract batch specs: no data batch
            # is consumed, so step 0 still trains on the stream's batch 0
            runtime.compile(state, batch_spec(cfg, args.batch, args.seq))
            compile_s = time.perf_counter() - t_c
            st = runtime.stats()
            print(f"compiled {runtime.n_unique_phases} unique phases "
                  f"(period {runtime.period}) in {compile_s:.1f}s; "
                  f"max collectives in a phase: "
                  f"{st['max_collectives_in_a_phase']} "
                  f"(vs {layout.n_leaves} per-leaf); "
                  f"update engine: "
                  f"{'flat/' + st['update_impl'] if st['flat_state'] else 'per-leaf tree'}"
                  + (f" (sharded 1/{st['shards']})"
                     if st.get("sharded_state") else ""))

        # ---- online adaptive control plane (--adapt) ------------------
        controller = None
        telemetry_src = None
        repartitioner = None
        run_base = None          # scale-1 run times after a repartition
        if args.adapt and runtime is not None:
            if args.adapt_repartition:
                model = build_leaf_time_model(
                    params_abs, cfg, hw,
                    args.seq, max(args.batch // dp, 1),
                )
                if args.coverage_rate > 0:
                    model = model.with_coverage_rate(
                        bucket_of, nb, args.coverage_rate
                    )
                repartitioner = Repartitioner(
                    model,
                    RepartitionConfig(
                        base_partition_elems=args.partition_elems
                    ),
                )
            controller = AdaptiveController(
                times, schedule, scfg,
                cfg=AdaptConfig(eta=1e-3, warmup_steps=4, check_every=4,
                                cooldown_steps=2 * schedule.period,
                                wire_precision=args.wire_precision),
                repartitioner=repartitioner,
                bucket_of=bucket_of if repartitioner else None,
                tracer=runtime.tracer,
                precision=plan.precision,
            )
            if args.adapt_drop_step > 0:
                telemetry_src = SyntheticTelemetrySource(
                    times,
                    BandwidthDrop(step=args.adapt_drop_step,
                                  comm_scale=args.adapt_drop_scale),
                )
                print(f"adapt: synthetic bandwidth drop "
                      f"x{args.adapt_drop_scale} at step "
                      f"{args.adapt_drop_step}")

        # ---- fault-tolerant elastic control plane (--elastic) ---------
        elastic = None
        scenario = None
        if args.elastic and runtime is not None:

            def model_for(width: int):
                m = build_leaf_time_model(
                    params_abs, cfg,
                    dataclasses.replace(hw, dp_degree=width),
                    args.seq, max(args.batch // width, 1),
                )
                if args.coverage_rate > 0:
                    m = m.with_coverage_rate(bucket_of, nb,
                                             args.coverage_rate)
                return m

            walk = WalkParams(s0=4.0, eta=0.01, mu=1.0, sigma=40.0,
                              batch=256)
            elastic = ElasticCoordinator(
                runtime,
                ElasticController(model_for, bucket_of, nb, walk=walk,
                                  scheduler_cfg=scfg),
                HealthMonitor(dp),
                params_abs=params_abs,
                batch_spec=batch_spec(cfg, args.batch, args.seq),
                checkpoint_dir=args.ckpt,
            )
            faults = []
            if args.elastic_drop_step > 0:
                shards = tuple(
                    int(s) for s in args.elastic_drop_shards.split(",") if s
                ) or (dp - 1,)
                faults.append(DeviceDrop(args.elastic_drop_step, shards))
                if args.elastic_return_step > 0:
                    faults.append(
                        CapacityReturn(args.elastic_return_step, shards)
                    )
            if args.elastic_straggler_step > 0:
                faults.append(StragglerSlowdown(
                    args.elastic_straggler_step,
                    args.elastic_straggler_shard,
                    args.elastic_straggler_factor,
                ))
            if faults:
                scenario = FaultScenario(n_shards=dp, events=tuple(faults))
                print("elastic: injected faults: " + "; ".join(
                    f"{type(e).__name__}@{e.step}" for e in faults))

        # a preemption signal (SIGTERM/SIGUSR1, what cluster managers
        # send before reclaiming the host) checkpoints and exits cleanly
        preempted = {"sig": None}
        if args.elastic or args.ckpt:
            def _on_preempt(signum, frame):
                preempted["sig"] = signum

            signal.signal(signal.SIGTERM, _on_preempt)
            signal.signal(signal.SIGUSR1, _on_preempt)

        losses, step_s, updated = [], [], []
        # the expert layers' counters: the last step's (gauge) and their
        # sum over the steps (counter)
        expert_counts = Metrics()
        donation_held = True
        t0 = time.time()
        # a resumed run continues the data stream where it left off —
        # otherwise steps N.. would retrain on batches 0.. and diverge
        # from the uninterrupted trajectory
        ds.step = start_step
        last_step = start_step + args.steps - 1
        halted = False
        for step in range(start_step, start_step + args.steps):
            if preempted["sig"] is not None:
                print(f"preemption signal {preempted['sig']}: "
                      f"checkpointing and exiting cleanly")
                if args.ckpt:
                    if elastic is not None:
                        path = elastic.emergency_checkpoint(step, state)
                    else:
                        tree_state = (runtime.state_to_tree(state)
                                      if runtime else state)
                        path = save_ckpt(args.ckpt, step, tree_state)
                        if runtime is not None:
                            save_layout_descriptor(
                                args.ckpt, step, runtime.layout,
                                next_phase=runtime.phase_in_cycle(step),
                                digest=schedule_digest(runtime.schedule),
                            )
                    print(f"checkpoint -> {path}")
                halted = True
                last_step = step - 1
                break
            batch = next(ds)
            prev = state
            t_s = time.perf_counter()
            try:
                if runtime is None:
                    state, m = step_fn(state, batch)
                elif elastic is not None:
                    state, m = elastic.step(step, state, batch)
                    runtime = elastic.runtime   # migrations swap it
                else:
                    state, m = runtime.step(step, state, batch)
            except ElasticHalt as e:
                # the degradation ladder bottomed out; the emergency
                # checkpoint (if --ckpt) is on disk — exit cleanly
                print(f"elastic: {e}")
                halted = True
                last_step = step - 1
                break
            # the step's wall time ends when its outputs are ready, not
            # when the dispatch returns
            jax.block_until_ready((state, m))
            wall = time.perf_counter() - t_s
            step_s.append(wall)
            losses.append(float(m["loss"]))
            updated.append(bool(m["updated"]))
            for key in STAT_KEYS:
                if key in m:
                    expert_counts.set(key, float(m[key]))
                    expert_counts.inc(key, float(m[key]))
            donation_held &= all(
                x.is_deleted() for x in jax.tree_util.tree_leaves(prev)
            )
            del prev
            if tracer is not None:
                # the blocked step, tagged with the cycle phase it ran:
                # what obs attribution measures phases by
                tags = {} if runtime is None else dict(
                    phase=runtime.last_phase,
                    first=runtime.last_dispatch_first)
                tracer.add("step", f"step{step}", t_s, tracer.now(),
                           step=step, **tags)
            if elastic is not None:
                if scenario is not None:
                    obs = scenario.observe(step, wall)
                    if obs.notices:
                        for ev in elastic.notice_preemption(
                                step, obs.notices):
                            print(format_event(ev))
                    if obs.returned:
                        elastic.notice_capacity(step, obs.returned)
                        print(f"elastic: capacity returned: "
                              f"shards {obs.returned}")
                    walls = obs.walls
                else:
                    walls = (wall,) * elastic.n_origin
                for ev in elastic.observe(step, walls):
                    print(format_event(ev))
            if controller is not None:
                if telemetry_src is not None:
                    wall = telemetry_src.wall_time(
                        step, controller.schedule, controller.scheduler_cfg,
                        runtime.last_phase,
                        # the priced view: synthetic walls must reflect
                        # the installed wire precision or every replan
                        # after a downgrade reads as fresh drift
                        solve_times=controller.wire_times(),
                        run_base=run_base,
                    )
                    cold = None     # synthetic walls: no dispatch pollution
                else:
                    # first-dispatch tag: a wall that includes an
                    # executable's one-off lazy work never enters the EMAs
                    cold = runtime.last_dispatch_first
                event = controller.observe(
                    step, runtime.last_phase, wall, loss=losses[-1],
                    cold=cold,
                )
                if event is not None:
                    print(format_event(event))
                    if event.changed:
                        new_layout = None
                        if repartitioner is not None:
                            # ALWAYS stage the layout the controller's
                            # installed view assumes — an earlier
                            # partition swap may have been superseded
                            # before it installed, and a schedule solved
                            # for partition B must never compile against
                            # layout A.  prepare_swap no-ops the repack
                            # when this equals the installed layout.
                            new_layout = build_bucket_layout(
                                params_abs, controller.bucket_of,
                                controller.times.n,
                                shard_count=dp if fsdp else 1,
                            )
                        if event.partition_changed:
                            run_base = repartitioner.base_times_for(
                                event.partition
                            )
                        # a precision change rides on the layout: same
                        # partition, different wire policy (pure-alias
                        # repack, DESIGN.md §13)
                        if new_layout is not None:
                            new_layout = new_layout.with_precision(
                                controller.precision
                            )
                        elif event.precision_changed:
                            new_layout = runtime.layout.with_precision(
                                controller.precision
                            )
                        runtime.prepare_swap(
                            event.schedule, state,
                            batch_spec(cfg, args.batch, args.seq),
                            background=True,
                            layout=new_layout,
                        )
            if args.ckpt and args.ckpt_every > 0 \
                    and (step + 1 - start_step) % args.ckpt_every == 0:
                tree_state = (runtime.state_to_tree(state)
                              if runtime else state)
                save_ckpt(args.ckpt, step + 1, tree_state)
                if runtime is not None:
                    save_layout_descriptor(
                        args.ckpt, step + 1, runtime.layout,
                        next_phase=runtime.phase_in_cycle(step + 1),
                        digest=schedule_digest(runtime.schedule),
                    )
            if (step - start_step) % max(args.steps // 10, 1) == 0 \
                    or step == last_step:
                print(f"step {step:4d} loss={losses[-1]:.4f} "
                      f"updated={updated[-1]} step_s={step_s[-1]:.4f}")
        dt = time.time() - t0
        print(f"{args.steps} steps in {dt:.1f}s "
              f"({args.steps * args.batch * args.seq / dt:.0f} tok/s)")
        if runtime is not None and args.adapt:
            st = runtime.stats()
            print(f"adapt: {st['replans']} replans, {st['hot_swaps']} "
                  f"hot-swaps ({st['layout_swaps']} layout-changing), "
                  f"{st['cached_phases']} cached phases")
            for sw in st["swap_log"]:
                print("  " + format_event(sw))
            for ev in (controller.events if controller else []):
                print("  " + format_event(ev))
        if elastic is not None:
            st = elastic.stats()
            print(f"elastic: members={st['members']} "
                  f"spares={st['spares']} "
                  f"{len(st['migrations'])} migrations, "
                  f"{len(st['fault_events'])} fault events")
            for mig in st["migrations"]:
                print("  " + format_event(mig))

    if args.ckpt and not halted:
        # checkpoint boundary: the flat-resident runtime state unflattens
        # to the tree form HERE and nowhere in the steady-state loop
        tree_state = runtime.state_to_tree(state) if runtime else state
        path = save_ckpt(args.ckpt, last_step + 1, tree_state)
        if runtime is not None:
            # the layout sidecar lets a later run restore this state
            # under a DIFFERENT partition / shard count (DESIGN.md §9)
            save_layout_descriptor(
                args.ckpt, last_step + 1, runtime.layout,
                next_phase=runtime.phase_in_cycle(last_step + 1),
                digest=schedule_digest(runtime.schedule),
            )
        print(f"checkpoint -> {path}")

    if tracer is not None:
        tracer.export_chrome_trace(args.trace)
        ts = tracer.stats()
        dropped = (f", {ts['dropped']} dropped (ring full)"
                   if ts["dropped"] else "")
        print(f"trace -> {args.trace} ({ts['retained']} spans{dropped})")
    return {
        "cfg": cfg,
        "n_params": cfg.total_params(),
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "losses": losses,
        "step_s": step_s,
        "updated": updated,
        "compile_s": compile_s,
        "donation_held": donation_held,
        "runtime": runtime,
        "loss_chunk": loss_chunk,
        "expert_counts": expert_counts.summary(),
    }


if __name__ == "__main__":
    main()
