import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh x mode)
combination against the production mesh without allocating a byte of
model memory (ShapeDtypeStruct inputs with NamedShardings).

The two XLA lines above MUST run before any other import — jax locks the
device count on first init, and the production meshes need 512 placeholder
host devices.

Cost methodology (see EXPERIMENTS.md §Dry-run):

* The FULL config is lowered with rolled scans — that compile is the
  memory evidence (buffer reuse across scan iterations matches a real
  run) and the gradient-sync collective evidence (grad all-reduces act on
  stacked leaves OUTSIDE the layer scan, so the rolled HLO counts them
  exactly).
* XLA cost_analysis counts a while-loop body once regardless of trip
  count, so FLOPs / bytes / total collective bytes come from TWO small
  fully-unrolled variants (1 and 2 scan periods) extrapolated linearly to
  the full depth: ``est(N) = c1 + (N - 1) * (c2 - c1)``.  The fixed parts
  (embedding, LM head + chunked CE, prefix/tail layers, encoder) cancel
  exactly in the delta.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all              # 40-combo baseline
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi # 512-chip pass
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k --mode deft
"""
import argparse
import dataclasses
import functools
import json
import pathlib
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, SHAPES, config_for_shape, get_shape
from repro.core.deft import Planner, PlanRequest
from repro.core.scheduler import SchedulerConfig
from repro.core.profiler import HardwareModel
from repro.launch.analysis import (
    analyse_compiled,
    collective_bytes,
    model_flops_for,
)
from repro.launch.inputs import serve_input_specs, train_input_specs
from repro.launch.mesh import make_production_mesh
from repro.models.model import stack_layout
from repro.optim.optimizers import adamw
from repro.serve.steps import decode_serve_step, prefill_serve_step
from repro.sharding.specs import needs_fsdp
from repro.train.bucketing import assign_buckets, leaf_bucket_times
from repro.train.steps import ddp_train_step, deft_phase_step, deft_rs_phase_step
from repro.util.flags import sharded_decode, unroll_scans

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"
# Sequence-chunked LM-head/CE (see models.model.chunked_ce): caps the live
# logits buffer at [B, chunk, V] — full [B,S,V] f32 logits do not fit HBM
# at the production train shape for the 256k-vocab archs.
LOSS_CHUNK = 512


def _mesh_desc(multi_pod: bool) -> str:
    return "2x16x16" if multi_pod else "16x16"


def _pick_phase(schedule):
    """Most representative phase: prefer one that syncs + updates."""
    best = schedule.phases[0]
    best_score = -1
    for ph in schedule.phases:
        score = sum(r == "sync" for r in ph.route_new) + sum(ph.sync_cur)
        score += 100 * ph.do_update
        if score > best_score:
            best, best_score = ph, score
    return best


def _variant_cfg(cfg, k: int):
    """Same architecture with k scanned periods (prefix/tail preserved)."""
    lay = stack_layout(cfg)
    n = len(lay.prefix_specs) + k * lay.period + len(lay.tail_specs)
    return dataclasses.replace(cfg, n_layers=n)


def _metrics(compiled) -> dict:
    ca = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        **{f"coll_{k}": float(v) for k, v in coll.items()},
    }


def lower_one(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    mode: Optional[str] = None,
    partition_elems: int = 50_000_000,
    verbose: bool = True,
    extrapolate: bool = True,
    opts: tuple = (),
):
    """Lower + compile one combination; returns (Roofline, seconds) or a
    skip-marker dict."""
    shape = get_shape(shape_name)
    cfg = config_for_shape(arch, shape_name)
    if shape_name == "long_500k" and not cfg.supports_long_context():
        return {"arch": arch, "shape": shape_name,
                "skip": "full-attention arch at 500k context (see DESIGN.md)"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    mode = mode or ("ddp" if shape.kind == "train" else shape.kind)
    opt = adamw(1e-3)
    fsdp = needs_fsdp(cfg.name)
    if shape.kind == "train" and mode == "deft" and fsdp and not multi_pod:
        return {"arch": arch, "shape": shape_name,
                "skip": "DeFT-RS needs the multi-pod mesh for FSDP archs"}
    if shape.kind == "train" and mode == "deft" and fsdp and multi_pod:
        return {"arch": arch, "shape": shape_name,
                "skip": "DeFT-RS at 512 devices aborts inside XLA's SPMD "
                        "partitioner (CHECK in ExpandDeviceGroupsWithIota, "
                        "partial-manual shard_map over 'pod' + FSDP 'data'; "
                        "repros on both FSDP archs). The identical step "
                        "compiles and trains on small meshes — see "
                        "tests/test_multidevice.py. Upstream XLA issue; "
                        "documented in EXPERIMENTS.md §Dry-run."}
    layout = "dp" if "dp-only" in opts else "tp"
    micro = 0
    for o in opts:
        if o.startswith("microbatch"):
            micro = int(o.split("=")[1])
    t0 = time.time()

    def build(cfg_x):
        """Lower the mode's step for (a possibly depth-reduced) cfg_x."""
        if shape.kind == "train":
            if mode == "deft":
                dp = (2 if fsdp else 16 * (2 if multi_pod else 1))
                state, batch = train_input_specs(
                    cfg_x, shape, mesh, multi_pod=multi_pod, opt_spec=opt,
                    deft=True, accum_devices=dp,
                    accum_dtype=jnp.bfloat16 if fsdp else jnp.float32,
                )
                bucket_of, nb = assign_buckets(state["params"], cfg_x,
                                               partition_elems)
                hw = HardwareModel(dp_degree=dp)
                times = leaf_bucket_times(
                    state["params"], cfg_x, bucket_of, nb, hw, shape.seq_len,
                    max(shape.global_batch // dp, 1),
                )
                # dryrun only needs a representative phase: solve without
                # the Preserver feedback loop
                schedule = Planner().plan(
                    PlanRequest(times=times, preserve=False)).schedule
                phase = _pick_phase(schedule)
                impl = deft_rs_phase_step if fsdp else deft_phase_step
                kw = dict(cfg=cfg_x, opt_spec=opt, phase=phase,
                          bucket_of_leaf=bucket_of, mesh=mesh,
                          loss_chunk=LOSS_CHUNK)
                if not fsdp:
                    kw["multi_pod"] = multi_pod
                fn = jax.jit(functools.partial(impl, **kw), donate_argnums=(0,))
                return fn.lower(state, batch)
            fn = jax.jit(functools.partial(
                ddp_train_step, cfg=cfg_x, opt_spec=opt,
                multi_pod=multi_pod, fsdp=fsdp, loss_chunk=LOSS_CHUNK,
                layout=layout, microbatch=micro,
            ), donate_argnums=(0,))
            state, batch = train_input_specs(
                cfg_x, shape, mesh, multi_pod=multi_pod, opt_spec=opt,
                layout=layout,
            )
            return fn.lower(state, batch)
        if shape.kind == "prefill":
            specs = serve_input_specs(cfg_x, shape, mesh, multi_pod=multi_pod)
            fn = jax.jit(functools.partial(
                prefill_serve_step, cfg=cfg_x, multi_pod=multi_pod,
            ), donate_argnums=(2,))
            kw = {"memory": specs["memory"]} if "memory" in specs else {}
            return fn.lower(specs["params"], specs["tokens"], specs["cache"], **kw)
        specs = serve_input_specs(cfg_x, shape, mesh, multi_pod=multi_pod)
        fn = jax.jit(functools.partial(
            decode_serve_step, cfg=cfg_x, multi_pod=multi_pod,
        ), donate_argnums=(2,))
        return fn.lower(specs["params"], specs["token"], specs["cache"],
                        specs["pos"])

    # The mesh context must be active while TRACING so the model's
    # logical-axis with_sharding_constraints resolve (otherwise the SPMD
    # partitioner free-wheels on every activation).
    # ---- full config, rolled scans: memory + grad-sync evidence ----
    with jax.set_mesh(mesh), sharded_decode("sharded-decode" in opts):
        compiled = build(cfg).compile()
    t_full = time.time() - t0
    rolled = _metrics(compiled)

    # ---- two small unrolled variants: exact per-period cost delta ----
    lay = stack_layout(cfg)
    est = dict(rolled)
    t_var = 0.0
    if extrapolate and lay.n_periods >= 2:
        tv = time.time()
        with jax.set_mesh(mesh), unroll_scans(), \
                sharded_decode("sharded-decode" in opts):
            m1 = _metrics(build(_variant_cfg(cfg, 1)).compile())
            m2 = _metrics(build(_variant_cfg(cfg, 2)).compile())
        est = {
            k: m1[k] + (lay.n_periods - 1) * (m2[k] - m1[k]) for k in m1
        }
        t_var = time.time() - tv

    roof = analyse_compiled(
        compiled,
        arch=arch,
        shape=shape_name,
        mesh_desc=_mesh_desc(multi_pod),
        mode=mode,
        n_chips=n_chips,
        model_flops=model_flops_for(cfg, shape),
    )
    roof.extra = {
        "rolled": rolled,
        "estimated": est,
        "n_periods": lay.n_periods,
        "wall_full_s": t_full,
        "wall_variants_s": t_var,
    }
    roof.hlo_flops = est["flops"]
    roof.hlo_bytes = est["bytes"]
    roof.coll_bytes = est["coll_total"]
    roof.coll_breakdown = {
        k.removeprefix("coll_"): int(v) for k, v in est.items()
        if k.startswith("coll_")
    }

    if verbose:
        ma = compiled.memory_analysis()
        print(f"--- {arch} x {shape_name} x {_mesh_desc(multi_pod)} [{mode}] "
              f"(full {t_full:.0f}s, variants {t_var:.0f}s)")
        print(f"    memory_analysis: args={ma.argument_size_in_bytes/2**30:.2f}GiB "
              f"temp={ma.temp_size_in_bytes/2**30:.2f}GiB "
              f"out={ma.output_size_in_bytes/2**30:.2f}GiB")
        print(f"    est cost: flops={roof.hlo_flops:.3e} bytes={roof.hlo_bytes:.3e}")
        print(f"    est collectives: { {k: f'{v/2**30:.2f}GiB' for k, v in roof.coll_breakdown.items()} }")
        print(f"    rolled grad-sync view: "
              f"{ {k.removeprefix('coll_'): f'{v/2**30:.2f}GiB' for k, v in rolled.items() if k.startswith('coll_')} }")
        print(f"    roofline: compute={roof.t_compute*1e3:.2f}ms "
              f"memory={roof.t_memory*1e3:.2f}ms "
              f"collective={roof.t_collective*1e3:.2f}ms "
              f"-> {roof.dominant}-bound, useful={roof.useful_flops_ratio:.2f}")
    return roof, time.time() - t0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default=None)
    ap.add_argument("--shape", choices=[s.name for s in SHAPES], default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--mode", choices=["ddp", "deft"], default=None,
                    help="train_4k only; serve shapes use their own step")
    ap.add_argument("--all", action="store_true", help="sweep all archs x shapes")
    ap.add_argument("--no-extrapolate", action="store_true",
                    help="skip the unrolled variant compiles")
    ap.add_argument("--opt", default="",
                    help="comma list of beyond-paper optimizations: "
                         "sharded-decode, dp-only, microbatch=N")
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()

    archs = ARCH_NAMES if (args.all or args.arch is None) else (args.arch,)
    shapes = [s.name for s in SHAPES] if (args.all or args.shape is None) else (args.shape,)
    meshes = {"single": (False,), "multi": (True,), "both": (False, True)}[args.mesh]

    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    n_ok = n_skip = n_fail = 0
    for multi_pod in meshes:
        for arch in archs:
            for shape_name in shapes:
                opts = tuple(o for o in args.opt.split(",") if o)
                tag = f"{arch}_{shape_name}_{_mesh_desc(multi_pod)}" + (
                    f"_{args.mode}" if args.mode else ""
                ) + ("_" + "-".join(opts) if opts else "")
                try:
                    res = lower_one(
                        arch, shape_name, multi_pod=multi_pod, mode=args.mode,
                        extrapolate=not args.no_extrapolate, opts=opts,
                    )
                    if isinstance(res, dict):  # skip marker
                        print(f"--- {tag}: SKIP ({res['skip']})")
                        (out_dir / f"{tag}.json").write_text(json.dumps(res))
                        n_skip += 1
                        continue
                    roof, secs = res
                    payload = roof.to_json()
                    payload["wall_seconds"] = secs
                    (out_dir / f"{tag}.json").write_text(json.dumps(payload, indent=1))
                    n_ok += 1
                except Exception as e:
                    n_fail += 1
                    print(f"--- {tag}: FAIL {type(e).__name__}: {e}")
                    traceback.print_exc()
    print(f"\ndry-run summary: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
