#!/usr/bin/env python3
"""Where a cell's step goes, by the engine's named work, on the chip.

    python bench/engine_report.py --workload <cell> --seed <n> --seconds <s>

One process on the attached TPU chips builds the cell's runtime as
``bench/run.py`` does, warms it up, then

1. runs an untraced window of ``--seconds`` and reports the step walls
   and their two parts, the dispatch (``DeftRuntime.step`` until it
   returns) and the block and loss read after it (median and p90);
2. traces the cell's ``trace_steps`` and reports, from the device trace
   and the profiler's host plane on one clock:

   * ``engine_device_ms`` and ``engine_host_ms`` (the median
     ``deft.phase`` span) beside ``engine_dispatch_ms``;
   * the exposed sync per link (``deft_sync.*.primary.*`` and
     ``.secondary.``), of the metrics psum and of comm ops no scope
     names, against ``collective_exposed_ms``;
   * one row per bucket (or per set of buckets XLA combined into one
     collective): link, generation, the plan's comm time for it and the
     measured in-flight and exposed ms;
   * the idle gaps named by the host spans, the engine's ``deft.*``
     among them, and the top device ops with their scope;
   * the traced steps' walls against the untraced ones: what tracing
     costs.

It runs no reference and decides no ``correct``: it reads what
``bench/run.py``'s readers cannot, the phases' compiled collectives
and the engine's host spans.  Tables go to stderr, one JSON line to
stdout.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

T_START = time.perf_counter()

import pathlib  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import engine_scopes, run, spec, trace_reduce  # noqa: E402
from bench.metrics import collective_exposed_ms  # noqa: E402

HOST_SPANS = ("bench.", "deft.")


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def quartiles(xs):
    """Median and p90 of ``xs`` (ms), as bench/run.py takes its p90."""
    return {"median": statistics.median(xs),
            "p90": statistics.quantiles(xs, n=10, method="inclusive")[8]}


def planned_comm(cell, devices):
    """Per bucket, the comm seconds the plan priced it at: the plan of
    ``run.make_plan``, built again to keep its times."""
    import jax

    from repro.core.profiler import HardwareModel
    from repro.launch.train import build_schedule
    from repro.models.model import init_params

    c, t = cell.cfg, cell.traffic
    g, p = c["guarantees"], t["plan"]
    acfg = run.program_config(c)
    params_abs = jax.eval_shape(lambda k: init_params(k, acfg),
                                jax.random.PRNGKey(0))
    _, _, _, plan = build_schedule(
        params_abs, acfg,
        hw=HardwareModel.for_device(devices[0], dp_degree=cell.chips),
        dp=cell.chips, seq_len=t["seq"], per_device_batch=t["batch_per_chip"],
        partition_elems=p["partition_elems"],
        coverage_rate=p["coverage_rate"], heterogeneous=p["heterogeneous"],
        mu=p["mu"], eps=g["preserver_eps"], max_retries=p["max_retries"],
        wire_precision=g["wire_precision"], master_dtype=g["master_dtype"])
    return plan.wire_times.comm, plan.schedule


def traced_ctx(rt, cell, schedule, tr, steps):
    """The traced window as bench/run.py hands it to its readers, with
    what the readers used here need."""
    lo, hi = trace_reduce.window(tr)
    texts = [rt.phase_executable(off).as_text()
             for off in range(schedule.period)]
    return {
        "trace": tr, "lo": lo, "hi": hi,
        "devices": sorted(tr.ops)[:cell.chips], "steps": steps,
        "scopes": trace_reduce.scopes_from_hlo(texts),
        "hlo": trace_reduce.hlo_ops(texts),
    }


def report(cell, seed: int, seconds: float, devices):
    import jax

    rt, state, pool, mesh, _, schedule = run.build(cell, seed, devices)
    comm_s, plan_schedule = planned_comm(cell, devices)
    if plan_schedule.phases != schedule.phases:
        raise run.BenchError("the plan built again differs from the run's")
    warm = max(cell.traffic["check_steps"], 2 * schedule.period)
    out = {"workload": cell.name, "seed": seed}
    with jax.set_mesh(mesh):
        state, i, _, _, _ = run.drive(rt, state, pool, 0, n=warm)
        out["setup_s"] = time.perf_counter() - T_START
        t0 = time.perf_counter()
        state, i, walls, disp, _ = run.drive(rt, state, pool, i,
                                             until=t0 + seconds)
        ms = lambda xs: [1e3 * x for x in xs]
        out["untraced"] = {
            "steps": len(walls),
            "wall_ms": quartiles(ms(walls)),
            "dispatch_ms": quartiles(ms(disp)),
            "block_loss_ms": quartiles(ms(w - d for w, d in zip(walls, disp))),
        }
        tdir = tempfile.mkdtemp(prefix="engine-report-")
        jax.profiler.start_trace(tdir)
        state, i1, twalls, tdisp, _ = run.drive(
            rt, state, pool, i, n=cell.traffic["trace_steps"], traced=True)
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    tr = trace_reduce.load(path, host_prefix=HOST_SPANS)
    shutil.rmtree(tdir, ignore_errors=True)
    ctx = traced_ctx(rt, cell, schedule, tr, i1 - i)
    scopes = {}
    for phase in rt.phase_collective_scopes():
        scopes.update(phase)
    lo, hi = ctx["lo"], ctx["hi"]
    host = {name: statistics.median(engine_scopes.host_ms(tr, name, lo, hi))
            for name in ("deft.phase", "deft.place", "deft.launch")
            if engine_scopes.host_ms(tr, name, lo, hi)}
    traced_wall = statistics.median(ms(twalls))
    out["traced"] = {
        "steps": ctx["steps"],
        "wall_ms": traced_wall,
        "tracing_cost_ms": traced_wall - out["untraced"]["wall_ms"]["median"],
        "engine_device_ms": engine_scopes.engine_ms(ctx),
        "engine_host_ms": host.get("deft.phase"),
        "host_span_ms": host,
        "engine_dispatch_ms": 1e3 * statistics.median(tdisp),
    }
    exposed = collective_exposed_ms.read(ctx)
    if exposed is not None:
        parts = engine_scopes.exposed_by_group(ctx, scopes)
        named = sum(parts.get(g, 0.0)
                    for g in ("primary", "secondary", "metrics"))
        out["sync"] = {"collective_exposed_ms": exposed,
                       "exposed_ms": parts, "residual_ms": exposed - named}
        log(f"exposed sync ms/step: {parts!r}, collective_exposed_ms "
            f"{exposed!r}, residual {exposed - named!r}")
        rows = []
        log("bucket(s) | link | gen | planned comm ms | in flight ms | "
            "exposed ms")
        for key, flight, exp in engine_scopes.by_scopes(ctx, scopes):
            syncs = sorted(k for k in key if k.startswith("deft_sync"))
            buckets = [int(k.split(".")[1][1:]) for k in syncs]
            gens = sorted({k.split(".")[3] for k in syncs})
            row = {"scopes": sorted(key), "group": engine_scopes.group(key),
                   "generation": "+".join(gens),
                   "planned_comm_ms": 1e3 * sum(comm_s[b] for b in buckets),
                   "in_flight_ms": flight, "exposed_ms": exp}
            rows.append(row)
            log(f"{'+'.join(f'b{b}' for b in buckets) or sorted(key)} | "
                f"{row['group']} | {row['generation']} | "
                f"{row['planned_comm_ms']!r} | {flight!r} | {exp!r}")
        out["buckets"] = rows
    out["idle_gaps"] = [list(x) for x in
                        trace_reduce.idle_gaps_by_host(tr, lo, hi)[:10]]
    st = {}
    for d in ctx["devices"]:
        for name, ns in trace_reduce.self_times(tr, d, lo, hi).items():
            st[name] = st.get(name, 0.0) + ns * 1e-6 / len(ctx["devices"])
    out["device_ops_ms_per_step"] = [
        [name, v / ctx["steps"],
         engine_scopes.innermost(ctx["scopes"].get(name, ""))]
        for name, v in sorted(st.items(), key=lambda x: -x[1])[:16]]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    man = spec.manifest()
    wl = spec.workload(man, args.workload)
    cell = run.Cell(wl["name"], spec.config(wl["config"]),
                    spec.traffic(wl["traffic"]), spec.limits(wl["name"]),
                    wl["chips"])
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"FAIL: the cell asks for {cell.chips} TPU chips, JAX sees "
            f"{len(devices)} {devices[0].platform} devices")
        return 3
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        out = report(cell, args.seed, args.seconds, devices)
    except run.BenchError as e:
        log(f"FAIL: {e}")
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
