#!/usr/bin/env python3
"""Run one benchmark cell once on the attached TPU chips.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in BENCHMARK.json) names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); its limits are in
``bench/limits/<cell>.json``.  One process:

1. builds the DeFT runtime as ``repro.launch.train`` does: the plan
   (``build_schedule``, priced for the attached chip at the mesh's
   data-parallel degree), the bucket layout, ``DeftRuntime`` with its
   ``RuntimeConfig``, the state, the AOT compile; the weights and the
   pool of batches are drawn on the device from ``--seed``;
2. warms up: it drives the first steps through the window's own loop
   and reads what the comparison with the reference needs;
3. measures for ``--seconds`` (``--trace 0``: the end-to-end metrics) or
   traces a few steps (``--trace 1``: the per-layer metrics, each read
   by ``bench/metrics/<metric>.py``);
4. frees the program's state and runs the reference over the first
   steps, and prints one JSON line last.

Each step blocks on its outputs and reads its loss, as the trainer
does.  Without a TPU, with fewer chips than the cell asks for, or with a
chip kind missing from ``bench/peaks.json``, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import spec  # noqa: E402

GIB = 2.0 ** 30


class BenchError(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ---- compile counting ----------------------------------------------------
class CompileCounter:
    """Counts XLA compilations and persistent-cache hits from JAX's
    monitoring events."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.backend = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **kw):
        if event == self.BACKEND:
            self.backend += 1

    def _ev(self, event, **kw):
        if event == self.HIT:
            self.hits += 1

    def snapshot(self) -> Dict[str, int]:
        return {"compiles": self.backend, "cache_hits": self.hits}


# ---- the program, built as launch/train.py builds it -----------------------
def program_config(c: Dict[str, Any]):
    """The program's ArchConfig at the file's sizes, by the
    configuration's architecture module (``bench/archs``)."""
    return spec.arch(c).program_config(c)


def plan_updates(schedule, n: int):
    """Per step: None, or (the steps whose gradients its update applies,
    the update's k), by following the schedule's generation routing."""
    cur: List[int] = []
    fut: List[int] = []
    out = []
    for i in range(n):
        ph = schedule.phases[i % schedule.period]
        if ph.rotate:
            gen, new_fut = fut + [i], []
        else:
            gen, new_fut = None, fut + [i]
        upd = None
        if ph.do_update:
            src = cur if ph.update_source == "cur" else gen
            upd = (tuple(src), int(ph.update_k))
            new_cur = gen if (ph.update_source == "cur" and gen is not None) \
                else []
        elif ph.rotate:
            new_cur = gen
        else:
            new_cur = cur
        cur, fut = new_cur, new_fut
        out.append(upd)
    return out


@dataclasses.dataclass
class Cell:
    name: str
    cfg: Dict[str, Any]          # configuration file
    traffic: Dict[str, Any]      # traffic file
    limits: Dict[str, Any]
    chips: int


def make_plan(cell: Cell, devices):
    """The program's config, parameter shapes, plan and layout for the
    cell, priced for the attached chip at the cell's data-parallel
    degree."""
    import jax

    from repro.core.profiler import HardwareModel
    from repro.launch.train import build_schedule
    from repro.models.model import init_params as program_init_params
    from repro.train.bucketing import build_bucket_layout

    c, t = cell.cfg, cell.traffic
    g = c["guarantees"]
    acfg = program_config(c)
    dp = cell.chips
    hw = HardwareModel.for_device(devices[0], dp_degree=dp)
    params_abs = jax.eval_shape(
        lambda k: program_init_params(k, acfg), jax.random.PRNGKey(0))
    p = t["plan"]
    bucket_of, nb, times, plan = build_schedule(
        params_abs, acfg, hw=hw, dp=dp, seq_len=t["seq"],
        per_device_batch=t["batch_per_chip"],
        partition_elems=p["partition_elems"],
        coverage_rate=p["coverage_rate"], heterogeneous=p["heterogeneous"],
        mu=p["mu"], eps=g["preserver_eps"], max_retries=p["max_retries"],
        wire_precision=g["wire_precision"], master_dtype=g["master_dtype"])
    layout = build_bucket_layout(params_abs, bucket_of, nb, shard_count=1)
    if plan.precision is not None:
        layout = layout.with_precision(plan.precision)
    s = plan.schedule
    log(f"plan: {nb} buckets, CR {times.coverage_rate!r}, period "
        f"{s.period}, updates/period {s.updates_per_period}, k-seq "
        f"{s.batch_size_sequence}, secondary-link buckets per phase "
        f"{[sum(ph.secondary) for ph in s.phases]} of {nb}")
    return acfg, params_abs, s, layout


def pool_tokens(cell: Cell, seed: int):
    """[pool x rows, seq] tokens of the cell's batch pool; batch i is
    rows i*rows .. (i+1)*rows - 1, rows = chips x batch per chip."""
    import jax

    from bench import weights

    t = cell.traffic
    rows = cell.chips * t["batch_per_chip"]
    st = t["stream"]
    vocab = program_config(cell.cfg).vocab_size
    return jax.jit(lambda k: weights.token_pool(
        k, t["pool"] * rows, t["seq"], vocab,
        st["zipf_exponent"], st["follow_p"]))(weights.base_key(seed))


def optimizer(cell: Cell):
    from repro.optim.optimizers import OptimizerSpec

    o = cell.traffic["optimizer"]
    return OptimizerSpec(o["name"], lr=o["lr"], beta1=o["beta1"],
                         beta2=o["beta2"], eps=o["eps"],
                         weight_decay=o["weight_decay"],
                         grad_clip=o["grad_clip"])


def build(cell: Cell, seed: int, devices):
    """Runtime, seeded state and batch pool, built as launch/train.py
    builds them."""
    import jax
    import jax.numpy as jnp

    from jax.sharding import AxisType

    from repro.data.pipeline import batch_spec
    from repro.launch.train import loss_chunk_for
    from repro.train.runtime import DeftRuntime, RuntimeConfig

    from bench import weights

    c, t = cell.cfg, cell.traffic
    g = c["guarantees"]
    acfg, _, schedule, layout = make_plan(cell, devices)
    mesh = jax.make_mesh((cell.chips, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=devices[:cell.chips])
    per_dev, seq = t["batch_per_chip"], t["seq"]
    rows = cell.chips * per_dev
    compute = {"bf16": jnp.bfloat16, "f32": None}[g["compute_dtype"]]
    rcfg = RuntimeConfig(
        compute_dtype=compute,
        loss_chunk=loss_chunk_for(acfg.vocab_size, per_dev, seq),
        master_dtype=None if g["master_dtype"] == "f32" else g["master_dtype"])
    rt = DeftRuntime(acfg, optimizer(cell), schedule, layout, mesh,
                     config=rcfg)
    log(f"collectives per phase {rt.collectives_per_phase()}")
    with jax.set_mesh(mesh):
        # the program's own initial state gives the placement its phases
        # expect; the benchmark's weights then enter through the
        # checkpoint-restore path (tree_to_state), zeros elsewhere
        state = rt.init_state(jax.random.PRNGKey(0),
                              dtype=compute or jnp.float32)
        shardings = jax.tree.map(lambda x: x.sharding, state)
        del state
        tree_struct = rt.checkpoint_struct()
        struct = tree_struct["params"]

        def seeded(key):
            tree = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                tree_struct)
            tree["params"] = weights.init_params(struct, c, key)
            return rt.tree_to_state(tree)

        state = jax.jit(seeded, out_shardings=shardings)(
            weights.base_key(seed))
        rt.compile(state, batch_spec(acfg, rows, seq))
        log(f"kernels per phase {rt.phase_kernels()}")
        bsh = rt.phase_executable(0).input_shardings[0][1]
        n_pool = t["pool"]
        split = jax.jit(
            lambda x: tuple({"tokens": x[i * rows:(i + 1) * rows],
                             "labels": x[i * rows:(i + 1) * rows]}
                            for i in range(n_pool)),
            out_shardings=tuple(bsh for _ in range(n_pool)))
        pool = split(pool_tokens(cell, seed))
    return rt, state, pool, mesh, struct, schedule


def check_rows(tokens, cell: Cell, n: int, device):
    """The sequences of the first ``n`` steps' batches, one list a step,
    on ``device``."""
    import jax
    import numpy as np

    rows = cell.chips * cell.traffic["batch_per_chip"]
    host = np.asarray(tokens[: n * rows])
    return [[jax.device_put(host[k * rows + r], device) for r in range(rows)]
            for k in range(n)]


def drive(rt, state, pool, start: int, *, n: Optional[int] = None,
          until: Optional[float] = None, traced: bool = False,
          after: Optional[Callable] = None):
    """The step loop of the window: pick a batch, dispatch the step,
    block on its outputs, read its loss.  Returns the state, the next
    step, per-step walls and dispatch times (s) and losses."""
    import jax

    ann = jax.profiler.TraceAnnotation if traced \
        else (lambda name: contextlib.nullcontext())
    walls, disp, losses = [], [], []
    i = start
    perf = time.perf_counter
    while (n is None or i - start < n) and (until is None or perf() < until):
        with ann("bench.step"):
            t0 = perf()
            with ann("bench.pick"):
                batch = pool[i % len(pool)]
            with ann("bench.dispatch"):
                state, m = rt.step(i, state, batch)
            t1 = perf()
            with ann("bench.block"):
                jax.block_until_ready((state, m))
            with ann("bench.loss"):
                loss = float(m["loss"])
            t2 = perf()
        walls.append(t2 - t0)
        disp.append(t1 - t0)
        losses.append(loss)
        if after is not None:
            after(i, state)
        i += 1
    return state, i, walls, disp, losses


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices, man: Dict[str, Any],
             step_wrapper: Optional[Callable] = None,
             peak: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Everything after the look for a chip: build, warm up, measure or
    trace, compare with the reference.  ``step_wrapper`` (tests only)
    wraps the runtime's step to plant a fault in the timed path;
    ``peak`` is the chip's row of bench/peaks.json (default: the row of
    ``devices[0]``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.train.bucketing import unflatten_buckets

    from bench import compare, reference, trace_reduce, weights

    counter = CompileCounter()
    rt, state, pool, mesh, struct, schedule = build(cell, seed, devices)
    if step_wrapper is not None:
        rt.step = step_wrapper(rt.step)
    o = cell.traffic["optimizer"]
    n_check = cell.traffic["check_steps"]
    updates = plan_updates(schedule, n_check)
    first = next((i for i, u in enumerate(updates) if u and u[0]), None)
    if first is None:
        raise BenchError(f"the plan applies no gradient in the first "
                         f"{n_check} steps: {updates}")
    if any(u and u[0] for u in updates[:first]):
        raise BenchError("the first gradient is read from Adam's m, which "
                         "needs every earlier update to be empty")
    readings: Dict[str, Any] = {}
    treedef = jax.tree_util.tree_structure(struct)

    def host_leaves(bufs):
        # per-bucket flat buffers copied to the host, split into leaves
        # there, so that the device holds nothing more than the step does
        lay = rt.layout
        flat = [np.asarray(b) for b in bufs]
        out = [None] * lay.n_leaves
        for b in range(lay.n_buckets):
            for i, off in zip(lay.leaves[b], lay.offsets[b]):
                n = int(np.prod(lay.shapes[i], dtype=np.int64))
                out[i] = flat[b][off:off + n].reshape(lay.shapes[i])
        return out

    key = weights.base_key(seed)
    # the change of each leaf since the seeded weights, read from the flat
    # engine's per-bucket weight buffers
    d_norms = jax.jit(lambda s, k: reference.leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b,
        jax.tree_util.tree_unflatten(
            treedef, unflatten_buckets(rt.layout, s["pbuf"])),
        weights.init_params(struct, cell.cfg, k))))

    def read_state(i, st):
        if i == first:
            readings["first_grad"] = [
                x / (1 - o["beta1"]) for x in host_leaves(st["opt"]["m"])]
        if i == n_check - 1:
            readings["delta"] = np.asarray(d_norms(st, key))

    period = schedule.period
    warm = max(n_check, 2 * period)
    with jax.set_mesh(mesh):
        state, i, _, _, warm_losses = drive(rt, state, pool, 0, n=warm,
                                            after=read_state)
    readings["losses"] = warm_losses[:n_check]
    at_window = counter.snapshot()
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s!r} s: {at_window['compiles']} compilations, "
        f"{at_window['cache_hits']} persistent-cache hits")

    c, t = cell.cfg, cell.traffic
    tokens_per_step = cell.chips * t["batch_per_chip"] * t["seq"]
    out: Dict[str, Any] = {}
    dev_out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
               "count": len(devices)}
    metrics: Dict[str, Dict[str, Any]] = {}
    with jax.set_mesh(mesh):
        if not trace:
            t0 = time.perf_counter()
            state, i1, walls, disp, losses = drive(
                rt, state, pool, i, until=t0 + seconds)
            window_s = time.perf_counter() - t0
            steps = i1 - i
        else:
            tdir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(tdir)
            state, i1, walls, disp, losses = drive(
                rt, state, pool, i, n=t["trace_steps"], traced=True)
            jax.profiler.stop_trace()
            steps = i1 - i
    in_window = counter.snapshot()
    n_compiles = in_window["compiles"] - at_window["compiles"]
    log(f"window: {steps} steps, {n_compiles} compilations inside it")
    used = devices[:cell.chips]
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in used)
    dev_out["memory_peak_bytes"] = int(mem_peak)
    failed = sum(1 for x in losses if not math.isfinite(x))

    if not trace:
        values = {
            "tokens_per_s": steps * tokens_per_step / window_s,
            "step_ms_p90": statistics.quantiles(
                walls, n=10, method="inclusive")[8] * 1e3,
            "peak_hbm_gib": mem_peak / GIB,
            "setup_s": setup_s,
        }
        for m in spec.end_to_end_for(man, cell.name):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
        log(f"step walls: median {statistics.median(walls)!r} s over {steps} "
            f"steps, window {window_s!r} s")
    else:
        path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        tr = trace_reduce.load(path)
        lo, hi = trace_reduce.window(tr)
        devs = sorted(tr.ops)[:cell.chips]
        busy = [trace_reduce.total(trace_reduce.busy(tr, d, lo, hi))
                for d in devs]
        dev_out["busy_s"] = sum(busy) / len(busy) * 1e-9 if busy else 0.0
        dev_out["window_s"] = (hi - lo) * 1e-9
        texts = []
        for off in range(schedule.period):
            texts.append(rt.phase_executable(off).as_text())
        ctx = {
            "trace": tr, "lo": lo, "hi": hi, "devices": devs,
            "steps": steps, "window_s": (hi - lo) * 1e-9,
            "chips": cell.chips, "config": c, "traffic": t,
            "peak": peak or spec.peaks()[devices[0].device_kind],
            "scopes": trace_reduce.scopes_from_hlo(texts),
            "hlo": trace_reduce.hlo_ops(texts),
            "tokens_per_step": tokens_per_step,
            "update_steps": sum(
                1 for k in range(i, i1)
                if schedule.phases[k % period].do_update),
            "zeroing_update_steps": sum(
                1 for k in range(i, i1)
                if schedule.phases[k % period].do_update
                and (schedule.phases[k % period].update_source == "new"
                     or not schedule.phases[k % period].rotate)),
            "dispatch_s": disp,
        }
        for m in spec.per_layer_for(man, cell.name):
            mod = importlib.import_module(f"bench.metrics.{m['name']}")
            v = mod.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        st: Dict[str, float] = {}
        for d in devs:
            for name, ns in trace_reduce.self_times(tr, d, lo, hi).items():
                st[name] = st.get(name, 0.0) + ns * 1e-9 / len(devs)
        def label(name):
            # the instruction and the tail of the op it came from
            scope = ctx["scopes"].get(name, "")
            return f"{name} {'/'.join(scope.split('/')[-3:])}".strip()

        out["breakdown"] = {
            "device_ops": [[label(k), v] for k, v in sorted(
                st.items(), key=lambda x: -x[1])[:10]],
            "idle_gaps": [list(x) for x in
                          trace_reduce.idle_gaps_by_host(tr, lo, hi)[:10]],
        }
        del tr
        shutil.rmtree(tdir, ignore_errors=True)

    # ---- the comparison with the reference, program state freed --------
    del state, rt, d_norms
    gc.collect()
    jax.clear_caches()
    del pool
    gc.collect()
    rows = check_rows(pool_tokens(cell, seed), cell, n_check, devices[0])
    t_ref = time.perf_counter()
    make = functools.partial(
        jax.jit(lambda k: weights.init_params(struct, c, k)), key)
    with jax.default_device(devices[0]):
        ref = reference.train(make, rows, updates, c, o,
                              devices=devices[:cell.chips])
    ref["delta"] = np.asarray(ref["delta"])
    log(f"reference: {time.perf_counter() - t_ref!r} s")
    names = reference.leaf_names(struct)
    nums = compare.numbers(readings, ref, names, first + 1)
    log("per leaf, program and reference: first gradient's norm, change's "
        "norm: " + ", ".join(
            f"{n} {float(np.linalg.norm(gp))!r} "
            f"{float(np.linalg.norm(gr))!r} {float(dp)!r} {float(dr)!r}"
            for n, gp, gr, dp, dr in zip(
                names, readings["first_grad"], ref["first_grad"],
                readings["delta"], ref["delta"])))
    within, checks = compare.verdict(nums, cell.limits)
    correct = failed == 0 and within
    log(f"losses: program {readings['losses']!r}, reference "
        f"{ref['losses']!r}")
    for k, v in nums.items():
        extra = {kk: vv for kk, vv in v.items() if kk != "value"}
        log(f"check {k} {v['value']!r} limit {cell.limits.get(k)!r} {extra}")
    out.update({"correct": bool(correct), "attempted": steps,
                "failed": failed, "metrics": metrics, "device": dev_out})
    # the result's keys in their fixed order, the compared numbers last
    order = ["correct", "attempted", "failed", "metrics", "device",
             "breakdown"]
    result = {k: out[k] for k in order if k in out}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    try:
        man = spec.manifest()
        problems = spec.validate(man)
        if problems:
            raise BenchError(f"BENCHMARK.json: {problems}")
        wl = spec.workload(man, args.workload)
        cell = Cell(wl["name"], spec.config(wl["config"]),
                    spec.traffic(wl["traffic"]), spec.limits(wl["name"]),
                    wl["chips"])
        if cell.traffic["chips"] != cell.chips:
            raise BenchError(f"traffic {wl['traffic']} is for "
                             f"{cell.traffic['chips']} chips, the cell "
                             f"asks for {cell.chips}")
    except (spec.SpecError, BenchError, KeyError) as e:
        log(f"FAIL: {e}")
        return 2

    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        log(f"FAIL: the program is not beside the benchmark ({e})")
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"FAIL: no TPU (JAX's devices are {devices[0].platform!r}); "
            f"device metrics come only from the chip")
        return 3
    if devices[0].device_kind not in spec.peaks():
        log(f"FAIL: no peaks for {devices[0].device_kind!r} in "
            f"bench/peaks.json")
        return 3
    if len(devices) < cell.chips:
        log(f"FAIL: the cell asks for {cell.chips} chips, JAX sees "
            f"{len(devices)}")
        return 3
    cache = enable_compile_cache()
    # every program, however quick to compile, is read back from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"device {devices[0].device_kind} x{len(devices)}, jax "
        f"{jax.__version__}, compile cache {cache}")
    try:
        res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       devices, man)
    except BenchError as e:
        log(f"FAIL: {e}")
        return 1
    for k, v in res["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
