#!/usr/bin/env python3
"""Chip smoke test: train qwen3-4b at its published widths on TPU through
the trainer's own entry point (``repro.launch.train.main``), and check
what comes out.

    python chip_smoke.py              # one chip: DeFT, depth cut to 2 layers
    python chip_smoke.py --chips 4    # four chips: DeFT vs DDP on data=4

Only the depth is cut (``--layers``); d_model 2560, 32/8 heads of 128,
d_ff 9728 and the tied 151,936-token vocabulary are the published ones.
The weights are random, drawn from ``--seed``; the data is the repo's
synthetic token stream.

One chip checks that the update engine is the flat Pallas kernel and the
attention the Pallas kernel (both read back from the compiled phases),
that at least one full schedule period with an update phase ran, that
every loss is finite and the last is below step 0's, that step 0's loss
matches a plain jitted ``loss_fn`` on the same initial parameters and
batch, and that every step consumed (donated) its input state.

Four chips run DeFT and then the DDP baseline on mesh data=4, model=1
(global batch 32), and check that their step-0 losses agree, that both
donate, and that DeFT's loss falls.

Everything runs in this one process.  The script exits non-zero, and
prints no JSON line, when no TPU is attached, when the repository's
package is not next to it, or when any phase or check fails.  On success
the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "qwen3-4b"
SEQ = 512
BATCH_PER_CHIP = 8
# AdamW at 1e-3 (the trainer's default, sized for the smoke widths)
# oscillates at d_model 2560 under DeFT's delayed updates; 3e-4 descends
LR = 3e-4
# bf16 keeps 8 mantissa bits: two computations of the same bf16 forward
# may differ by a few roundings of the final mean
LOSS_RTOL = 1e-2


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def train(trainer, *, scheduler: str, chips: int, layers: int, steps: int,
          seed: int) -> dict:
    argv = [
        "--arch", ARCH, "--layers", str(layers),
        "--scheduler", scheduler, "--compute-dtype", "bf16",
        "--batch", str(BATCH_PER_CHIP * chips), "--seq", str(SEQ),
        "--steps", str(steps), "--seed", str(seed), "--lr", repr(LR),
        "--data", str(chips), "--model", "1",
    ]
    print(f"== {scheduler}: python -m repro.launch.train {' '.join(argv)}",
          flush=True)
    res = trainer.main(argv)
    for i, (loss, wall, upd) in enumerate(
            zip(res["losses"], res["step_s"], res["updated"])):
        print(f"{scheduler} step {i}: loss={loss!r} step_s={wall!r} "
              f"updated={upd}", flush=True)
    check(len(res["losses"]) == steps,
          f"{scheduler}: ran {len(res['losses'])} of {steps} steps")
    check(all(math.isfinite(x) for x in res["losses"]),
          f"{scheduler}: non-finite loss in {res['losses']}")
    check(res["donation_held"],
          f"{scheduler}: a step left its input state alive (donation "
          f"failed)")
    return res


def check_deft(res: dict) -> None:
    """The DeFT run took the flat Pallas update engine and Pallas
    attention, covered a full period with an update, and its loss fell."""
    rt = res["runtime"]
    st = rt.stats()
    engine = ("flat/" + st["update_impl"] if st["flat_state"]
              else "per-leaf tree")
    print(f"deft: update engine {engine}, period {st['period']}, "
          f"{st['unique_phases']} unique phases compiled in "
          f"{res['compile_s']!r} s", flush=True)
    check(engine == "flat/pallas",
          f"update engine is {engine}, not flat/pallas")
    kernels = rt.phase_kernels()
    for i, pk in enumerate(kernels):
        print(f"deft: phase {i} do_update={pk['do_update']} "
              f"kernels={pk['kernels']}", flush=True)
        check("flash_attention_fwd" in pk["kernels"],
              f"phase {i} does not call the Pallas attention kernel")
        if pk["do_update"]:
            check("bucket_update" in pk["kernels"],
                  f"update phase {i} does not call the Pallas bucket-update "
                  f"kernel")
    check(len(res["losses"]) >= st["period"],
          f"{len(res['losses'])} steps do not cover the period "
          f"{st['period']}")
    check(any(res["updated"]), "no step applied a delayed update")
    check(res["losses"][-1] < res["losses"][0],
          f"loss did not fall: step 0 {res['losses'][0]!r}, last "
          f"{res['losses'][-1]!r}")
    for i, c in enumerate(st["collectives_per_phase"]):
        print(f"deft: schedule phase {i}: {c['primary']} primary, "
              f"{c['secondary']} secondary collectives", flush=True)
    if not any(c["secondary"] for c in st["collectives_per_phase"]):
        print("deft: no bucket went to the secondary ring chain", flush=True)


def reference_step0_loss(res: dict, seed: int) -> float:
    """Plain jitted ``loss_fn`` on the runtime's own initial parameters
    (drawn at bf16, as ``DeftRuntime.init_state`` draws them) and on the
    stream's batch 0."""
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import make_batch
    from repro.models.model import init_params, loss_fn

    cfg = res["cfg"]
    params = init_params(jax.random.PRNGKey(seed), cfg, dtype=jnp.bfloat16)
    batch = make_batch(cfg, seed, 0, BATCH_PER_CHIP, SEQ)
    f = jax.jit(lambda p, b: loss_fn(p, cfg, b,
                                     loss_chunk=res["loss_chunk"])[0])
    return float(f(params, batch))


def close(a: float, b: float) -> bool:
    return abs(a - b) <= LOSS_RTOL * max(abs(a), abs(b))


def one_chip(trainer, args) -> None:
    from repro.kernels.flash_attention.ops import default_attention_impl

    print(f"attention implementation: "
          f"{default_attention_impl(SEQ, SEQ)}", flush=True)
    res = train(trainer, scheduler="deft", chips=1, layers=args.layers,
                steps=args.steps, seed=args.seed)
    print(f"model: {ARCH} d_model={res['cfg'].d_model} "
          f"vocab={res['cfg'].vocab_size} layers={res['cfg'].n_layers} "
          f"params={res['n_params']} loss_chunk={res['loss_chunk']}",
          flush=True)
    check_deft(res)
    ref = reference_step0_loss(res, args.seed)
    print(f"step-0 loss: runtime {res['losses'][0]!r} vs plain loss_fn "
          f"{ref!r}", flush=True)
    check(close(res["losses"][0], ref),
          f"step-0 loss {res['losses'][0]!r} differs from plain loss_fn "
          f"{ref!r} beyond rtol {LOSS_RTOL}")


def four_chips(trainer, args) -> None:
    deft = train(trainer, scheduler="deft", chips=4, layers=args.layers,
                 steps=args.steps, seed=args.seed)
    check_deft(deft)
    ddp = train(trainer, scheduler="ddp", chips=4, layers=args.layers,
                steps=args.steps, seed=args.seed)
    print(f"step-0 loss: deft {deft['losses'][0]!r} vs ddp "
          f"{ddp['losses'][0]!r}", flush=True)
    check(close(deft["losses"][0], ddp["losses"][0]),
          "DeFT and DDP step-0 losses disagree")
    # step 0 of DDP compiles on first call, DeFT compiled ahead of time
    for name, r in (("deft", deft), ("ddp", ddp)):
        steady = r["step_s"][1:]
        print(f"{name}: mean step_s over steps 1..{len(steady)} = "
              f"{sum(steady) / len(steady)!r}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--layers", type=int, default=2,
                    help="depth cut of qwen3-4b (widths stay published)")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    try:
        from repro.launch import train as trainer
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: FAIL: the repository's package is not next to "
              f"this script ({e})", file=sys.stderr)
        return 1
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: FAIL: no TPU found (JAX's devices are "
              f"{dev.platform!r})", file=sys.stderr)
        return 1
    for var in ("REPRO_BUCKET_UPDATE", "REPRO_QUANTIZE"):
        val = os.environ.get(var, "").strip().lower()
        if val in ("ref", "interpret"):
            print(f"chip_smoke: FAIL: {var}={val} takes the kernels off "
                  f"the chip", file=sys.stderr)
            return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: FAIL: --chips {args.chips} but JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 1

    cache = enable_compile_cache()
    print(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__}"
          f", compile cache {cache}", flush=True)
    try:
        (four_chips if args.chips == 4 else one_chip)(trainer, args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
