#!/usr/bin/env python3
"""Readings of the control and of planted faults, for setting a cell's
limits (``bench/limits/<cell>.json``).  The benchmark's runs never run
this.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...] \\
        [--variants fp8 half_batch no_exchange]

For each seed, in one process on one chip: the reference over the
cell's first steps (its plan, its sizes, its weights and batches), then
each variant put in the program's place and compared with it by the
numbers that decide ``correct``:

* ``fp8`` — the control: the reference with every matrix product in
  float8 e4m3 (one precision below the bf16 that the configuration
  states);
* ``half_batch`` — half of each step's rows (or of a single row's
  positions) left out, the mean taken over the rest;
* ``no_exchange`` — no gradient exchange between chips: each chip's
  update sees its own gradient alone (cells on more than one chip).

A state left unchanged reads 1 by ``update_gap`` and needs no run.
One JSON line per seed and variant, with the verdict that the cell's
limits (``bench/limits/<cell>.json``) give it through the comparison
that decides a run's ``correct`` (``compare.verdict``): a control or a
fault has to come out ``"correct": false``.
"""
from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run, spec  # noqa: E402


def readings(cell: run.Cell, seeds, variants, devices):
    import jax
    import numpy as np

    from bench import compare, reference, weights

    _, params_abs, schedule, _ = run.make_plan(cell, devices)
    n = cell.traffic["check_steps"]
    updates = run.plan_updates(schedule, n)
    steady = 1 + next(i for i, u in enumerate(updates) if u and u[0])
    names = reference.leaf_names(params_abs)
    o = cell.traffic["optimizer"]
    out = []
    for seed in seeds:
        rows = run.check_rows(run.pool_tokens(cell, seed), cell, n,
                              devices[0])
        make = functools.partial(jax.jit(
            lambda k: weights.init_params(params_abs, cell.cfg, k)),
            weights.base_key(seed))
        as_np = lambda r: {**r, "delta": np.asarray(r["delta"])}
        with jax.default_device(devices[0]):
            ref = as_np(reference.train(make, rows, updates, cell.cfg, o))
            for var in variants:
                if var == "no_exchange" and cell.chips == 1:
                    continue
                kw = ({"precision": "fp8"} if var == "fp8"
                      else {"fault": var})
                got = as_np(reference.train(make, rows, updates, cell.cfg,
                                            o, **kw))
                nums = compare.numbers(got, ref, names, steady)
                correct, _ = compare.verdict(nums, cell.limits)
                rec = {"workload": cell.name, "seed": seed, "variant": var,
                       "correct": correct,
                       **{k: v["value"] for k, v in nums.items()},
                       "losses": got["losses"], "ref_losses": ref["losses"]}
                print(json.dumps(rec), flush=True)
                out.append(rec)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+",
                    default=["fp8", "half_batch", "no_exchange"])
    args = ap.parse_args(argv)
    man = spec.manifest()
    wl = spec.workload(man, args.workload)
    cell = run.Cell(wl["name"], spec.config(wl["config"]),
                    spec.traffic(wl["traffic"]), spec.limits(wl["name"]),
                    wl["chips"])
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: FAIL: no TPU", file=sys.stderr)
        return 3
    readings(cell, args.seeds, args.variants, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
