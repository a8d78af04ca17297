"""A run with the timed path broken underneath comes out not correct.

Each test drives the whole of a run but the look for a chip (build,
warm-up, window, reference, comparison) at test widths on the CPU,
under the cell's own limits, with one fault planted in the program: a
step that returns its state unchanged, half of the batch left out (the
mean taken over the rest), the exchange between chips left out, and
the loss altered where it is produced.  The same run without a fault
comes out correct.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from conftest import tiny_cell, tiny_layernorm_cell

ROOT = pathlib.Path(__file__).resolve().parents[2]
ONE_CHIP = "qwen3-4b-l1.1chip.s4096"
SEED = 2147483659


def _run(cell, wrapper=None):
    import jax

    from bench import run, spec

    return run.run_cell(cell, SEED, 1.0, False, jax.devices(),
                        spec.manifest(), step_wrapper=wrapper,
                        peak=spec.peaks()["TPU v5 lite"])


def _unchanged(step):
    import jax
    import jax.numpy as jnp

    def f(i, state, batch):
        keep = jax.tree.map(jnp.copy, state)
        _, m = step(i, state, batch)
        return keep, m
    return f


def _half_batch(step):
    # the second half of the rows repeats the first: the mean is the
    # first half's alone
    def f(i, state, batch):
        n = batch["tokens"].shape[0] // 2
        half = {k: v.at[n:].set(v[:n]) for k, v in batch.items()}
        return step(i, state, half)
    return f


def _loss_altered(step):
    def f(i, state, batch):
        state, m = step(i, state, batch)
        return state, {**m, "loss": m["loss"] * 1.01}
    return f


@pytest.mark.parametrize("make", [tiny_cell, lambda _, **kw:
                                  tiny_layernorm_cell(**kw)],
                         ids=["qwen3", "layernorm_gelu_window"])
def test_sound_run_is_correct(make):
    res = _run(make(ONE_CHIP, batch_per_chip=2))
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _loss_altered],
                         ids=["state_unchanged", "half_batch",
                              "loss_altered"])
def test_fault_is_not_correct(fault):
    res = _run(tiny_cell(ONE_CHIP, batch_per_chip=2), fault)
    assert not res["correct"], res["checks"]


CHILD = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
sys.path.insert(0, sys.argv[1] + "/src")
sys.path.insert(0, sys.argv[1] + "/bench/tests")
import jax
import repro.train.runtime as runtime
if sys.argv[2] == "no_exchange":
    runtime._sync_primary = lambda x, axes: x
    runtime._sync_secondary = lambda x, axes, sizes, chain=None: x
from conftest import tiny_cell, tiny_layernorm_cell
from bench import run, spec
cell = tiny_cell("qwen3-4b-l1.4chip.s4096")
res = run.run_cell(cell, 2147483659, 1.0, False, jax.devices(),
                   spec.manifest(), peak=spec.peaks()["TPU v5 lite"])
print(json.dumps({"correct": res["correct"], "checks": res["checks"]}))
"""


@pytest.mark.parametrize("fault", ["none", "no_exchange"])
def test_exchange_left_out_on_four_devices(fault):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHILD, str(ROOT), fault],
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] == (fault == "none"), res["checks"]
