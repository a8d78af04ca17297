"""The user-facing entry points: ``chip_smoke.py`` refuses to run without
a TPU, ``launch.train`` cuts depth only and bounds the logits block, the
compile cache goes where it is documented, and the planner's peaks are
keyed by device kind."""
from __future__ import annotations

import os
import pathlib
import shutil
import subprocess
import sys
import types

import jax
import pytest

from repro.core.profiler import DEVICE_PEAKS, HardwareModel, peaks_for
from repro.launch import compile_cache, train
from repro.launch.train import LOGITS_BUDGET_BYTES, loss_chunk_for, main

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run_smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_without_tpu():
    proc = _run_smoke(ROOT, ROOT / "chip_smoke.py")
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _run_smoke(tmp_path, tmp_path / "chip_smoke.py")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_layers_is_refused_with_smoke():
    with pytest.raises(SystemExit) as e:
        main(["--arch", "qwen3-4b", "--smoke", "--layers", "2"])
    assert e.value.code == 2


@pytest.mark.parametrize("scheduler", ["deft", "ddp"])
def test_main_returns_what_each_step_measured(scheduler, monkeypatch):
    # the tests stay cache-free
    monkeypatch.setattr(train, "enable_compile_cache", lambda: None)
    steps = 4
    res = main(["--arch", "qwen3-4b", "--smoke", "--scheduler", scheduler,
                "--steps", str(steps), "--batch", "2", "--seq", "16",
                "--lr", "3e-4", "--data", "1", "--model", "1"])
    assert res["cfg"].name == "qwen3-4b-smoke"
    for key in ("losses", "step_s", "updated"):
        assert len(res[key]) == steps, key
    assert all(x > 0 for x in res["step_s"])
    assert all(x == x for x in res["losses"])          # no NaN
    # every step consumed (donated) the state it was given
    assert res["donation_held"]
    assert (res["runtime"] is None) == (scheduler == "ddp")
    if scheduler == "deft":
        assert res["compile_s"] > 0
        assert res["runtime"].opt_spec.lr == 3e-4


@pytest.mark.parametrize("vocab,batch,seq", [
    (512, 4, 32),             # smoke: the whole sequence fits
    (151_936, 8, 512),        # qwen3-4b on one chip
    (256_000, 2, 4096),       # gemma-sized vocabulary, long sequence
])
def test_loss_chunk_bounds_the_logits_block(vocab, batch, seq):
    chunk = loss_chunk_for(vocab, batch, seq)
    block = 4 * vocab * batch * (chunk or seq)
    assert block <= LOGITS_BUDGET_BYTES
    if chunk:
        assert chunk < seq and seq % chunk == 0
        # the largest power of two that fits
        assert 2 * block > LOGITS_BUDGET_BYTES or 2 * chunk >= seq


def test_compile_cache_placement(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", prev)
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(ROOT / ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_peaks_are_keyed_by_device_kind():
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    hw = HardwareModel.for_device(v5e, dp_degree=4)
    assert hw.chip_flops == DEVICE_PEAKS["TPU v5 lite"].bf16_flops
    assert hw.dp_degree == 4
    # a CPU prices the planning target; an unknown TPU is an error
    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
    assert HardwareModel.for_device(cpu) == HardwareModel()
    with pytest.raises(ValueError, match="no published peaks"):
        HardwareModel.for_device(
            types.SimpleNamespace(platform="tpu", device_kind="TPU v9"))
    with pytest.raises(ValueError):
        peaks_for("TPU v9")
