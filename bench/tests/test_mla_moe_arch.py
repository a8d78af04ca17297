"""The DeepSeek-V2-Lite module (``archs/mla_moe.py``) against the program:
at test widths on the CPU the program's loss and first gradient, through
``DeftRuntime`` as ``run.py`` drives it, match the module's plain
reference; at the configuration's sizes the module counts the
program's parameter tree."""
import json

import numpy as np
import pytest

from conftest import tiny_cell

from bench import flops, run, spec
from bench.archs import mla_moe

CELL = "deepseek-v2-lite-l5.1chip.s4096"
SEED = 2147483659


def test_total_params_is_the_programs_tree():
    import jax

    from repro.models.model import init_params

    c = spec.config("deepseek-v2-lite-l5")
    acfg = run.program_config(c)
    struct = jax.eval_shape(lambda k: init_params(k, acfg),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(struct))
    assert n == flops.total_params(c) == 535_060_992
    assert struct["stack"][0]["ffn"]["experts"]["gate"].shape == (
        4, 8, 2048, 1408)
    assert struct["stack"][0]["ffn"]["router"].shape == (4, 2048, 64)
    assert struct["head"]["w"].shape == (2048, 12800)
    assert acfg.moe.held == 8 and acfg.moe.n_experts == 64
    assert not acfg.moe.norm_topk_prob and acfg.moe.seq_aux


@pytest.mark.parametrize("key,value", [
    ("routed_scaling_factor", 2.5), ("q_lora_rank", 1536),
    ("scoring_func", "sigmoid"), ("topk_method", "group_limited_greedy")])
def test_program_config_refuses_what_the_program_does_not_run(key, value):
    c = dict(spec.config("deepseek-v2-lite-l5"), **{key: value})
    with pytest.raises(ValueError, match="archs.mla_moe runs"):
        mla_moe.program_config(c)


def test_leaf_kind_knows_the_latent_leaves():
    c = spec.config("deepseek-v2-lite-l5")
    kind = mla_moe.leaf_kind
    assert kind(("stack", "0", "mixer", "kv_norm"), 2, c) == "rms_scale"
    assert kind(("prefix", "0", "mixer", "wq"), 2, c) == "matrix"
    assert kind(("stack", "0", "ffn", "experts", "down"), 4, c) == "matrix"
    assert kind(("embed", "table"), 2, c) == "embed"
    with pytest.raises(ValueError):
        kind(("stack", "0", "mixer", "mystery"), 1, c)


def test_counts_at_the_configurations_sizes():
    c = spec.config("deepseek-v2-lite-l5")
    per_token = flops.model_flops_per_token(c, 4096)
    attn_mats = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    moe = 2048 * 64 + 6 * 8 / 64 * 3 * 2048 * 1408 + 2 * 3 * 2048 * 1408
    mats = 5 * attn_mats + 3 * 2048 * 10944 + 4 * moe + 12800 * 2048
    scores = 5 * 2 * 16 * 320 * 4097 / 2
    assert per_token == pytest.approx(6 * mats + 3 * scores)
    cost = mla_moe.expert_mm_cost(c, 4096, 1)
    rows = 4096 * 6 * 8 / 64
    assert cost["flops"] == pytest.approx(4 * 18 * rows * 2048 * 1408)
    weights = 3 * 8 * 2048 * 1408
    assert cost["bytes"] == pytest.approx(
        4 * 2 * (3 * weights + rows * 9 * (2048 + 1408)))
    a = flops.attn_fwd_cost(c, 4096, 1)
    assert a["flops"] == pytest.approx(2 * 16 * 320 * 4097 / 2 * 4096)


def test_program_matches_the_reference_through_the_runtime():
    """In f32 on both sides the program and the reference differ only in
    the order of their sums: every compared number is under 1e-5 of its
    scale (measured about 1e-7 to 2e-6)."""
    import jax

    cell = tiny_cell(CELL, batch_per_chip=2)
    cell.cfg["guarantees"] = {**cell.cfg["guarantees"], "compute_dtype": "f32"}
    res = run.run_cell(cell, SEED, 1.0, False, jax.devices(),
                       spec.manifest(), peak=spec.peaks()["TPU v5 lite"])
    checks = {k: v["value"] for k, v in res["checks"].items()}
    assert res["failed"] == 0
    assert checks["loss_gap"] < 1e-5, checks
    assert checks["grad_gap"] < 1e-5, checks
    assert checks["grad_diff"] < 2e-5, checks
    assert checks["update_gap"] < 1e-5, checks
    assert res["correct"], json.dumps(res["checks"])
