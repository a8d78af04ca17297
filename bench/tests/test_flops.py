"""bench/flops.py and the dense module's counts (bench/archs/dense.py)
against hand counts of the benchmark's configuration and of the
StarCoder2 test configuration (LayerNorm, non-gated MLP)."""
import json

import pytest

from conftest import DATA

from bench import flops, spec
from bench.archs import dense


def test_qwen3_4b_l1_counts():
    c = spec.config("qwen3-4b-l1")
    d, ff, v, hd = 2560, 9728, 151_936, 128
    attn = d * 32 * hd + 2 * d * 8 * hd + 32 * hd * d      # q, k, v, o
    mlp = 3 * d * ff                                        # gate, up, down
    assert dense.matmul_params(c) == attn + mlp + v * d == 489_881_600
    # + 2 RMSNorm weights a layer, the q/k norms, the final norm
    assert flops.total_params(c) == 489_881_600 + 2 * d + 2 * hd + d
    per_tok = 6 * 489_881_600 + 3 * 4 * 32 * hd * (4096 + 1) / 2
    assert flops.model_flops_per_token(c, 4096) == pytest.approx(per_tok)
    assert per_tok * 4096 == pytest.approx(12.4517e12, rel=1e-4)


def test_starcoder2_7b_l1_counts():
    c = json.loads((DATA / "starcoder2-7b-l1.json").read_text())
    d, ff, v, hd = 4608, 18432, 49_152, 128
    attn = d * 36 * hd + 2 * d * 4 * hd + 36 * hd * d
    mlp = 2 * d * ff                                        # up, down
    assert dense.matmul_params(c) == attn + mlp + v * d == 443_547_648
    # LayerNorm scale and bias: two a layer and the final one
    assert flops.total_params(c) == 443_547_648 + 3 * 2 * d
    # a window of 4,096 over 4,096 tokens hides no key
    per_tok = 6 * 443_547_648 + 3 * 4 * 36 * hd * (4096 + 1) / 2
    assert flops.model_flops_per_token(c, 4096) == pytest.approx(per_tok)
    assert per_tok * 4096 == pytest.approx(11.3646e12, rel=1e-4)


def test_mean_context_inside_a_window():
    # 8 positions, window 4: positions 0..3 see 1..4 keys, 4..7 see 4
    assert dense.mean_context(8, 4) == (1 + 2 + 3 + 4 + 4 * 4) / 8
    assert dense.mean_context(8, 0) == 4.5
    assert dense.mean_context(8, 8) == 4.5


def test_attention_forward_and_update_bytes():
    c = spec.config("qwen3-4b-l1")
    cost = flops.attn_fwd_cost(c, 4096, 1)
    assert cost["flops"] == 4 * 32 * 128 * 2048.5 * 4096
    qkv = 4096 * (32 + 16) * 128 * 2
    out = 4096 * 32 * 128 * 2
    assert cost["bytes"] == qkv + out + 32 * 4096 * 4
    assert flops.bucket_update_bytes(10, False) == 10 * 4 * 7
    assert flops.bucket_update_bytes(10, True) == 10 * 4 * 8
    peak = spec.peaks()["TPU v5 lite"]
    assert flops.least_time_s(197e12, 0, peak) == 1.0
    assert flops.least_time_s(0, 819e9, peak) == 1.0
