"""Data pipeline determinism + checkpoint roundtrip + config registry."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import restore, save
from repro.configs import (
    ARCH_NAMES,
    SHAPES,
    get_config,
    config_for_shape,
    reduce_for_smoke,
)
from repro.data.pipeline import SyntheticDataset, make_batch
from repro.models.model import init_params


def test_make_batch_deterministic():
    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    b1 = make_batch(cfg, seed=7, step=3, batch=4, seq_len=16)
    b2 = make_batch(cfg, seed=7, step=3, batch=4, seq_len=16)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = make_batch(cfg, seed=7, step=4, batch=4, seq_len=16)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    b4 = make_batch(cfg, seed=8, step=3, batch=4, seq_len=16)
    assert not np.array_equal(b1["tokens"], b4["tokens"])


def test_dataset_iterator_advances():
    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    ds = SyntheticDataset(cfg, seed=0, batch=2, seq_len=8)
    a = next(ds)
    b = next(ds)
    assert not np.array_equal(a["tokens"], b["tokens"])


def test_batch_tokens_learnable_structure():
    """The synthetic stream is Markov-ish: a model can beat the unigram
    entropy, so convergence tests actually converge.  Check that the
    bigram distribution is far from independent."""
    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    b = make_batch(cfg, seed=0, step=0, batch=8, seq_len=256)
    toks = np.asarray(b["tokens"]).reshape(-1)
    pairs = {}
    for x, y in zip(toks[:-1], toks[1:]):
        pairs.setdefault(int(x), []).append(int(y))
    # for tokens with >=8 successors, the mode should be overrepresented
    frac = []
    for x, ys in pairs.items():
        if len(ys) >= 8:
            vals, counts = np.unique(ys, return_counts=True)
            frac.append(counts.max() / len(ys))
    assert np.mean(frac) > 0.3


def test_checkpoint_roundtrip(tmp_path):
    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    state = {"params": params, "step": jnp.asarray(17)}
    save(str(tmp_path), 17, state)
    back = restore(str(tmp_path), 17, state)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_registry_complete():
    assert len(ARCH_NAMES) == 11
    assert len(SHAPES) == 4
    for name in ARCH_NAMES:
        cfg = get_config(name)
        assert cfg.total_params() > 0
        smoke = reduce_for_smoke(cfg)
        assert smoke.d_model <= 512
        assert smoke.n_layers <= 3
        if smoke.moe:
            assert smoke.moe.n_experts <= 4


def test_assigned_config_numbers():
    """Spot-check the assigned architecture table."""
    c = get_config("deepseek-v2-236b")
    assert (c.n_layers, c.d_model, c.n_heads, c.vocab_size) == (60, 5120, 128, 102400)
    assert c.moe.n_experts == 160 and c.moe.experts_per_token == 6
    assert c.mla.kv_lora_rank == 512
    c = get_config("gemma2-2b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff) == (
        26, 2304, 8, 4, 9216)
    assert c.vocab_size == 256000 and c.attn_logit_softcap > 0
    c = get_config("llama4-maverick-400b-a17b")
    assert c.moe.n_experts == 128 and c.moe.experts_per_token == 1
    c = get_config("rwkv6-1.6b")
    assert c.n_layers == 24 and c.d_model == 2048 and c.vocab_size == 65536
    c = get_config("llama-3.2-vision-90b")
    assert c.n_layers == 100 and c.d_model == 8192
    c = get_config("seamless-m4t-large-v2")
    assert c.is_encoder_decoder and c.n_encoder_layers == 24


def test_long_context_applicability():
    runnable = {a for a in ARCH_NAMES
                if config_for_shape(a, "long_500k").supports_long_context()}
    # starcoder2 uses a native 4k sliding window on every layer, so its
    # ring cache is O(window) and 500k decode is runnable (DESIGN.md §4)
    assert runnable == {"recurrentgemma-9b", "rwkv6-1.6b", "gemma2-2b",
                        "starcoder2-7b"}
