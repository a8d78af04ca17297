"""Serving-path correctness: prefill + token-by-token decode must produce
the same logits as one full forward pass (per architecture family)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.models.model import (
    decode_step,
    encode,
    forward,
    init_cache,
    init_params,
    prefill,
)

# one representative per family/mixer type (keeps CPU time sane)
FAMILIES = [
    "qwen3-4b",           # dense GQA + qk-norm
    "gemma2-2b",          # local+global alternating + softcaps + post-norms
    "deepseek-v2-236b",   # MLA + MoE
    "deepseek-v2-lite",   # MLA without a q LoRA, YaRN + MoE (no renorm)
    "rwkv6-1.6b",         # rwkv recurrence
    "recurrentgemma-9b",  # rglru + local attention hybrid
    "seamless-m4t-large-v2",   # enc-dec cross attention
    "llama-3.2-vision-90b",    # gated cross-attention VLM
]


@pytest.mark.parametrize("arch", FAMILIES)
def test_prefill_then_decode_matches_forward(arch):
    cfg = reduce_for_smoke(get_config(arch))
    key = jax.random.PRNGKey(1)
    params = init_params(key, cfg)
    B, S = 2, 24
    n_prefill = 16
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    memory = None
    if cfg.modality != "text":
        memory = jax.random.normal(
            key, (B, max(cfg.n_modal_tokens, 1), cfg.d_model)
        )

    enc_mem = memory
    if cfg.is_encoder_decoder:
        enc_mem = encode(params, cfg, memory)

    # ground truth: full forward over all S positions.  The expert layer
    # drops no token however many share a dispatch (48-token forward vs
    # 1-token decode), so both paths must agree exactly.
    full_logits, _, _, stats = forward(params, cfg, tokens, memory=enc_mem)
    if cfg.moe is not None:
        assert float(stats["moe_dropped"]) == 0.0
        assert float(stats["moe_rows"]) > 0.0

    # prefill the first n_prefill tokens, then decode the rest one by one
    cache = init_cache(cfg, B, S, prefill_chunk=n_prefill)
    last, cache = prefill(params, cfg, tokens[:, :n_prefill], cache,
                          memory=memory)
    got = [last]
    for i in range(n_prefill, S):
        logits, cache = decode_step(params, cfg, tokens[:, i],
                                    cache, jnp.asarray(i))
        got.append(logits)
    got = jnp.stack(got, axis=1)  # positions n_prefill-1 .. S-1

    want = full_logits[:, n_prefill - 1 :]
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want))) + 1e-6
    assert err / scale < 2e-4, f"{arch}: decode diverges from forward ({err})"


def test_sliding_window_ring_cache_long_decode():
    """Decode far past the window: the ring cache must keep exactly the
    window and match a full forward."""
    cfg = reduce_for_smoke(get_config("gemma2-2b"))
    assert cfg.sliding_window
    key = jax.random.PRNGKey(2)
    params = init_params(key, cfg)
    B = 1
    S = cfg.sliding_window * 2 + 7   # well past the ring size
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    full_logits, _, _, _ = forward(params, cfg, tokens)

    cache = init_cache(cfg, B, S, prefill_chunk=1)
    logits, cache = prefill(params, cfg, tokens[:, :1], cache)
    for i in range(1, S):
        logits, cache = decode_step(params, cfg, tokens[:, i], cache,
                                    jnp.asarray(i))
    err = float(jnp.max(jnp.abs(logits - full_logits[:, -1])))
    scale = float(jnp.max(jnp.abs(full_logits[:, -1]))) + 1e-6
    assert err / scale < 2e-4
