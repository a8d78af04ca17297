"""Shared model primitives: norms, RoPE, activations, initializers.

Pure functions over explicit parameter dicts — no module framework.  All
weights are created by ``init_*`` helpers taking a PRNG key and returning
plain jnp arrays; forward helpers take ``(params, x, ...)``.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------
def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32) -> jax.Array:
    """Truncated-normal fan-in init (std = 1/sqrt(d_in))."""
    std = 1.0 / math.sqrt(d_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, (d_in, d_out)) * std).astype(
        dtype
    )


def embed_init(key, vocab: int, d: int, dtype=jnp.float32) -> jax.Array:
    return (jax.random.truncated_normal(key, -2.0, 2.0, (vocab, d)) * 0.02).astype(
        dtype
    )


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def init_norm(kind: str, d: int, dtype=jnp.float32):
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((d,), dtype)}  # gemma-style (1+scale)
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def apply_norm(p, x: jax.Array, kind: str, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * (1.0 + p["scale"].astype(jnp.float32))
        return y.astype(x.dtype)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_per_head(x: jax.Array, scale: Optional[jax.Array], eps: float = 1e-6):
    """qk-norm: RMS-normalize the last (head) dim. scale: [head_dim] or None."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * (1.0 + scale.astype(jnp.float32))
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor (HF ``yarn_get_mscale``)."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(scaling, head_dim: int, theta: float
                          ) -> Tuple[int, int]:
    """(low, high): the frequency indices where YaRN's ramp from the
    unscaled to the interpolated frequencies starts and ends (HF
    ``yarn_find_correction_range``)."""
    def dim(rotations):
        return (head_dim * math.log(
            scaling.original_max_position_embeddings
            / (rotations * 2 * math.pi))) / (2 * math.log(theta))

    low = math.floor(dim(scaling.beta_fast))
    high = math.ceil(dim(scaling.beta_slow))
    return max(low, 0), min(high, head_dim - 1)


def rope_frequencies(head_dim: int, theta: float, scaling=None) -> jax.Array:
    """[head_dim/2] inverse frequencies.  With YaRN ``scaling``
    (:class:`~repro.configs.base.RopeScaling`) the frequencies below the
    correction range keep theta's, those above it are divided by the
    factor, and a linear ramp blends the two in between (HF
    ``DeepseekV2YarnRotaryEmbedding``)."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    freqs = 1.0 / (theta**exponent)
    if scaling is None:
        return freqs
    low, high = yarn_correction_range(scaling, head_dim, theta)
    ramp = (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / max(
        high - low, 1e-3)
    keep = 1.0 - jnp.clip(ramp, 0.0, 1.0)
    return freqs / scaling.factor * (1.0 - keep) + freqs * keep


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling=None) -> jax.Array:
    """x: [..., seq, n_heads, head_dim]; positions: [..., seq] int32.

    Half-split rotation: element i of the first half and element i of
    the second half rotate together as one pair, at frequency i.  With
    YaRN ``scaling`` the frequencies are :func:`rope_frequencies`' and
    cos/sin are scaled by mscale(factor, mscale) / mscale(factor,
    mscale_all_dim).
    """
    half = x.shape[-1] // 2
    freqs = rope_frequencies(x.shape[-1], theta, scaling)  # [half]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, half]
    angles = angles[..., None, :]  # broadcast over heads
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            sin, cos = sin * m, cos * m
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1.astype(jnp.float32) * cos - x2.astype(jnp.float32) * sin
    y2 = x2.astype(jnp.float32) * cos + x1.astype(jnp.float32) * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------
def softcap(x: jax.Array, cap: float) -> jax.Array:
    if not cap:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


def gated_act(kind: str, gate: jax.Array, up: jax.Array) -> jax.Array:
    if kind == "silu":
        return jax.nn.silu(gate) * up
    if kind == "gelu":
        return jax.nn.gelu(gate, approximate=True) * up
    raise ValueError(kind)


def ffn_param_shapes(cfg, d_ff: Optional[int] = None) -> Tuple[str, ...]:
    return ("gate", "up", "down") if cfg.ffn_activation in ("silu", "gelu") else (
        "up",
        "down",
    )


def init_ffn(key, cfg, d_ff: Optional[int] = None, dtype=jnp.float32):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    keys = jax.random.split(key, 3)
    if cfg.ffn_activation in ("silu", "gelu"):
        return {
            "gate": dense_init(keys[0], d, f, dtype),
            "up": dense_init(keys[1], d, f, dtype),
            "down": dense_init(keys[2], f, d, dtype),
        }
    return {
        "up": dense_init(keys[0], d, f, dtype),
        "down": dense_init(keys[1], f, d, dtype),
    }


def apply_ffn(p, x: jax.Array, cfg) -> jax.Array:
    from repro.sharding import constrain

    # NOTE: the leading dim must be named 'batch' — with_sharding_constraint
    # treats None dims as FORCED-REPLICATED, and an unnamed batch dim made
    # the partitioner all-gather the global batch into every FFN matmul
    # (54 GiB f32/step at gemma2-2b train_4k; see EXPERIMENTS.md §Perf).
    names_in = ["batch"] + [None] * (x.ndim - 2)
    if cfg.ffn_activation in ("silu", "gelu"):
        gate = constrain(x @ p["gate"], (*names_in, "ff"))
        up = constrain(x @ p["up"], (*names_in, "ff"))
        h = gated_act(cfg.ffn_activation, gate, up)
    else:  # plain (non-gated) GELU MLP — starcoder2 / seamless / rwkv-style
        h = jax.nn.gelu(constrain(x @ p["up"], (*names_in, "ff")), approximate=True)
    return constrain(h @ p["down"], (*names_in, "embed"))


def cross_entropy_loss(
    logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None
) -> jax.Array:
    """Mean token cross-entropy; logits [..., V], labels int32 [...]."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
