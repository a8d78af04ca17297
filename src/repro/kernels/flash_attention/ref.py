"""Pure-jnp oracle for flash attention.

Naive O(S^2)-memory attention with every mask/feature the models need:
causal, sliding window, logit soft-capping, GQA head grouping.  This is
the ground truth the Pallas kernel and the blocked-jnp path are tested
against (tests/test_kernels_attention.py sweeps shapes/dtypes).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import jax


def attention_reference(
    q: jax.Array,            # [B, Sq, H, D]
    k: jax.Array,            # [B, Sk, KV, D]
    v: jax.Array,            # [B, Sk, KV, Dv]
    *,
    causal: bool = True,
    window: int = 0,          # 0 = unlimited; else causal sliding window
    softcap: float = 0.0,
    q_offset: int = 0,        # absolute position of q[0] (decode/prefill)
    kv_length: Optional[jax.Array] = None,  # valid kv prefix length [B]
    sm_scale: Optional[float] = None,        # default 1/sqrt(D)
) -> jax.Array:
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    assert h % kv == 0
    group = h // kv
    if sm_scale is None:
        qf = q.astype(jnp.float32) / jnp.sqrt(d).astype(jnp.float32)
    else:
        qf = q.astype(jnp.float32) * sm_scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # expand kv heads to full heads
    kf = jnp.repeat(kf, group, axis=2)
    vf = jnp.repeat(vf, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)
    if softcap:
        scores = softcap * jnp.tanh(scores / softcap)
    qpos = q_offset + jnp.arange(sq)[:, None]          # [Sq, 1]
    kpos = jnp.arange(sk)[None, :]                     # [1, Sk]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    mask = mask[None, None]
    if kv_length is not None:
        mask = mask & (kpos[None, None] < kv_length[:, None, None, None])
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    return out.astype(q.dtype)
