"""Pallas TPU flash attention (blockwise online-softmax).

TPU-native adaptation: q/k/v tiles are staged HBM->VMEM by BlockSpec, the
score matmul hits the MXU with 128-aligned tiles, and the online-softmax
running state (m, l, acc) lives in VMEM scratch carried across the
innermost (kv) grid dimension.  Causal and sliding-window blocks that are
fully masked are skipped with ``pl.when`` — the skip is structural (the
MXU work is never issued), which is what makes local attention
sub-quadratic on the long_500k path.

Layout: q/k [B, H|KV, S, D], v [B, KV, Sk, Dv] (latent attention's v
heads are narrower than its q/k heads); GQA is handled in the BlockSpec
index_map (head h reads kv head h // (H // KV)).  The softmax scale is
``sm_scale``, 1/sqrt(D) unless given.  Besides the
output the kernel writes each row's log-sum-exp [B, H, Sq], the one
residual the backward pass (ops.py) needs to recompute probabilities.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref,  # VMEM tiles
    acc_ref, m_ref, l_ref,       # scratch
    *,
    causal: bool,
    window: int,
    softcap: float,
    block_q: int,
    block_kv: int,
    sm_scale: float,
    num_kv_blocks: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_kv

    # Structural skip: block entirely above the diagonal (causal) or
    # entirely left of the window.
    needed = True
    if causal:
        needed = k_start <= q_start + block_q - 1
    if window:
        needed = jnp.logical_and(
            needed, k_start + block_kv - 1 > q_start - window
        )

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale          # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)                     # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                        # [bq, bk]
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = jnp.ones((block_q, block_kv), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                      # [bq]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, ...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[...] = (m_ref[...] + jnp.log(l))[None, :]


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "softcap", "block_q", "block_kv", "interpret",
        "sm_scale",
    ),
)
def flash_attention_pallas(
    q: jax.Array,  # [B, H, Sq, D]
    k: jax.Array,  # [B, KV, Sk, D]
    v: jax.Array,  # [B, KV, Sk, Dv]
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
    sm_scale: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns ``(out [B, H, Sq, Dv], lse [B, H, Sq] f32)``."""
    b, h, sq, d = q.shape
    _, kvh, sk, _ = k.shape
    dv = v.shape[-1]
    assert h % kvh == 0
    group = h // kvh
    block_q = min(block_q, sq)
    block_kv = min(block_kv, sk)
    assert sq % block_q == 0 and sk % block_kv == 0, (sq, block_q, sk, block_kv)
    nq, nk = sq // block_q, sk // block_kv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _attn_kernel,
        causal=causal,
        window=window,
        softcap=softcap,
        block_q=block_q,
        block_kv=block_kv,
        sm_scale=sm_scale,
        num_kv_blocks=nk,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec(
                (1, 1, block_kv, d),
                lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_kv, dv),
                lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, block_q, dv), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
            ),
            # a unit sublane dim keeps the block a legal (1, 128k) tile
            pl.BlockSpec(
                (None, None, 1, block_q),
                lambda ib, ih, iq, ik: (ib, ih, 0, iq),
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return out, lse[:, :, 0]
