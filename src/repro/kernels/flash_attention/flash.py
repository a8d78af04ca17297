"""Memory-efficient blockwise attention with a flash-style custom VJP.

This is the pure-jnp twin of the Pallas TPU kernel (kernel.py): identical
blocking structure, identical recompute-based backward.  It exists because

* the multi-pod dry-run lowers on the CPU backend, where a ``pallas_call``
  cannot lower non-interpreted — the roofline must see the blockwise
  compute/memory profile, not an O(S^2) naive softmax;
* plain autodiff through a blockwise online-softmax scan saves the per-
  chunk probability matrices as VJP residuals — O(S^2) memory again.  The
  custom VJP stores only (q, k, v, out, lse) = O(S·d) and recomputes
  scores per chunk in the backward pass, exactly like flash attention.

Two variants:

``flash_global``  one kv-chunk scan over the whole sequence (causal or
                  bidirectional; optional logit softcap).  Causal masking
                  is applied per chunk; masked chunks still compute
                  (static shapes), so causal FLOPs are ~2x the ideal —
                  the TPU kernel skips them via its grid, noted in the
                  roofline analysis.
``flash_local``   sliding-window: a scan over q blocks, each attending to
                  a statically-sized kv span (window + block) via dynamic
                  slice — FLOPs O(S * window), which is what makes 32k+
                  prefill with a 2-4k window tractable.

GQA is handled by folding the q heads into (kv_head, group).  v may be
narrower than q/k (latent attention); the softmax scale is ``sm_scale``,
1/sqrt(D) of the q/k heads unless given.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.util.flags import scan_unroll_enabled

_NEG = -1e30


def _fold_gqa(q: jax.Array, kvh: int) -> jax.Array:
    """[B, Sq, H, D] -> [B, Sq, KVH, G, D]."""
    b, sq, h, d = q.shape
    return q.reshape(b, sq, kvh, h // kvh, d)


def _scaled_q(q5, d: int, sm_scale):
    """q in f32 times the softmax scale (1/sqrt(d) unless given)."""
    q5 = q5.astype(jnp.float32)
    return q5 / jnp.sqrt(d) if sm_scale is None else q5 * sm_scale


def _bwd_scale(d: int, sm_scale):
    return 1.0 / jnp.sqrt(d) if sm_scale is None else sm_scale


def _chunk_mask(qpos, kpos, *, causal: bool, window: int, sk: int):
    m = kpos[None, :] < sk
    if causal:
        m = m & (kpos[None, :] <= qpos[:, None])
    if window:
        m = m & (kpos[None, :] > qpos[:, None] - window)
    return m  # [Sq, C]


def _scores(q5f, kf, softcap: float):
    """q5f [B,Sq,KVH,G,D] (pre-scaled), kf [B,C,KVH,D] -> s [B,KVH,G,Sq,C]
    (+ tanh(s_raw/cap) when softcapped, for the backward chain rule)."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q5f, kf)
    if softcap:
        t = jnp.tanh(s / softcap)
        return softcap * t, t
    return s, None


# ---------------------------------------------------------------------------
# Global (full / causal) attention
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_global(q, k, v, causal: bool, softcap: float, q_offset: int,
                 chunk: int, sm_scale=None):
    out, _ = _global_fwd_impl(q, k, v, causal, softcap, q_offset, chunk,
                              sm_scale)
    return out


def _global_fwd_impl(q, k, v, causal, softcap, q_offset, chunk, sm_scale):
    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    dv = v.shape[-1]                   # MLA: d_qk (192) != d_v (128)
    chunk = min(chunk, sk)
    pad = (-sk) % chunk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nk = kp.shape[1] // chunk
    kc = kp.reshape(b, nk, chunk, kvh, d).transpose(1, 0, 2, 3, 4)
    vc = vp.reshape(b, nk, chunk, kvh, dv).transpose(1, 0, 2, 3, 4)
    q5f = _scaled_q(_fold_gqa(q, kvh), d, sm_scale)
    qpos = q_offset + jnp.arange(sq)

    def step(carry, xs):
        acc, m, l = carry
        ic, kblk, vblk = xs
        kf = kblk.astype(jnp.float32)
        vf = vblk.astype(jnp.float32)
        s, _ = _scores(q5f, kf, softcap)
        kpos = ic * chunk + jnp.arange(chunk)
        mask = _chunk_mask(qpos, kpos, causal=causal, window=0, sk=sk)
        s = jnp.where(mask[None, None, None], s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, vf)
        return (acc, m_new, l), None

    g = h // kvh
    acc0 = jnp.zeros((b, kvh, g, sq, dv), jnp.float32)
    m0 = jnp.full((b, kvh, g, sq), _NEG, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), (jnp.arange(nk), kc, vc),
                                  unroll=scan_unroll_enabled())
    l_safe = jnp.maximum(l, 1e-30)
    out5 = acc / l_safe[..., None]                       # [B,KVH,G,Sq,Dv]
    out = out5.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dv).astype(q.dtype)
    lse = m + jnp.log(l_safe)                            # [B,KVH,G,Sq]
    return out, lse


def _global_fwd(q, k, v, causal, softcap, q_offset, chunk, sm_scale):
    out, lse = _global_fwd_impl(q, k, v, causal, softcap, q_offset, chunk,
                                sm_scale)
    return out, (q, k, v, out, lse)


def _global_bwd(causal, softcap, q_offset, chunk, sm_scale, res, gout):
    q, k, v, out, lse = res
    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    dv = v.shape[-1]
    g = h // kvh
    chunk = min(chunk, sk)
    pad = (-sk) % chunk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nk = kp.shape[1] // chunk
    kc = kp.reshape(b, nk, chunk, kvh, d).transpose(1, 0, 2, 3, 4)
    vc = vp.reshape(b, nk, chunk, kvh, dv).transpose(1, 0, 2, 3, 4)

    scale = _bwd_scale(d, sm_scale)
    q5f = _fold_gqa(q, kvh).astype(jnp.float32) * scale
    g5 = _fold_gqa(gout, kvh).astype(jnp.float32)        # [B,Sq,KVH,G,D]
    o5 = _fold_gqa(out, kvh).astype(jnp.float32)
    # D_i = sum_d g_i * o_i  (the softmax-grad diagonal term)
    delta = jnp.einsum("bqhgd,bqhgd->bhgq", g5, o5)
    qpos = q_offset + jnp.arange(sq)

    def step(dq, xs):
        ic, kblk, vblk = xs
        kf = kblk.astype(jnp.float32)
        vf = vblk.astype(jnp.float32)
        s, t = _scores(q5f, kf, softcap)
        kpos = ic * chunk + jnp.arange(chunk)
        mask = _chunk_mask(qpos, kpos, causal=causal, window=0, sk=sk)
        s = jnp.where(mask[None, None, None], s, _NEG)
        p = jnp.exp(s - lse[..., None])                  # [B,KVH,G,Sq,C]
        dv_c = jnp.einsum("bhgqk,bqhgd->bkhd", p, g5)
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", g5, vf)
        ds = p * (dp - delta[..., None])
        if softcap:
            ds = ds * (1.0 - t * t)
        ds = jnp.where(mask[None, None, None], ds, 0.0)
        dq = dq + jnp.einsum("bhgqk,bkhd->bqhgd", ds, kf) * scale
        dk_c = jnp.einsum("bhgqk,bqhgd->bkhd", ds, q5f)
        return dq, (dk_c, dv_c)

    dq0 = jnp.zeros((b, sq, kvh, g, d), jnp.float32)
    dq5, (dkc, dvc) = jax.lax.scan(step, dq0, (jnp.arange(nk), kc, vc),
                                   unroll=scan_unroll_enabled())
    dq = dq5.reshape(b, sq, h, d).astype(q.dtype)
    dk = dkc.transpose(1, 0, 2, 3, 4).reshape(b, nk * chunk, kvh, d)[:, :sk]
    dv_ = dvc.transpose(1, 0, 2, 3, 4).reshape(b, nk * chunk, kvh, dv)[:, :sk]
    return dq, dk.astype(k.dtype), dv_.astype(v.dtype)


flash_global.defvjp(_global_fwd, _global_bwd)


# ---------------------------------------------------------------------------
# Sliding-window attention (q-block outer loop, static kv span)
# ---------------------------------------------------------------------------
def _local_geometry(q, k, window: int, block_q: int):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    pad_q = (-sq) % block_q
    nq = (sq + pad_q) // block_q
    span = window + block_q
    return b, sq, h, d, sk, block_q, pad_q, nq, span


def _local_block(q5f, kblk, vblk, qpos, kpos, softcap, sk, window):
    """Exact softmax over one q block's visible span.  Returns out5, p, t
    (p/t reused by the backward)."""
    kf = kblk.astype(jnp.float32)
    vf = vblk.astype(jnp.float32)
    s, t = _scores(q5f, kf, softcap)
    mask = (kpos[None, :] >= 0) & _chunk_mask(
        qpos, kpos, causal=True, window=window, sk=sk
    )
    s = jnp.where(mask[None, None, None], s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out5 = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
    return out5, p, t, mask


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_local(q, k, v, window: int, softcap: float, q_offset: int,
                block_q: int, sm_scale=None):
    out, _ = _local_fwd_impl(q, k, v, window, softcap, q_offset, block_q,
                             sm_scale)
    return out


def _pad_kv(k, span, block_q):
    return jnp.pad(k, ((0, 0), (span, block_q), (0, 0), (0, 0)))


def _local_fwd_impl(q, k, v, window, softcap, q_offset, block_q, sm_scale):
    b, sq, h, d, sk, block_q, pad_q, nq, span = _local_geometry(
        q, k, window, block_q
    )
    kvh = k.shape[2]
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    q5 = _scaled_q(_fold_gqa(qp, kvh), d, sm_scale)
    qb = q5.reshape(b, nq, block_q, kvh, h // kvh, d).transpose(1, 0, 2, 3, 4, 5)
    kp = _pad_kv(k, span, block_q)
    vp = _pad_kv(v, span, block_q)

    def step(_, xs):
        iq, qblk = xs
        q_start = q_offset + iq * block_q
        kv_start = q_start - window + 1
        start = kv_start + span
        kblk = jax.lax.dynamic_slice(kp, (0, start, 0, 0), (b, span, kvh, d))
        vblk = jax.lax.dynamic_slice(vp, (0, start, 0, 0), (b, span, kvh, d))
        kpos = kv_start + jnp.arange(span)
        qpos = q_start + jnp.arange(block_q)
        out5, _, _, _ = _local_block(qblk, kblk, vblk, qpos, kpos, softcap,
                                     sk, window)
        return None, out5

    _, outs = jax.lax.scan(step, None, (jnp.arange(nq), qb),
                           unroll=scan_unroll_enabled())
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * block_q, h, d)
    return out[:, :sq].astype(q.dtype), None


def _local_fwd(q, k, v, window, softcap, q_offset, block_q, sm_scale):
    out, _ = _local_fwd_impl(q, k, v, window, softcap, q_offset, block_q,
                             sm_scale)
    return out, (q, k, v)


def _local_bwd(window, softcap, q_offset, block_q, sm_scale, res, gout):
    q, k, v = res
    b, sq, h, d, sk, block_q, pad_q, nq, span = _local_geometry(
        q, k, window, block_q
    )
    kvh = k.shape[2]
    g = h // kvh
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    gp = jnp.pad(gout, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    scale = _bwd_scale(d, sm_scale)
    q5 = _fold_gqa(qp, kvh).astype(jnp.float32) * scale
    g5 = _fold_gqa(gp, kvh).astype(jnp.float32)
    qb = q5.reshape(b, nq, block_q, kvh, g, d).transpose(1, 0, 2, 3, 4, 5)
    gb = g5.reshape(b, nq, block_q, kvh, g, d).transpose(1, 0, 2, 3, 4, 5)
    kp = _pad_kv(k, span, block_q)
    vp = _pad_kv(v, span, block_q)
    dkp0 = jnp.zeros(kp.shape, jnp.float32)
    dvp0 = jnp.zeros(vp.shape, jnp.float32)

    def step(carry, xs):
        dkp, dvp = carry
        iq, qblk, gblk = xs
        q_start = q_offset + iq * block_q
        kv_start = q_start - window + 1
        start = kv_start + span
        kblk = jax.lax.dynamic_slice(kp, (0, start, 0, 0), (b, span, kvh, d))
        vblk = jax.lax.dynamic_slice(vp, (0, start, 0, 0), (b, span, kvh, d))
        kpos = kv_start + jnp.arange(span)
        qpos = q_start + jnp.arange(block_q)
        out5, p, t, mask = _local_block(qblk, kblk, vblk, qpos, kpos, softcap,
                                        sk, window)
        vf = vblk.astype(jnp.float32)
        kf = kblk.astype(jnp.float32)
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", gblk, vf)
        delta = jnp.einsum("bqhgd,bqhgd->bhgq", gblk, out5)
        ds = p * (dp - delta[..., None])
        if softcap:
            ds = ds * (1.0 - t * t)
        ds = jnp.where(mask[None, None, None], ds, 0.0)
        dq_blk = jnp.einsum("bhgqk,bkhd->bqhgd", ds, kf) * scale
        dk_blk = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qblk)
        dv_blk = jnp.einsum("bhgqk,bqhgd->bkhd", p, gblk)
        dk_old = jax.lax.dynamic_slice(dkp, (0, start, 0, 0), (b, span, kvh, d))
        dv_old = jax.lax.dynamic_slice(dvp, (0, start, 0, 0), (b, span, kvh, d))
        dkp = jax.lax.dynamic_update_slice(dkp, dk_old + dk_blk, (0, start, 0, 0))
        dvp = jax.lax.dynamic_update_slice(dvp, dv_old + dv_blk, (0, start, 0, 0))
        return (dkp, dvp), dq_blk

    (dkp, dvp), dqb = jax.lax.scan(
        step, (dkp0, dvp0), (jnp.arange(nq), qb, gb),
        unroll=scan_unroll_enabled(),
    )
    dq = dqb.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * block_q, h, d)[:, :sq]
    dk = dkp[:, span : span + sk]
    dv = dvp[:, span : span + sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_local.defvjp(_local_fwd, _local_bwd)
