"""Dispatching wrapper for attention.

``flash_attention`` picks the right implementation per platform and shape:

* ``pallas``   — the TPU kernel (kernel.py) forward under a custom VJP
                 whose backward is the blocked recompute backward of
                 flash.py (see :func:`_pallas_attention`); interpret=True
                 on CPU tests.
* ``blocked``  — pure-jnp blockwise online-softmax (lax.scan over kv
                 chunks; dynamic-sliced kv window for local attention) —
                 O(S) memory, used for long prefill on non-TPU backends
                 and as the lowering the dry-run roofline sees.
* ``ref``      — the naive oracle (ref.py), used for short sequences where
                 the O(S^2) score tensor is cheap and autodiff through it
                 is the fastest option.

All impls share the layout q [B, Sq, H, D], k/v [B, Sk, KV, D].
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_attention.flash import (
    _global_bwd,
    _local_bwd,
    flash_global,
    flash_local,
)
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_reference
from repro.sharding import spec_for

# Naive-path threshold: above this the O(S^2) score tensor dominates step
# memory (4k seq at per-device batch 16 is already ~8.6 GB f32), so the
# blockwise paths take over.  Short sequences (unit tests, decode) keep the
# naive oracle, which autodiffs fastest.
_REF_MAX_SEQ = 1024


def _blocked_global(
    q, k, v, *, causal: bool, softcap: float, q_offset: int, chunk: int
) -> jax.Array:
    """Online-softmax scan over kv chunks (no window)."""
    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    group = h // kvh
    pad = (-sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nk = k.shape[1] // chunk
    kc = k.reshape(b, nk, chunk, kvh, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nk, chunk, kvh, d).transpose(1, 0, 2, 3, 4)
    qf = q.astype(jnp.float32) / jnp.sqrt(d)
    qpos = q_offset + jnp.arange(sq)

    def step(carry, xs):
        acc, m, l = carry
        ic, kblk, vblk = xs
        kf = jnp.repeat(kblk.astype(jnp.float32), group, axis=2)
        vf = jnp.repeat(vblk.astype(jnp.float32), group, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        kpos = ic * chunk + jnp.arange(chunk)
        mask = kpos[None, :] < sk
        if causal:
            mask = mask & (kpos[None, :] <= qpos[:, None])
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vf)
        return (acc, m_new, l), None

    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (acc, _, l), _ = jax.lax.scan(
        step, (acc0, m0, l0), (jnp.arange(nk), kc, vc)
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _blocked_local(
    q, k, v, *, window: int, softcap: float, q_offset: int, block_q: int
) -> jax.Array:
    """Sliding-window attention: per q-block dynamic slice of the kv range
    [q_start - window + 1, q_start + block_q) — FLOPs O(S * window), not
    O(S^2)."""
    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    pad_q = (-sq) % block_q
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    nq = q.shape[1] // block_q
    span = window + block_q  # kv positions any query in the block can see
    # pad kv on both sides so every dynamic slice is in-bounds (the last q
    # block's span can run one block past the sequence end)
    pad_left = span
    kp = jnp.pad(k, ((0, 0), (pad_left, block_q), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (pad_left, block_q), (0, 0), (0, 0)))
    qb = q.reshape(b, nq, block_q, h, d).transpose(1, 0, 2, 3, 4)

    def per_block(iq, qblk):
        # absolute kv start of the visible span for this q block
        q_start = q_offset + iq * block_q
        kv_start = q_start - window + 1  # may be negative; padding absorbs
        start = kv_start + pad_left
        kblk = jax.lax.dynamic_slice(kp, (0, start, 0, 0), (b, span, kvh, d))
        vblk = jax.lax.dynamic_slice(vp, (0, start, 0, 0), (b, span, kvh, d))
        kpos = kv_start + jnp.arange(span)
        qpos = q_start + jnp.arange(block_q)
        valid = (kpos[None, :] >= 0) & (kpos[None, :] < sk)
        valid &= kpos[None, :] <= qpos[:, None]
        valid &= kpos[None, :] > qpos[:, None] - window
        out = _masked_naive(qblk, kblk, vblk, valid, softcap)
        return out

    outs = jax.vmap(per_block)(jnp.arange(nq), qb)  # [nq, B, bq, H, D]
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nq * block_q, h, d)
    return out[:, :sq]


def _masked_naive(q, k, v, mask, softcap):
    b, sq, h, d = q.shape
    group = h // k.shape[2]
    kf = jnp.repeat(k.astype(jnp.float32), group, axis=2)
    vf = jnp.repeat(v.astype(jnp.float32), group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) / jnp.sqrt(d), kf)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vf).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _pallas_attention(q, k, v, causal: bool, window: int, softcap: float,
                      block_q: int, block_kv: int, interpret: bool):
    """Pallas flash attention on [B, S, H, D] operands, differentiable.

    The forward is the Pallas kernel, which also returns each row's
    log-sum-exp.  The chosen backward is the blocked recompute backward
    of flash.py, run on the kernel's own residuals: ``_global_bwd`` from
    (q, k, v, out, lse), or ``_local_bwd`` from (q, k, v) when a
    sliding window is set.  It runs under the named scope
    ``flash_attention_bwd_blocked``, so a trace shows which backward
    ran.
    """
    out, _ = _pallas_fwd_impl(q, k, v, causal, window, softcap, block_q,
                              block_kv, interpret)
    return out


def _pallas_fwd_impl(q, k, v, causal, window, softcap, block_q, block_kv,
                     interpret):
    out, lse = flash_attention_pallas(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal,
        window=window,
        softcap=softcap,
        block_q=block_q,
        block_kv=block_kv,
        interpret=interpret,
    )
    return out.transpose(0, 2, 1, 3), lse


def _pallas_fwd(q, k, v, causal, window, softcap, block_q, block_kv,
                interpret):
    out, lse = _pallas_fwd_impl(q, k, v, causal, window, softcap, block_q,
                                block_kv, interpret)
    return out, (q, k, v, out, lse)


def _pallas_bwd(causal, window, softcap, block_q, block_kv, interpret, res,
                gout):
    q, k, v, out, lse = res
    with jax.named_scope("flash_attention_bwd_blocked"):
        if window:
            return _local_bwd(window, softcap, 0, block_q, (q, k, v), gout)
        b, h, sq = lse.shape
        kvh = k.shape[2]
        # [B, H, Sq] -> [B, KVH, G, Sq]: head h reads kv head h // G
        lse5 = lse.reshape(b, kvh, h // kvh, sq)
        return _global_bwd(causal, softcap, 0, block_kv,
                           (q, k, v, out, lse5), gout)


_pallas_attention.defvjp(_pallas_fwd, _pallas_bwd)


def _per_shard(fn, q, k, v):
    """``fn(q, k, v)`` on each device's own shard.

    XLA cannot partition a Mosaic kernel, so where the ambient mesh still
    has auto axes (a plain jit over several chips, as the DDP step is)
    the call runs in a shard_map over them: batch split as the 'batch'
    rule says, heads as 'heads'/'kv' say when both q and kv heads divide
    (head h then still reads kv head h // G locally), the rest
    replicated.  Inside a shard_map that made every axis manual, as the
    DeFT phases do, ``fn`` runs as is."""
    mesh = jax.sharding.get_abstract_mesh()
    auto = frozenset(a for a, t in zip(mesh.axis_names, mesh.axis_types)
                     if t != jax.sharding.AxisType.Manual)
    if not auto:
        return fn(q, k, v)
    qs = spec_for(("batch", None, "heads", None), q.shape)
    ks = spec_for(("batch", None, "kv", None), k.shape)
    heads = qs[2] if qs[2] == ks[2] else None
    spec = P(qs[0], None, heads, None)
    return jax.shard_map(fn, in_specs=(spec, spec, spec), out_specs=spec,
                         axis_names=auto, check_vma=False)(q, k, v)


def default_attention_impl(sq: int, sk: int, *, window: int = 0,
                           has_kv_length: bool = False) -> str:
    """The implementation :func:`flash_attention` picks when ``impl`` is
    not given, for this backend and shape."""
    if jax.default_backend() == "tpu" and not has_kv_length and sq > 1:
        return "pallas"
    if sk <= _REF_MAX_SEQ or sq == 1 or has_kv_length:
        return "ref"
    if window and window < sk:
        return "blocked_local"
    return "blocked"


def flash_attention(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Sk, KV, D]
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_offset: int = 0,
    kv_length: Optional[jax.Array] = None,
    impl: Optional[str] = None,
    block_q: int = 512,
    block_kv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Dispatching attention entry point used by the models."""
    sq, sk = q.shape[1], k.shape[1]
    if impl is None:
        impl = default_attention_impl(sq, sk, window=window,
                                      has_kv_length=kv_length is not None)

    if impl == "pallas":
        if q_offset != 0:
            raise ValueError(
                f"the Pallas attention path has no query offset "
                f"(q_offset={q_offset}); use impl='blocked' or 'ref'"
            )
        if window and not causal:
            raise ValueError("a sliding window on the Pallas path needs "
                             "causal=True")
        bq, bk = min(block_q, sq), min(block_kv, sk)
        return _per_shard(
            lambda q, k, v: _pallas_attention(q, k, v, causal, window,
                                              softcap, bq, bk, interpret),
            q, k, v,
        )
    if impl == "ref":
        return attention_reference(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_offset=q_offset, kv_length=kv_length,
        )
    if impl == "blocked_local":
        assert window and causal
        return flash_local(
            q, k, v, window, softcap, q_offset, min(block_q, sq)
        )
    if impl == "blocked":
        return flash_global(
            q, k, v, causal, softcap, q_offset, min(block_kv, sk)
        )
    raise ValueError(f"unknown impl {impl!r}")
