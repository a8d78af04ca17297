"""Attention mixers: GQA self-attention (full / sliding-window / softcap /
qk-norm), cross-attention, and Multi-head Latent Attention (DeepSeek-V2).

Each mixer exposes ``init_*`` (params) and ``apply_*`` (forward) plus cache
constructors for the decode path:

* full attention      — KV cache [B, S_max, KV, D], written at ``pos``.
* sliding window      — ring-buffer cache [B, window, KV, D] (O(window)
                        state: this is what makes long_500k runnable for
                        local-attention architectures).
* MLA                 — *latent* cache [B, S_max, kv_lora + rope_dim];
                        decode uses the absorbed-matmul formulation so the
                        per-step cost is O(S * (kv_lora + rope)) per head,
                        never materializing full K/V.
* cross attention     — K/V of the (static) memory computed at prefill and
                        reused every decode step.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.sharded_decode import sharded_flash_decode
from repro.models.common import (
    apply_rope,
    dense_init,
    rms_norm_per_head,
    yarn_mscale,
)
from repro.sharding import constrain
from repro.util.flags import sharded_decode_enabled


def _use_sharded_decode(cache_k: jax.Array) -> bool:
    """Opt-in distributed-softmax decode over a sequence-sharded cache."""
    if not sharded_decode_enabled():
        return False
    mesh = jax.sharding.get_abstract_mesh()
    names = getattr(mesh, "axis_names", ()) if mesh is not None else ()
    if "model" not in names:
        return False
    n = dict(mesh.shape)["model"]
    return cache_k.shape[1] % n == 0 and cache_k.shape[1] >= n


# ---------------------------------------------------------------------------
# GQA self-attention / cross-attention
# ---------------------------------------------------------------------------
def init_attention(key, cfg, dtype=jnp.float32) -> Dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    kq, kk, kv_, ko, kn = jax.random.split(key, 5)
    p = {
        "wq": dense_init(kq, d, cfg.n_heads * hd, dtype),
        "wk": dense_init(kk, d, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(kv_, d, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(ko, cfg.n_heads * hd, d, dtype),
    }
    if cfg.use_qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _project_qkv(p, xq, xkv, cfg):
    b, sq, _ = xq.shape
    sk = xkv.shape[1]
    hd = cfg.resolved_head_dim
    q = (xq @ p["wq"]).reshape(b, sq, cfg.n_heads, hd)
    k = (xkv @ p["wk"]).reshape(b, sk, cfg.n_kv_heads, hd)
    v = (xkv @ p["wv"]).reshape(b, sk, cfg.n_kv_heads, hd)
    q = constrain(q, ("batch", None, "heads", None))
    k = constrain(k, ("batch", None, "kv", None))
    v = constrain(v, ("batch", None, "kv", None))
    if cfg.use_qk_norm:
        q = rms_norm_per_head(q, p["q_norm"])
        k = rms_norm_per_head(k, p["k_norm"])
    return q, k, v


def make_kv_cache(
    cfg, batch: int, max_len: int, window: int = 0, dtype=jnp.float32,
    prefill_chunk: int = 1,
):
    """window > 0 -> ring buffer.  The ring must hold ``window +
    prefill_chunk - 1`` positions so a chunked prefill never clobbers keys
    still visible to queries in the same chunk; decode (chunk=1) needs
    exactly ``window``.  Small contexts (max_len <= that) fall back to a
    plain full cache."""
    size = min(max_len, window + prefill_chunk - 1) if window else max_len
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, size, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, size, cfg.n_kv_heads, hd), dtype),
    }


def apply_self_attention(
    p: Dict,
    x: jax.Array,                       # [B, S, d]
    *,
    cfg,
    window: int = 0,
    causal: bool = True,
    pos: int | jax.Array = 0,           # absolute position of x[:, 0]
    cache: Optional[Dict] = None,       # decode: updated in place (functionally)
    kv_length: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict]]:
    b, s, d = x.shape
    q, k, v = _project_qkv(p, x, x, cfg)
    qpos = pos + jnp.arange(s)
    q = apply_rope(q, qpos, cfg.rope_theta)
    k = apply_rope(k, qpos, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        size = cache["k"].shape[1]
        if (
            window
            and isinstance(pos, int)
            and pos + s > size                  # this call wraps the ring
            and size < window + s - 1           # ...and the ring is too small
        ):
            raise ValueError(
                f"ring cache ({size}) too small for window={window} with "
                f"chunk={s}; init it with prefill_chunk>={s}"
            )
        if window:
            # ring buffer write at pos % size
            idx = (pos + jnp.arange(s)) % size
            ck = cache["k"].at[:, idx].set(k)
            cv = cache["v"].at[:, idx].set(v)
            new_cache = {"k": ck, "v": cv}
            # linearize the ring for attention: roll so that the oldest
            # retained position comes first; compute absolute positions.
            newest = pos + s - 1
            oldest = jnp.maximum(newest - size + 1, 0)
            # absolute position stored in slot j is the largest p <= newest
            # with p % size == j
            slot = jnp.arange(size)
            slot_pos = newest - ((newest - slot) % size)
            att = _ring_attention(
                q, ck, cv, qpos, slot_pos, oldest, window,
                cfg.attn_logit_softcap,
            )
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, pos, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, pos, axis=1)
            new_cache = {"k": ck, "v": cv}
            length = kv_length if kv_length is not None else pos + s
            if s == 1 and _use_sharded_decode(ck):
                att = sharded_flash_decode(
                    q, ck, cv, length, softcap=cfg.attn_logit_softcap,
                )
            else:
                att = flash_attention(
                    q, ck, cv, causal=causal, softcap=cfg.attn_logit_softcap,
                    q_offset=pos, kv_length=jnp.broadcast_to(length, (b,)),
                    impl="ref",
                )
    else:
        att = flash_attention(
            q, k, v, causal=causal, window=window,
            softcap=cfg.attn_logit_softcap, q_offset=0,
        )
    att = constrain(att, ("batch", None, "heads", None))
    out = att.reshape(b, s, -1) @ p["wo"]
    return constrain(out, ("batch", None, "embed")), new_cache


def kv_size_needed(window: int, q_len: int) -> int:
    return window + q_len - 1


def _ring_attention(q, ck, cv, qpos, slot_pos, oldest, window, softcap_v):
    """Attention over a ring-buffer cache with absolute slot positions."""
    b, s, h, hd = q.shape
    kvh = ck.shape[2]
    group = h // kvh
    kf = jnp.repeat(ck.astype(jnp.float32), group, axis=2)
    vf = jnp.repeat(cv.astype(jnp.float32), group, axis=2)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32) / jnp.sqrt(hd), kf
    )
    if softcap_v:
        scores = softcap_v * jnp.tanh(scores / softcap_v)
    valid = (slot_pos[None, :] <= qpos[:, None]) & (slot_pos[None, :] >= oldest)
    valid &= slot_pos[None, :] > qpos[:, None] - window  # window semantics
    scores = jnp.where(valid[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Cross-attention (enc-dec decoder sublayer / VLM gated layer)
# ---------------------------------------------------------------------------
def init_cross_attention(key, cfg, dtype=jnp.float32) -> Dict:
    p = init_attention(key, cfg, dtype)
    p["gate"] = jnp.zeros((), dtype)  # VLM-style tanh gate (starts closed)
    return p


def cross_kv(p: Dict, memory: jax.Array, cfg):
    """Project the (static) memory to K/V once; reused across decode."""
    b, m, _ = memory.shape
    hd = cfg.resolved_head_dim
    k = (memory @ p["wk"]).reshape(b, m, cfg.n_kv_heads, hd)
    v = (memory @ p["wv"]).reshape(b, m, cfg.n_kv_heads, hd)
    if cfg.use_qk_norm:
        k = rms_norm_per_head(k, p["k_norm"])
    return constrain(k, ("batch", "modal", "kv", None)), constrain(
        v, ("batch", "modal", "kv", None)
    )


def apply_cross_attention(
    p: Dict,
    x: jax.Array,
    kv: Tuple[jax.Array, jax.Array],
    *,
    cfg,
    gated: bool = False,
) -> jax.Array:
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(b, s, cfg.n_heads, hd)
    q = constrain(q, ("batch", None, "heads", None))
    if cfg.use_qk_norm:
        q = rms_norm_per_head(q, p["q_norm"])
    k, v = kv
    att = flash_attention(q, k, v, causal=False, softcap=cfg.attn_logit_softcap)
    out = att.reshape(b, s, -1) @ p["wo"]
    if gated:
        out = jnp.tanh(p["gate"].astype(jnp.float32)).astype(out.dtype) * out
    return constrain(out, ("batch", None, "embed"))


# ---------------------------------------------------------------------------
# Multi-head Latent Attention (DeepSeek-V2)
# ---------------------------------------------------------------------------
def init_mla(key, cfg, dtype=jnp.float32) -> Dict:
    """Latent attention's weights; without a q LoRA (``q_lora_rank``
    None) q is one projection ``wq`` [d, H x (nope + rope)]."""
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    keys = jax.random.split(key, 8)
    if m.q_lora_rank is None:
        q = {"wq": dense_init(keys[0], d, h * qk_head, dtype)}
    else:
        q = {
            "wdq": dense_init(keys[0], d, m.q_lora_rank, dtype),
            "q_norm": jnp.zeros((m.q_lora_rank,), dtype),
            "wuq": dense_init(keys[1], m.q_lora_rank, h * qk_head, dtype),
        }
    return {
        **q,
        "wdkv": dense_init(keys[2], d, m.kv_lora_rank + m.qk_rope_head_dim, dtype),
        "kv_norm": jnp.zeros((m.kv_lora_rank,), dtype),
        "wuk": dense_init(keys[3], m.kv_lora_rank, h * m.qk_nope_head_dim, dtype),
        "wuv": dense_init(keys[4], m.kv_lora_rank, h * m.v_head_dim, dtype),
        "wo": dense_init(keys[5], h * m.v_head_dim, d, dtype),
    }


def mla_softmax_scale(cfg) -> float:
    """(nope + rope)^-1/2, times mscale(factor, mscale_all_dim)^2 under
    YaRN (HF ``DeepseekV2Attention.softmax_scale``)."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def make_mla_cache(cfg, batch: int, max_len: int, dtype=jnp.float32):
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
    }


def _mla_q(p, x, cfg, qpos):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    if m.q_lora_rank is None:
        q = x @ p["wq"]
    else:
        q = rms_norm_per_head(x @ p["wdq"], p["q_norm"]) @ p["wuq"]
    q = q.reshape(b, s, h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q = constrain(q, ("batch", None, "heads", None))
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim :]
    q_rope = apply_rope(q_rope, qpos, cfg.rope_theta, cfg.rope_scaling)
    return q_nope, q_rope


def _mla_latent(p, x, cfg, kpos):
    m = cfg.mla
    dkv = x @ p["wdkv"]
    ckv = rms_norm_per_head(dkv[..., : m.kv_lora_rank], p["kv_norm"])
    k_rope = dkv[..., m.kv_lora_rank :]
    # shared-across-heads rope key: add a singleton head dim for rotation
    k_rope = apply_rope(k_rope[:, :, None, :], kpos, cfg.rope_theta,
                        cfg.rope_scaling)[:, :, 0, :]
    return ckv, k_rope


def apply_mla(
    p: Dict,
    x: jax.Array,
    *,
    cfg,
    pos: int | jax.Array = 0,
    cache: Optional[Dict] = None,
    kv_length: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict]]:
    """Train/prefill: expanded K/V. Decode (cache given): absorbed matmuls
    against the latent cache.  The latent projections and the rotation
    run under the named scope ``mla``."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    qpos = pos + jnp.arange(s)
    sm_scale = mla_softmax_scale(cfg)
    with jax.named_scope("mla"):
        q_nope, q_rope = _mla_q(p, x, cfg, qpos)
        ckv, k_rope = _mla_latent(p, x, cfg, qpos)

    if cache is None:
        # expanded path
        with jax.named_scope("mla"):
            k_nope = (ckv @ p["wuk"]).reshape(b, s, h, m.qk_nope_head_dim)
            vv = (ckv @ p["wuv"]).reshape(b, s, h, m.v_head_dim)
            k_full = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                          (b, s, h, m.qk_rope_head_dim))],
                axis=-1,
            )
            q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        att = flash_attention(q_full, k_full, vv, causal=True,
                              sm_scale=sm_scale)
        out = att.reshape(b, s, -1) @ p["wo"]
        return constrain(out, ("batch", None, "embed")), None

    # absorbed decode path
    cckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv, pos, axis=1)
    ckrope = jax.lax.dynamic_update_slice_in_dim(cache["krope"], k_rope, pos, axis=1)
    new_cache = {"ckv": cckv, "krope": ckrope}
    length = kv_length if kv_length is not None else pos + s
    smax = cckv.shape[1]
    # absorb W_uk into q: q_lat [b, s, h, kv_lora]
    wuk = p["wuk"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bshd,lhd->bshl", q_nope, wuk)
    scores = (
        jnp.einsum("bshl,bkl->bhsk", q_lat.astype(jnp.float32), cckv.astype(jnp.float32))
        + jnp.einsum("bshd,bkd->bhsk", q_rope.astype(jnp.float32), ckrope.astype(jnp.float32))
    ) * sm_scale
    kpos_all = jnp.arange(smax)
    valid = (kpos_all[None, :] <= qpos[:, None]) & (kpos_all[None, :] < length)
    scores = jnp.where(valid[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out_lat = jnp.einsum("bhsk,bkl->bshl", probs, cckv.astype(jnp.float32))
    wuv = p["wuv"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    att = jnp.einsum("bshl,lhd->bshd", out_lat, wuv).astype(x.dtype)
    out = att.reshape(b, s, -1) @ p["wo"]
    return constrain(out, ("batch", None, "embed")), new_cache
