"""Decoder/encoder block assembly per LayerSpec.

A block = (norm -> sequence mixer -> residual) -> (norm -> FFN -> residual),
with gemma2-style post-norms when cfg.post_block_norm.  Variants:

* attn / local_attn — GQA self-attention (window for local).
* mla               — DeepSeek-V2 latent attention.
* cross_attn        —
    - enc-dec decoder (seamless): self-attn + cross-attn + FFN sublayers;
    - VLM (llama-3.2-vision): standalone *gated* cross-attention block.
* rglru             — Griffin recurrent block.
* rwkv              — RWKV-6 time-mix; its FFN sublayer is the RWKV
                      channel-mix (token-shifted squared-relu MLP).

``apply_block`` threads an optional per-block cache (decode) and returns
the MoE auxiliary loss (0 for dense) and the expert layer's counters
({} for dense; ``models/moe.py``).  All functions are shape-polymorphic
over batch/sequence and contain no Python-level branching on traced
values, so the same code lowers for train, prefill and decode.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec
from repro.models import attention as attn
from repro.models import recurrent as rec
from repro.models.common import apply_norm, init_ffn, init_norm, apply_ffn
from repro.models.moe import apply_moe, init_moe


def init_block(
    key, cfg, spec: LayerSpec, *, dense_ffn_width: Optional[int] = None,
    dtype=jnp.float32,
) -> Dict:
    kmix, kffn, kx = jax.random.split(key, 3)
    p: Dict = {"pre_norm": init_norm(cfg.norm, cfg.d_model, dtype)}
    if cfg.post_block_norm:
        p["post_mixer_norm"] = init_norm(cfg.norm, cfg.d_model, dtype)
        p["post_ffn_norm"] = init_norm(cfg.norm, cfg.d_model, dtype)

    if spec.kind in ("attn", "local_attn"):
        p["mixer"] = attn.init_attention(kmix, cfg, dtype)
    elif spec.kind == "mla":
        p["mixer"] = attn.init_mla(kmix, cfg, dtype)
    elif spec.kind == "cross_attn":
        if cfg.is_encoder_decoder:
            k1, k2 = jax.random.split(kmix)
            p["mixer"] = attn.init_attention(k1, cfg, dtype)       # self
            p["cross"] = attn.init_cross_attention(k2, cfg, dtype)
            p["cross_norm"] = init_norm(cfg.norm, cfg.d_model, dtype)
        else:  # VLM gated cross block
            p["mixer"] = attn.init_cross_attention(kmix, cfg, dtype)
    elif spec.kind == "rglru":
        p["mixer"] = rec.init_rglru_block(kmix, cfg, dtype)
    elif spec.kind == "rwkv":
        p["mixer"] = rec.init_rwkv_timemix(kmix, cfg, dtype)
    else:
        raise ValueError(spec.kind)

    p["ffn_norm"] = init_norm(cfg.norm, cfg.d_model, dtype)
    if spec.kind == "rwkv":
        p["ffn"] = rec.init_rwkv_channelmix(kffn, cfg, dtype)
    elif spec.ffn == "moe":
        p["ffn"] = init_moe(kffn, cfg, dtype)
    else:
        p["ffn"] = init_ffn(kffn, cfg, d_ff=dense_ffn_width, dtype=dtype)
    return p


def init_block_cache(
    cfg, spec: LayerSpec, batch: int, max_len: int, *, dtype=jnp.float32,
    prefill_chunk: int = 1,
) -> Dict:
    c: Dict = {}
    if spec.kind == "attn":
        c["kv"] = attn.make_kv_cache(cfg, batch, max_len, dtype=dtype)
    elif spec.kind == "local_attn":
        c["kv"] = attn.make_kv_cache(
            cfg, batch, max_len, window=cfg.sliding_window, dtype=dtype,
            prefill_chunk=prefill_chunk,
        )
    elif spec.kind == "mla":
        c["kv"] = attn.make_mla_cache(cfg, batch, max_len, dtype=dtype)
    elif spec.kind == "cross_attn":
        if cfg.is_encoder_decoder:
            c["kv"] = attn.make_kv_cache(cfg, batch, max_len, dtype=dtype)
        m = max(cfg.n_modal_tokens, 1)
        hd = cfg.resolved_head_dim
        c["cross_k"] = jnp.zeros((batch, m, cfg.n_kv_heads, hd), dtype)
        c["cross_v"] = jnp.zeros((batch, m, cfg.n_kv_heads, hd), dtype)
    elif spec.kind == "rglru":
        c["state"] = rec.make_rglru_state(cfg, batch, dtype=dtype)
    elif spec.kind == "rwkv":
        c["state"] = rec.make_rwkv_state(cfg, batch, dtype=dtype)
    return c


def apply_block(
    p: Dict,
    x: jax.Array,
    *,
    cfg,
    spec: LayerSpec,
    pos: int | jax.Array = 0,
    cache: Optional[Dict] = None,
    memory: Optional[jax.Array] = None,   # cross-attn memory [B, M, d]
    fill_cross_cache: bool = False,       # prefill: project+store memory kv
    causal: bool = True,
    kv_length: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict], jax.Array, Dict[str, jax.Array]]:
    new_cache: Dict = dict(cache) if cache is not None else None
    aux = jnp.zeros((), jnp.float32)
    stats: Dict[str, jax.Array] = {}

    def norm(name, h):
        return apply_norm(p[name], h, cfg.norm)

    h = norm("pre_norm", x)
    if spec.kind in ("attn", "local_attn"):
        window = cfg.sliding_window if spec.kind == "local_attn" else 0
        out, kvc = attn.apply_self_attention(
            p["mixer"], h, cfg=cfg, window=window, causal=causal, pos=pos,
            cache=cache.get("kv") if cache else None, kv_length=kv_length,
        )
        if kvc is not None:
            new_cache["kv"] = kvc
    elif spec.kind == "mla":
        out, kvc = attn.apply_mla(
            p["mixer"], h, cfg=cfg, pos=pos,
            cache=cache.get("kv") if cache else None, kv_length=kv_length,
        )
        if kvc is not None:
            new_cache["kv"] = kvc
    elif spec.kind == "cross_attn" and cfg.is_encoder_decoder:
        out, kvc = attn.apply_self_attention(
            p["mixer"], h, cfg=cfg, causal=causal, pos=pos,
            cache=cache.get("kv") if cache else None, kv_length=kv_length,
        )
        if kvc is not None:
            new_cache["kv"] = kvc
    elif spec.kind == "cross_attn":  # VLM gated cross block
        kv = _resolve_cross_kv(p["mixer"], cache, new_cache, memory, cfg,
                               fill_cross_cache)
        out = attn.apply_cross_attention(p["mixer"], h, kv, cfg=cfg, gated=True)
    elif spec.kind == "rglru":
        out, st = rec.apply_rglru(
            p["mixer"], h, cfg=cfg, state=cache.get("state") if cache else None
        )
        if st is not None:
            new_cache["state"] = st
    elif spec.kind == "rwkv":
        out, st = rec.apply_rwkv_timemix(
            p["mixer"], h, cfg=cfg, state=cache.get("state") if cache else None
        )
        if st is not None:
            new_cache["state"] = st
    else:
        raise ValueError(spec.kind)

    if cfg.post_block_norm:
        out = norm("post_mixer_norm", out)
    x = x + out

    # enc-dec cross-attention sublayer
    if spec.kind == "cross_attn" and cfg.is_encoder_decoder:
        h = norm("cross_norm", x)
        kv = _resolve_cross_kv(p["cross"], cache, new_cache, memory, cfg,
                               fill_cross_cache)
        x = x + attn.apply_cross_attention(p["cross"], h, kv, cfg=cfg)

    # FFN sublayer
    h = norm("ffn_norm", x)
    if spec.kind == "rwkv":
        out, st = rec.apply_rwkv_channelmix(
            p["ffn"], h, state=new_cache.get("state") if cache else None
        )
        if st is not None:
            new_cache["state"] = st
    elif spec.ffn == "moe":
        out, aux, stats = apply_moe(p["ffn"], h, cfg=cfg)
    else:
        out = apply_ffn(p["ffn"], h, cfg)
    if cfg.post_block_norm:
        out = norm("post_ffn_norm", out)
    x = x + out
    return x, new_cache, aux, stats


def _resolve_cross_kv(mixer_p, cache, new_cache, memory, cfg, fill):
    """Cross-attention K/V: from memory at train/prefill; cached at decode."""
    if memory is not None:
        kv = attn.cross_kv(mixer_p, memory, cfg)
        if cache is not None and fill:
            new_cache["cross_k"], new_cache["cross_v"] = kv
        return kv
    assert cache is not None, "cross-attn needs memory or a filled cache"
    return cache["cross_k"], cache["cross_v"]
