"""A tests-only architecture module (the contract is in
``bench/archs/__init__.py``), named by ``plugin-mla-moe.json`` alone.

Its model is a toy of latent attention and sparse experts over the
program's own parameter tree for a tiny MLA + MoE configuration: a
leading dense layer (``prefix``), stacked expert layers with routed and
shared experts, an untied head (``head/w``) and one-dimensional norm
weights that the dense rules do not know (``q_norm``, ``kv_norm`` of
the latent projections).  The loss is not a reference of the program's
layers (no rotary embedding, every expert computed for every token) and
nothing compares the two: the module shows that the harness takes an
architecture by a configuration file and a module, with no shared file
changed.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from bench import reference

HIGHEST = jax.lax.Precision.HIGHEST
NORMS = ("scale", "q_norm", "kv_norm")
MATRICES = ("wdq", "wuq", "wdkv", "wuk", "wuv", "wo", "router", "gate", "up",
            "down", "w", "table")


def program_config(c: Dict[str, Any]):
    from repro.configs.base import ArchConfig, LayerSpec, MLAConfig, MoEConfig

    return ArchConfig(
        name="plugin-mla-moe", family="moe", citation="tests only",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_attention_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], layer_pattern=(LayerSpec("mla", "moe"),),
        mla=MLAConfig(kv_lora_rank=c["kv_lora_rank"],
                      q_lora_rank=c["q_lora_rank"],
                      qk_nope_head_dim=c["qk_nope_head_dim"],
                      qk_rope_head_dim=c["qk_rope_head_dim"],
                      v_head_dim=c["v_head_dim"]),
        moe=MoEConfig(n_experts=c["n_routed_experts"],
                      experts_per_token=c["num_experts_per_tok"],
                      n_shared_experts=c["n_shared_experts"],
                      d_expert=c["moe_intermediate_size"],
                      first_k_dense=c["first_k_dense_replace"],
                      router_aux_coef=c["aux_loss_alpha"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), norm="rmsnorm",
        ffn_activation="silu")


def leaf_kind(names: tuple, ndim: int, c: Dict[str, Any]) -> str:
    last = names[-1]
    if last == "table":
        return "embed"
    if last in NORMS:
        return "rms_scale"
    if ndim >= 2:
        return "matrix"
    raise ValueError(f"unknown parameter {'/'.join(names)}")


def tiny(c: Dict[str, Any], seq: int) -> Dict[str, Any]:
    return {**c, "hidden_size": 64, "vocab_size": 256}


def matrices(c: Dict[str, Any]):
    return MATRICES


def _rms(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * (1.0 + scale)


def _latent_attention(p, h, c, ein):
    s, nh = h.shape[0], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    eps, r = c["rms_norm_eps"], c["kv_lora_rank"]
    q = _rms(ein("sd,dr->sr", h, p["wdq"], True), p["q_norm"], eps)
    q = ein("sr,re->se", q, p["wuq"], True).reshape(s, nh, dn + dr)
    kv = ein("sd,de->se", h, p["wdkv"], True)
    ckv, kr = _rms(kv[:, :r], p["kv_norm"], eps), kv[:, r:]
    kn = ein("sr,re->se", ckv, p["wuk"], True).reshape(s, nh, dn)
    v = ein("sr,re->se", ckv, p["wuv"], True).reshape(s, nh, dv)
    k = jnp.concatenate([kn, jnp.broadcast_to(kr[:, None], (s, nh, dr))], -1)
    sc = ein("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(dn + dr))
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = ein("hqk,khd->qhd", jax.nn.softmax(sc, -1), v).reshape(s, nh * dv)
    return ein("se,ed->sd", o, p["wo"], True)


def _mlp(p, h, ein):
    a = jax.nn.silu(ein("sd,df->sf", h, p["gate"], True))
    return ein("sf,fd->sd", a * ein("sd,df->sf", h, p["up"], True),
               p["down"], True)


def _experts(p, h, c, ein):
    """Top-k of the router's softmax over every expert, and a switch-style
    load-balance loss."""
    e, k = c["n_routed_experts"], c["num_experts_per_tok"]
    probs = jax.nn.softmax(ein("sd,de->se", h, p["router"], True), -1)
    _, idx = jax.lax.top_k(probs, k)
    chosen = jax.nn.one_hot(idx, e).sum(1)
    ex = p["experts"]
    a = jax.nn.silu(ein("sd,edf->sef", h, ex["gate"], True))
    a = a * ein("sd,edf->sef", h, ex["up"], True)
    y = ein("sef,efd->sed", a, ex["down"], True)
    out = jnp.einsum("se,sed->sd", chosen * probs, y, precision=HIGHEST)
    out = out + _mlp(p["shared"], h, ein)
    aux = e * jnp.sum(jnp.mean(chosen / k, 0) * jnp.mean(probs, 0))
    return out, aux


def _layers(params) -> List[Dict[str, Any]]:
    out = list(params["prefix"])
    for stacked in params["stack"]:
        n = jax.tree.leaves(stacked)[0].shape[0]
        out += [jax.tree.map(lambda x, i=i: x[i], stacked) for i in range(n)]
    return out + list(params["tail"])


def row_loss(params, tokens, c: Dict[str, Any], precision: str, block: int,
             positions: Optional[int]):
    """Mean next-token cross entropy plus the router's load-balance loss;
    at a toy's sizes every query and position is computed at once."""
    ein = reference.einsum_for(precision)
    eps = c["rms_norm_eps"]
    x = params["embed"]["table"][tokens]
    if precision == "fp8":
        params = reference.round_weights(params, matrices(c))
    aux = 0.0
    for p in _layers(params):
        x = x + _latent_attention(p["mixer"], _rms(
            x, p["pre_norm"]["scale"], eps), c, ein)
        h = _rms(x, p["ffn_norm"]["scale"], eps)
        if "router" in p["ffn"]:
            y, a = _experts(p["ffn"], h, c, ein)
            aux = aux + a
        else:
            y = _mlp(p["ffn"], h, ein)
        x = x + y
    x = _rms(x, params["final_norm"]["scale"], eps)
    n = x.shape[0] - 1 if positions is None else positions
    logits = ein("sd,dv->sv", x[:n], params["head"]["w"], True)
    gold = jnp.take_along_axis(logits, tokens[1:n + 1, None], -1)[:, 0]
    ce = jnp.mean(jax.nn.logsumexp(logits, -1) - gold)
    return ce + c["aux_loss_alpha"] * aux


def _mla_params(c: Dict[str, Any]) -> Dict[str, int]:
    d, nh = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    ql, r = c["q_lora_rank"], c["kv_lora_rank"]
    mats = (d * ql + ql * nh * (dn + dr) + d * (r + dr) + r * nh * (dn + dv)
            + nh * dv * d)
    return {"matrices": mats, "norms": ql + r}


def _ffn_params(c: Dict[str, Any]) -> Dict[str, int]:
    d, de = c["hidden_size"], c["moe_intermediate_size"]
    return {"dense": 3 * d * c["intermediate_size"],
            "router": d * c["n_routed_experts"], "expert": 3 * d * de,
            "shared": 3 * d * de * c["n_shared_experts"]}


def total_params(c: Dict[str, Any]) -> int:
    d, v = c["hidden_size"], c["vocab_size"]
    k0, n = c["first_k_dense_replace"], c["num_hidden_layers"]
    a, f = _mla_params(c), _ffn_params(c)
    attn = a["matrices"] + a["norms"] + 2 * d
    moe = f["router"] + c["n_routed_experts"] * f["expert"] + f["shared"]
    head = v * d * (1 if c["tie_word_embeddings"] else 2)
    return k0 * (attn + f["dense"]) + (n - k0) * (attn + moe) + head + d


def _attn_flops_per_token_fwd(c: Dict[str, Any], seq: int) -> float:
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (2.0 * c["num_attention_heads"] * (qk + c["v_head_dim"])
            * (seq + 1) / 2)


def model_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    k0, n = c["first_k_dense_replace"], c["num_hidden_layers"]
    a, f = _mla_params(c), _ffn_params(c)
    moe = (f["router"] + c["num_experts_per_tok"] * f["expert"]
           + f["shared"])
    mats = (n * a["matrices"] + k0 * f["dense"] + (n - k0) * moe
            + c["vocab_size"] * c["hidden_size"])
    return 6.0 * mats + 3.0 * n * _attn_flops_per_token_fwd(c, seq)


def attn_fwd_cost(c: Dict[str, Any], seq: int,
                  batch: int) -> Dict[str, float]:
    nh = c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    io = batch * seq * nh * (2 * qk + 2 * c["v_head_dim"]) * 2
    lse = batch * nh * seq * 4
    return {"flops": _attn_flops_per_token_fwd(c, seq) * seq * batch,
            "bytes": float(io + lse)}
