"""Latent attention and a share of routed experts (DeepSeek-V2-Lite).

Parameter names (the program's tree): ``embed/table``, ``head/w``
(untied, [d, V]), ``final_norm``; per layer ``pre_norm``,
``mixer/{wq,wdkv,kv_norm,wuk,wuv,wo}`` (no q LoRA), ``ffn_norm`` and
either a dense ``ffn/{gate,up,down}`` (the leading ``prefix`` layers)
or ``ffn/{router,experts/{gate,up,down},shared/{gate,up,down}}``
(stacked on a leading layer axis).  The layer holds
``n_routed_experts`` experts, ids 0 onward, of the router's
``router_experts``.

The reference (:func:`row_loss`), one sequence at a time, in float32
with matrix products at ``Precision.HIGHEST``; it imports nothing of the
program:

* latent attention: q = h Wq split into 128 + 64 (nope + rope); the
  latent c = RMSNorm(h Wdkv[:, :512]) and the shared rotary key h
  Wdkv[:, 512:]; k_nope = c Wuk and v = c Wuv expanded per head; YaRN
  rotation of the rope parts (half-split pairs; the frequencies of HF
  ``DeepseekV2YarnRotaryEmbedding``: theta's below the correction range,
  theta's over the factor above it, a linear ramp between); softmax
  scale (nope + rope)^-1/2 x mscale(factor, mscale_all_dim)^2; causal,
  in blocks of queries recomputed in the backward pass;
* the expert layer: the router's softmax over all ``router_experts``,
  the greedy top-k (renormalised only with ``norm_topk_prob``); every held expert applied to every token
  and weighted by the token's weight for it, 0 where the token did not
  choose it; the shared experts added once; the auxiliary loss
  ``aux_loss_alpha`` x sum_i f_i P_i over all router experts, f_i = E /
  (k S) x count_i and P_i the mean probability over the sequence;
* the leading dense layer's gated SiLU MLP, RMSNorm weights ``1 +
  scale``, the untied head over the vocabulary slice and the mean
  next-token cross entropy, plus the layers' auxiliary losses.

Counts: :func:`model_flops_per_token` is 6 x the parameters in matrix
products a token passes through (a routed expert at k x held / E of the
held experts' parameters, the shared experts and the router whole, the
head once, the embedding lookup free) plus the attention products over
the keys a query sees (q.k over 192 dims, p.v over 128), forward and
backward; recomputation does not count.  :func:`attn_fwd_cost`: one
layer's score and value products over the visible keys, q, k, v read
and the output and its row log-sum-exp written, operands in bf16.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from bench import reference

HIGHEST = jax.lax.Precision.HIGHEST
# bytes of an attention operand (bf16)
ACT_BYTES = 2
NORMS = ("scale", "kv_norm")
MATRICES = ("wq", "wdkv", "wuk", "wuv", "wo", "router", "gate", "up",
            "down", "w")
# widths a CPU test can hold: three layers (the dense one and two expert
# layers), a router of 16 experts of which 4 are held; every other key is
# the file's own
TINY = dict(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, kv_lora_rank=32, intermediate_size=96,
            moe_intermediate_size=32, router_experts=16, n_routed_experts=4,
            vocab_size=256)


# ---- the program -----------------------------------------------------------
def program_config(c: Dict[str, Any]):
    """The program's ArchConfig at the file's sizes."""
    from repro.configs import get_config
    from repro.configs.base import MLAConfig, MoEConfig, RopeScaling

    rs = c["rope_scaling"]
    if rs.get("type") != "yarn" or c["q_lora_rank"] is not None \
            or c["scoring_func"] != "softmax" \
            or c["topk_method"] != "greedy" or c["hidden_act"] != "silu" \
            or c["routed_scaling_factor"] != 1:
        raise ValueError("archs.mla_moe runs a softmax router with greedy "
                         "top-k and routed_scaling_factor 1, SiLU, YaRN "
                         "and no q LoRA")
    return dataclasses.replace(
        get_config(c["arch"]),
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        mla=MLAConfig(kv_lora_rank=c["kv_lora_rank"], q_lora_rank=None,
                      qk_nope_head_dim=c["qk_nope_head_dim"],
                      qk_rope_head_dim=c["qk_rope_head_dim"],
                      v_head_dim=c["v_head_dim"]),
        moe=MoEConfig(n_experts=c["router_experts"],
                      experts_per_token=c["num_experts_per_tok"],
                      n_shared_experts=c["n_shared_experts"],
                      d_expert=c["moe_intermediate_size"],
                      router_aux_coef=c["aux_loss_alpha"],
                      first_k_dense=c["first_k_dense_replace"],
                      experts_held=c["n_routed_experts"],
                      norm_topk_prob=bool(c["norm_topk_prob"]),
                      seq_aux=bool(c["seq_aux"])),
        rope_scaling=RopeScaling(
            factor=float(rs["factor"]),
            original_max_position_embeddings=int(
                rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])))


def leaf_kind(names: tuple, ndim: int, c: Dict[str, Any]) -> str:
    last = names[-1]
    if last == "table":
        return "embed"
    if last in NORMS:
        return "rms_scale"
    if ndim >= 2:       # stacked leaves keep a leading layer axis
        return "matrix"
    raise ValueError(f"unknown parameter {'/'.join(names)}")


def tiny(c: Dict[str, Any], seq: int) -> Dict[str, Any]:
    return {**c, **TINY}


# ---- the reference ---------------------------------------------------------
def matrices(c: Dict[str, Any]):
    return MATRICES


def _rms(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * (1.0 + scale)


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(c: Dict[str, Any]) -> jax.Array:
    """[rope/2] inverse frequencies of the YaRN rotation."""
    rs, d, base = c["rope_scaling"], c["qk_rope_head_dim"], c["rope_theta"]

    def corr(rotations):
        return (d * math.log(rs["original_max_position_embeddings"]
                             / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    inter = extra / rs["factor"]
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return inter * (1.0 - keep) + extra * keep


def _rope(x, c):
    """x [S, H, D]: element i of the first half and element i of the
    second half rotate together at frequency i."""
    s, _, d = x.shape
    rs = c["rope_scaling"]
    m = _mscale(rs["factor"], rs["mscale"]) / _mscale(rs["factor"],
                                                      rs["mscale_all_dim"])
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * yarn_frequencies(c)
    cos, sin = m * jnp.cos(ang)[:, None, :], m * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(c: Dict[str, Any]) -> float:
    rs = c["rope_scaling"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return qk ** -0.5 * _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2


def _latent_attention(p, h, c, ein, block: int):
    s, nh = h.shape[0], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    r = c["kv_lora_rank"]
    q = ein("sd,de->se", h, p["wq"], True).reshape(s, nh, dn + dr)
    kv = ein("sd,de->se", h, p["wdkv"], True)
    ckv = _rms(kv[:, :r], p["kv_norm"], c["rms_norm_eps"])
    k_rope = _rope(kv[:, None, r:], c)
    kn = ein("sr,re->se", ckv, p["wuk"], True).reshape(s, nh, dn)
    v = ein("sr,re->se", ckv, p["wuv"], True).reshape(s, nh, dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], c)], -1)
    k = jnp.concatenate([kn, jnp.broadcast_to(k_rope, (s, nh, dr))], -1)
    scale = softmax_scale(c)
    kpos = jnp.arange(s)
    block = min(block, s)
    nb = -(-s // block)
    qs = jnp.pad(q, ((0, nb * block - s), (0, 0), (0, 0)))

    @jax.checkpoint
    def one_block(_, xs):
        qb, start = xs
        qpos = start + jnp.arange(block)
        sc = ein("qhd,khd->hqk", qb, k) * scale
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        return None, ein("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)

    _, outs = jax.lax.scan(one_block, None, (
        qs.reshape(nb, block, nh, dn + dr), jnp.arange(nb) * block))
    o = outs.reshape(nb * block, nh * dv)[:s]
    return ein("se,ed->sd", o, p["wo"], True)


def _mlp(p, h, ein):
    a = jax.nn.silu(ein("sd,df->sf", h, p["gate"], True))
    return ein("sf,fd->sd", a * ein("sd,df->sf", h, p["up"], True),
               p["down"], True)


def experts(p, h, c, ein):
    """The layer's share of the routed experts and its shared experts
    for the tokens ``h`` [S, d], and the sequence's auxiliary loss."""
    e, k = c["router_experts"], c["num_experts_per_tok"]
    held = c["n_routed_experts"]
    probs = jax.nn.softmax(ein("sd,de->se", h, p["router"], True), -1)
    top_p, top_e = jax.lax.top_k(probs, k)
    if c["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    # [S, held]: the token's weight for each held expert, 0 unchosen
    ids = jnp.arange(held)
    gate_w = jnp.sum(jnp.where(top_e[..., None] == ids, top_p[..., None],
                               0.0), axis=1)
    ex = p["experts"]
    a = jax.nn.silu(ein("sd,edf->sef", h, ex["gate"], True))
    a = a * ein("sd,edf->sef", h, ex["up"], True)
    y = ein("sef,efd->sed", a, ex["down"], True)
    out = jnp.einsum("se,sed->sd", gate_w, y, precision=HIGHEST)
    out = out + _mlp(p["shared"], h, ein)
    counts = jax.nn.one_hot(top_e, e).sum((0, 1))
    f = counts * e / (k * h.shape[0])
    aux = c["aux_loss_alpha"] * jnp.sum(f * jnp.mean(probs, 0))
    return out, aux


def _layer(x, p, c, ein, block):
    """One layer: (x after it, its auxiliary loss)."""
    eps = c["rms_norm_eps"]
    x = x + _latent_attention(p["mixer"], _rms(x, p["pre_norm"]["scale"], eps),
                              c, ein, block)
    h = _rms(x, p["ffn_norm"]["scale"], eps)
    if "router" in p["ffn"]:
        y, aux = experts(p["ffn"], h, c, ein)
    else:
        y, aux = _mlp(p["ffn"], h, ein), jnp.zeros((), jnp.float32)
    return x + y, aux


def row_loss(params, tokens, c: Dict[str, Any], precision: str, block: int,
             positions: Optional[int]):
    """Mean next-token cross entropy of one sequence ``tokens`` [S] plus
    the expert layers' auxiliary losses; ``positions`` keeps only the
    first that many targets.  Each layer, query block and loss block is
    recomputed in the backward pass; stacked layers and blocks run as
    scans, so that the program stays small."""
    ein = reference.einsum_for(precision)
    x = params["embed"]["table"][tokens]
    if precision == "fp8":
        params = reference.round_weights(params, matrices(c))
    layer = jax.checkpoint(lambda x, p: _layer(x, p, c, ein, block))
    aux = jnp.zeros((), jnp.float32)
    for p in params["prefix"]:
        x, a = layer(x, p)
        aux = aux + a
    for stacked in params["stack"]:
        x, a = jax.lax.scan(layer, x, stacked)
        aux = aux + jnp.sum(a)
    for p in params["tail"]:
        x, a = layer(x, p)
        aux = aux + a
    x = _rms(x, params["final_norm"]["scale"], c["rms_norm_eps"])
    h, y = x[:-1], tokens[1:]
    n = h.shape[0] if positions is None else positions
    head = params["head"]["w"]
    block = min(block, h.shape[0])
    nb = -(-h.shape[0] // block)
    pad = nb * block - h.shape[0]
    h = jnp.pad(h, ((0, pad), (0, 0))).reshape(nb, block, -1)
    y = jnp.pad(y, (0, pad)).reshape(nb, block)

    @jax.checkpoint
    def chunk(tot, xs):
        hc, yc, start = xs
        logits = ein("sd,dv->sv", hc, head, True)
        lz = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, yc[:, None], -1)[:, 0]
        keep = start + jnp.arange(block) < n
        return tot + jnp.sum(jnp.where(keep, lz - gold, 0.0)), None

    tot, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32),
                          (h, y, jnp.arange(nb) * block))
    return tot / n + aux


# ---- counts ----------------------------------------------------------------
def _attn_matrices(c: Dict[str, Any]) -> int:
    d, nh = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    r = c["kv_lora_rank"]
    return (d * nh * (dn + dr) + d * (r + dr) + r * nh * (dn + dv)
            + nh * dv * d)


def _ffn(c: Dict[str, Any]) -> Dict[str, int]:
    d, de = c["hidden_size"], c["moe_intermediate_size"]
    return {"dense": 3 * d * c["intermediate_size"],
            "router": d * c["router_experts"], "expert": 3 * d * de,
            "shared": 3 * d * de * c["n_shared_experts"]}


def total_params(c: Dict[str, Any]) -> int:
    """Every parameter the program holds: the held experts only."""
    d, v = c["hidden_size"], c["vocab_size"]
    k0, n = c["first_k_dense_replace"], c["num_hidden_layers"]
    f = _ffn(c)
    attn = _attn_matrices(c) + c["kv_lora_rank"] + 2 * d
    moe = f["router"] + c["n_routed_experts"] * f["expert"] + f["shared"]
    head = v * d * (1 if c["tie_word_embeddings"] else 2)
    return k0 * (attn + f["dense"]) + (n - k0) * (attn + moe) + head + d


def mean_context(seq: int) -> float:
    return (seq + 1) / 2


def _attn_flops_per_token_fwd(c: Dict[str, Any], seq: int) -> float:
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (2.0 * c["num_attention_heads"] * (qk + c["v_head_dim"])
            * mean_context(seq))


def routed_experts_per_token(c: Dict[str, Any]) -> float:
    """Held experts a token passes through on average: k x held / E."""
    return (c["num_experts_per_tok"] * c["n_routed_experts"]
            / c["router_experts"])


def model_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    k0, n = c["first_k_dense_replace"], c["num_hidden_layers"]
    f = _ffn(c)
    moe = (f["router"] + routed_experts_per_token(c) * f["expert"]
           + f["shared"])
    mats = (n * _attn_matrices(c) + k0 * f["dense"] + (n - k0) * moe
            + c["vocab_size"] * c["hidden_size"])
    return 6.0 * mats + 3.0 * n * _attn_flops_per_token_fwd(c, seq)


def attn_fwd_cost(c: Dict[str, Any], seq: int,
                  batch: int) -> Dict[str, float]:
    nh = c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    io = batch * seq * nh * (2 * qk + 2 * c["v_head_dim"]) * ACT_BYTES
    lse = batch * nh * seq * 4
    return {"flops": _attn_flops_per_token_fwd(c, seq) * seq * batch,
            "bytes": float(io + lse)}


def expert_mm_cost(c: Dict[str, Any], seq: int,
                   batch: int) -> Dict[str, float]:
    """FLOPs and HBM bytes of one step's grouped matmuls of the held
    experts, from the configuration and the traffic alone, at a balanced
    load: rows = T k held / E over the expert layers; FLOPs 18 rows d de
    (forward: gate, up, down; backward: twice that); bytes: the held
    experts' weights read twice (forward and backward) and their
    gradients written once, and the rows in and out: each of the nine
    products reads its row operands and writes its row result, 9 (d +
    de) elements a row in all; bf16 throughout."""
    d, de = c["hidden_size"], c["moe_intermediate_size"]
    layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    rows = batch * seq * routed_experts_per_token(c)
    weights = 3 * c["n_routed_experts"] * d * de
    per_row = 9 * (d + de)
    nbytes = layers * ACT_BYTES * (3 * weights + rows * per_row)
    return {"flops": 18.0 * layers * rows * d * de, "bytes": float(nbytes)}
