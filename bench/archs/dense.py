"""The dense grouped-query block (Qwen3, StarCoder2).

Parameter names (the program's tree): ``embed/table``, ``final_norm``,
and per layer ``pre_norm``, ``mixer/{wq,wk,wv,wo[,q_norm,k_norm]}``,
``ffn_norm``, ``ffn/{gate,up,down}`` or ``ffn/{up,down}``, stacked on a
leading layer axis.  The head is the tied embedding.

The reference (:func:`row_loss`), one sequence at a time, in float32
with matrix products at ``Precision.HIGHEST``: token embedding; per
layer a pre-norm, grouped-query causal attention (per-head RMS q/k
norms where the model has them, rotary embedding with the two halves of
each head rotated together, softmax scale 1/sqrt(head_dim), keys inside
the sliding window where one is set), a residual, a pre-norm and the
MLP (gated SiLU or tanh-GELU), a residual; a final norm, the tied LM
head and the mean next-token cross entropy.  Attention runs in blocks
of queries and the loss in blocks of positions, each recomputed in the
backward pass, so that 4,096 tokens fit.  Departures the configuration
states (norm epsilon, no biases in linear layers) are read from its
file; an RMSNorm weight is ``1 + scale``, as the program writes it.

Counts (:func:`model_flops_per_token`): 6 x the parameters that take
part in a matrix product (the tied embedding counts once, as the LM
head; its lookup is free) plus the attention products over the keys
each query sees (causal, and inside the window where one is set).
Recomputation does not count.  :func:`attn_fwd_cost`: the score and
value products over the visible keys, q/k/v read and the output and its
row log-sum-exp written, operands in bf16.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from bench import reference

# bytes of an attention operand (bf16)
ACT_BYTES = 2
# widths a CPU test can hold; everything else is the file's own
TINY = dict(num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, intermediate_size=256,
            vocab_size=512)
MATRICES = ("wq", "wk", "wv", "wo", "gate", "up", "down", "table")


# ---- the program -----------------------------------------------------------
def program_config(c: Dict[str, Any]):
    """The program's ArchConfig at the file's sizes."""
    from repro.configs import get_config

    act = {"silu": "silu", "gelu_pytorch_tanh": "gelu_mlp"}[c["hidden_act"]]
    base = get_config(c["arch"])
    kw = dict(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or 0, d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), norm=c["norm"],
        use_qk_norm=bool(c.get("qk_norm")), ffn_activation=act,
        sliding_window=c.get("sliding_window") or 0,
    )
    return dataclasses.replace(base, **kw)


def leaf_kind(names: tuple, ndim: int, c: Dict[str, Any]) -> str:
    last = names[-1]
    if last == "table":
        return "embed"
    if last in ("q_norm", "k_norm"):
        return "rms_scale"
    if last == "bias":
        return "ln_bias"
    if last == "scale":
        return "rms_scale" if c["norm"] == "rmsnorm" else "ln_scale"
    if ndim >= 2:       # stacked leaves keep a leading layer axis
        return "matrix"
    raise ValueError(f"unknown parameter {'/'.join(names)}")


def tiny(c: Dict[str, Any], seq: int) -> Dict[str, Any]:
    t = {**c, **TINY}
    if t.get("sliding_window"):
        t["sliding_window"] = seq * 3 // 4
    return t


# ---- the reference ---------------------------------------------------------
def matrices(c: Dict[str, Any]):
    return MATRICES


def _eps(c: Dict[str, Any]) -> float:
    return c["rms_norm_eps"] if c["norm"] == "rmsnorm" else c["norm_epsilon"]


def _norm(p, x, c):
    eps = _eps(c)
    if c["norm"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * (1.0 + p["scale"])
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rms_head(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * (1.0 + scale)


def _rope(x, theta):
    """x [S, H, D]; the first and second halves of D rotate as pairs."""
    s, _, d = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, h, c, ein, block: int):
    s = h.shape[0]
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // nh
    g = nh // nkv
    window = c.get("sliding_window") or 0
    q = ein("sd,de->se", h, p["wq"], True).reshape(s, nh, hd)
    k = ein("sd,de->se", h, p["wk"], True).reshape(s, nkv, hd)
    v = ein("sd,de->se", h, p["wv"], True).reshape(s, nkv, hd)
    if c.get("qk_norm"):
        q = _rms_head(q, p["q_norm"], _eps(c))
        k = _rms_head(k, p["k_norm"], _eps(c))
    q = _rope(q, c["rope_theta"]).reshape(s, nkv, g, hd)
    k = _rope(k, c["rope_theta"])
    kpos = jnp.arange(s)

    @jax.checkpoint
    def one_block(qb, start):
        qpos = start + jnp.arange(qb.shape[0])
        sc = ein("qkgd,tkd->kgqt", qb, k) / jnp.sqrt(jnp.float32(hd))
        ok = kpos[None, :] <= qpos[:, None]
        if window:
            ok &= kpos[None, :] > qpos[:, None] - window
        sc = jnp.where(ok, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return ein("kgqt,tkd->qkgd", pr, v)

    outs = [one_block(q[i:i + block], i) for i in range(0, s, block)]
    o = jnp.concatenate(outs, 0).reshape(s, nh * hd)
    return ein("se,ed->sd", o, p["wo"], True)


def _mlp(p, h, c, ein):
    if c["hidden_act"] == "silu":
        a = jax.nn.silu(ein("sd,df->sf", h, p["gate"], True))
        a = a * ein("sd,df->sf", h, p["up"], True)
    else:
        a = jax.nn.gelu(ein("sd,df->sf", h, p["up"], True),
                        approximate=True)
    return ein("sf,fd->sd", a, p["down"], True)


def _layers(params) -> List[Dict[str, Any]]:
    if params.get("prefix") or params.get("tail"):
        raise ValueError("the dense block comes only in a stacked layer "
                         "pattern")
    out = []
    for stacked in params["stack"]:
        n = jax.tree.leaves(stacked)[0].shape[0]
        out += [jax.tree.map(lambda x, i=i: x[i], stacked) for i in range(n)]
    return out


def row_loss(params, tokens, c: Dict[str, Any], precision: str, block: int,
             positions: Optional[int]):
    """Mean next-token cross entropy of one sequence ``tokens`` [S];
    ``positions`` keeps only the first that many targets."""
    ein = reference.einsum_for(precision)
    x = params["embed"]["table"][tokens]
    if precision == "fp8":
        params = reference.round_weights(params, matrices(c))
    table = params["embed"]["table"]
    for p in _layers(params):
        layer = jax.checkpoint(
            lambda x, p: x + _attention(p["mixer"], _norm(p["pre_norm"], x, c),
                                        c, ein, block))
        x = layer(x, p)
        x = x + _mlp(p["ffn"], _norm(p["ffn_norm"], x, c), c, ein)
    x = _norm(params["final_norm"], x, c)
    h, y = x[:-1], tokens[1:]
    n = h.shape[0] if positions is None else positions

    @jax.checkpoint
    def chunk(hc, yc):
        logits = ein("sd,vd->sv", hc, table, True)
        lz = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, yc[:, None], -1)[:, 0]
        return jnp.sum(lz - gold)

    tot = sum(chunk(h[i:min(i + block, n)], y[i:min(i + block, n)])
              for i in range(0, n, block))
    return tot / n


# ---- counts ----------------------------------------------------------------
def dims(c: Dict[str, Any]) -> Dict[str, int]:
    d = c["hidden_size"]
    nh = c["num_attention_heads"]
    return {
        "layers": c["num_hidden_layers"],
        "d": d,
        "nh": nh,
        "nkv": c["num_key_value_heads"],
        "hd": c.get("head_dim") or d // nh,
        "ff": c["intermediate_size"],
        "vocab": c["vocab_size"],
        "window": c.get("sliding_window") or 0,
        "gated": c["hidden_act"] == "silu",
        "tied": bool(c["tie_word_embeddings"]),
    }


def layer_matmul_params(c: Dict[str, Any]) -> int:
    m = dims(c)
    attn = m["d"] * m["hd"] * (2 * m["nh"] + 2 * m["nkv"])
    mlp = (3 if m["gated"] else 2) * m["d"] * m["ff"]
    return attn + mlp


def matmul_params(c: Dict[str, Any]) -> int:
    m = dims(c)
    head = m["vocab"] * m["d"]
    return m["layers"] * layer_matmul_params(c) + head


def total_params(c: Dict[str, Any]) -> int:
    """Every parameter the program holds: matrices, the embedding (twice
    when untied), and the norm weights (and LayerNorm biases)."""
    m = dims(c)
    norm_width = 2 if c["norm"] == "layernorm" else 1
    per_layer = layer_matmul_params(c) + 2 * norm_width * m["d"]
    if c.get("qk_norm"):
        per_layer += 2 * m["hd"]
    embed = m["vocab"] * m["d"] * (1 if m["tied"] else 2)
    return m["layers"] * per_layer + embed + norm_width * m["d"]


def mean_context(seq: int, window: int) -> float:
    """Mean number of keys a causal query sees, inside ``window``."""
    if not window or window >= seq:
        return (seq + 1) / 2
    # positions 0..w-1 see i+1 keys, the rest see w
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def attn_flops_per_token_fwd(c: Dict[str, Any], seq: int) -> float:
    m = dims(c)
    return (m["layers"] * 4.0 * m["nh"] * m["hd"]
            * mean_context(seq, m["window"]))


def model_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    return 6.0 * matmul_params(c) + 3.0 * attn_flops_per_token_fwd(c, seq)


def attn_fwd_cost(c: Dict[str, Any], seq: int,
                  batch: int) -> Dict[str, float]:
    m = dims(c)
    flops = (4.0 * m["nh"] * m["hd"] * mean_context(seq, m["window"])
             * seq * batch)
    qkv = batch * seq * (m["nh"] + 2 * m["nkv"]) * m["hd"] * ACT_BYTES
    out = batch * seq * m["nh"] * m["hd"] * ACT_BYTES
    lse = batch * m["nh"] * seq * 4
    return {"flops": flops, "bytes": float(qkv + out + lse)}
