"""Operations and bytes from shapes, read from a configuration file.

Nothing here reads the program: parameter counts and FLOPs come from
the published sizes in ``configs/<config>.json``.

* :func:`model_flops_per_token` — the FLOPs that the forward and the
  backward pass require for one trained token: 6 x the parameters that
  take part in a matrix product (the tied embedding counts once, as
  the LM head; its lookup is free) plus the attention products over
  the keys each query sees (causal, and inside the window where one is
  set).  Recomputation does not count.
* :func:`attn_fwd_cost` — the useful FLOPs and HBM bytes of one
  attention forward over a batch: the score and value products over
  the visible keys, q/k/v read and the output and its row log-sum-exp
  written.
* :func:`bucket_update_bytes` — HBM bytes of one fused AdamW update
  over every parameter: f32 p, m, v and g read, p, m, v written, and g
  written again where the update also zeroes the gradient buffer.
"""
from __future__ import annotations

from typing import Any, Dict


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


def attn_fwd_cost(c: Dict[str, Any], seq: int, batch: int,
                  act_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and bytes of one layer's attention forward over ``batch``
    sequences, operands in a type of ``act_bytes`` bytes."""
    m = dims(c)
    flops = (4.0 * m["nh"] * m["hd"] * mean_context(seq, m["window"])
             * seq * batch)
    qkv = batch * seq * (m["nh"] + 2 * m["nkv"]) * m["hd"] * act_bytes
    out = batch * seq * m["nh"] * m["hd"] * act_bytes
    lse = batch * m["nh"] * seq * 4
    return {"flops": flops, "bytes": float(qkv + out + lse)}


def bucket_update_bytes(n_params: int, zero_grads: bool) -> float:
    return float(n_params) * 4 * (4 + 3 + (1 if zero_grads else 0))


def least_time_s(flops: float, nbytes: float, peak: Dict[str, Any]) -> float:
    """The larger of compute time at the bf16 peak and HBM time at the
    bandwidth peak."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
