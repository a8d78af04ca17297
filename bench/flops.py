"""Operations and bytes from shapes, read from a configuration file.

Nothing here reads the program: parameter counts and FLOPs come from
the published sizes in ``configs/<config>.json``, counted by the
configuration's architecture module (``bench/archs``).

* :func:`total_params` — every parameter the program holds;
* :func:`model_flops_per_token` — the FLOPs that the forward and the
  backward pass require for one trained token; recomputation does not
  count;
* :func:`attn_fwd_cost` — the useful FLOPs and HBM bytes of one layer's
  attention forward over a batch;
* :func:`bucket_update_bytes` — HBM bytes of one fused AdamW update
  over every parameter: f32 p, m, v and g read, p, m, v written, and g
  written again where the update also zeroes the gradient buffer.
"""
from __future__ import annotations

from typing import Any, Dict

from bench import spec


def total_params(c: Dict[str, Any]) -> int:
    return spec.arch(c).total_params(c)


def model_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    return spec.arch(c).model_flops_per_token(c, seq)


def attn_fwd_cost(c: Dict[str, Any], seq: int,
                  batch: int) -> Dict[str, float]:
    """FLOPs and bytes of one layer's attention forward over ``batch``
    sequences of ``seq`` tokens."""
    return spec.arch(c).attn_fwd_cost(c, seq, batch)


def bucket_update_bytes(n_params: int, zero_grads: bool) -> float:
    return float(n_params) * 4 * (4 + 3 + (1 if zero_grads else 0))


def least_time_s(flops: float, nbytes: float, peak: Dict[str, Any]) -> float:
    """The larger of compute time at the bf16 peak and HBM time at the
    bandwidth peak."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
