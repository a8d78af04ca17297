"""Pallas TPU kernels: fused optimizer update over a whole flat bucket.

One launch applies SGD-momentum or Adam to an entire bucket buffer —
params, moments and the merged gradient are the per-bucket flat f32
buffers of ``BucketLayout`` reshaped to (rows, 128) lanes and tiled over
a 1-D grid of row blocks.  Everything a per-leaf optimizer pays per
tensor (launch, dispatch, tree bookkeeping) is paid once per bucket.

* **Masked tail** — buffers are padded to a lane multiple; a 2-D iota
  against the static valid length keeps the tail at its (zero) value
  even if garbage rides in the gradient tail.  On the sharded flat
  engine the operand is one device's shard span and the valid length is
  device-dependent, so the caller pre-masks the gradient and passes
  ``n_valid == span`` (the mask compiles away — see ops.py).
* **Segment hparams** — per-leaf (lr_scale, weight_decay) arrive either
  as compile-time scalars (uniform buckets, the default — no O(params)
  constants) or as materialized per-element arrays blocked like the
  buffers (see segments.py).
* **Fused zeroing** — with ``zero_grads`` the kernel also writes zeros
  through an output aliased to the gradient buffer, so the delayed-update
  accumulator reset costs no extra pass.
* Dynamic scalars (grad scale, clip, lr, bias corrections) ride in one
  (1, 128) f32 row broadcast to every program (ops.SCALARS_* layout).

In-place semantics come from ``input_output_aliases``.  The pure-JAX
twin in ref.py computes the same expressions in the same order, so the
two bit-match.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.optim.optimizers import OptimizerSpec

_LANES = 128

# the layout pads buffers in units of train.bucketing.PAD_MULTIPLE; the
# two constants must agree (imported lazily there to keep kernels free
# of train-package imports — verified here instead of at a distance)
def _check_lane_width() -> None:
    from repro.train.bucketing import PAD_MULTIPLE

    assert PAD_MULTIPLE == _LANES, (PAD_MULTIPLE, _LANES)


def _update_kernel(
    *refs,
    spec: OptimizerSpec,
    n_valid: int,
    rows_total: int,
    block_rows: int,
    uniform: Optional[Tuple[float, float]],
    zero_grads: bool,
):
    """Shared SGD/Adam body on one (block_rows, 128) tile."""
    adam = spec.name == "adamw"
    i = 0
    scal_ref = refs[i]; i += 1
    p_ref = refs[i]; i += 1
    m_ref = refs[i]; i += 1
    v_ref = refs[i] if adam else None
    i += 1 if adam else 0
    g_ref = refs[i]; i += 1
    if uniform is None:
        sc_ref = refs[i]; i += 1
        wd_ref = refs[i]; i += 1
    p_out = refs[i]; i += 1
    m_out = refs[i]; i += 1
    if adam:
        v_out = refs[i]; i += 1
    if zero_grads:
        g_out = refs[i]; i += 1

    pid = pl.program_id(0)
    base = pid * block_rows * _LANES
    idx = base + (
        jax.lax.broadcasted_iota(jnp.int32, (block_rows, _LANES), 0) * _LANES
        + jax.lax.broadcasted_iota(jnp.int32, (block_rows, _LANES), 1)
    )
    masked = n_valid < rows_total * _LANES
    mask = idx < n_valid

    gscale = scal_ref[0, 0]
    clip = scal_ref[0, 1]
    lr = scal_ref[0, 2]
    if uniform is not None:
        sc, wd = uniform
    else:
        sc, wd = sc_ref[...], wd_ref[...]

    p = p_ref[...]
    m = m_ref[...]
    g = g_ref[...]
    ghat = (g * gscale) * clip
    if adam:
        bc1, bc2 = scal_ref[0, 3], scal_ref[0, 4]
        b1, b2 = spec.beta1, spec.beta2
        v = v_ref[...]
        m_new = b1 * m + (1 - b1) * ghat
        v_new = b2 * v + (1 - b2) * ghat * ghat
        u = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + spec.eps)
    else:
        m_new = spec.momentum * m + ghat
        u = m_new
    if (uniform is None) or wd:
        u = u + wd * p
    p_new = p - (lr * sc) * u

    if masked:
        p_new = jnp.where(mask, p_new, p)
        m_new = jnp.where(mask, m_new, m)
        if adam:
            v_new = jnp.where(mask, v_new, v)
    p_out[...] = p_new
    m_out[...] = m_new
    if adam:
        v_out[...] = v_new
    if zero_grads:
        g_out[...] = jnp.zeros_like(g)


def bucket_update_pallas(
    spec: OptimizerSpec,
    p: jax.Array,
    m: jax.Array,
    v: Optional[jax.Array],
    g: jax.Array,
    scalars: jax.Array,
    *,
    n_valid: int,
    uniform: Optional[Tuple[float, float]],
    elem_hparams: Optional[Tuple[jax.Array, jax.Array]] = None,
    zero_grads: bool = False,
    block_rows: int = 256,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array], Optional[jax.Array]]:
    """Fused bucket update, one pallas_call.  Same contract as
    ref.bucket_update_ref (flat f32[padded] buffers in/out)."""
    adam = spec.name == "adamw"
    if spec.name not in ("adamw", "sgd"):
        raise ValueError(spec.name)
    _check_lane_width()
    padded = p.shape[0]
    assert padded % _LANES == 0, (
        f"bucket buffer length {padded} not a lane multiple; build the "
        f"layout with pad_multiple={_LANES}"
    )
    rows = padded // _LANES
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)

    shape2d = (rows, _LANES)
    row_spec = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
    scal_spec = pl.BlockSpec((1, _LANES), lambda i: (0, 0))

    operands = [scalars]
    in_specs = [scal_spec]
    for x in (p, m) + ((v,) if adam else ()) + (g,):
        operands.append(x.reshape(shape2d))
        in_specs.append(row_spec)
    if uniform is None:
        sc_arr, wd_arr = elem_hparams
        operands += [sc_arr.reshape(shape2d), wd_arr.reshape(shape2d)]
        in_specs += [row_spec, row_spec]

    n_out = (3 if adam else 2) + (1 if zero_grads else 0)
    out_shape = [jax.ShapeDtypeStruct(shape2d, jnp.float32)] * n_out
    out_specs = [row_spec] * n_out

    # operand k of (p, m, [v], g) aliases output k: in-place update
    n_state = 3 if adam else 2
    aliases = {1 + k: k for k in range(n_state)}
    if zero_grads:
        aliases[1 + n_state] = n_state    # g -> zeroed accumulator

    kernel = functools.partial(
        _update_kernel,
        spec=spec,
        n_valid=n_valid,
        rows_total=rows,
        block_rows=block_rows,
        uniform=uniform,
        zero_grads=zero_grads,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name="bucket_update",
    )(*operands)
    out = [o.reshape(padded) for o in out]
    p_new, m_new = out[0], out[1]
    v_new = out[2] if adam else None
    gz = out[-1] if zero_grads else None
    return p_new, m_new, v_new, gz
