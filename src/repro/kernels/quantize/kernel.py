"""Pallas TPU kernels for the wire-precision casts (DESIGN.md §13).

Same layout discipline as kernels/bucket_update: the flat bucket buffer
reshapes to (rows, 128) lanes and tiles over a 1-D grid of row blocks;
a 2-D broadcasted iota against the static valid length zeroes the
padded tail.  The stochastic-rounding randomness is a counter-based
murmur3-finalizer hash of the GLOBAL flat element index (derived from
``program_id`` inside the kernel), so the bits are independent of the
grid/block geometry and bit-match the pure-JAX twin in ref.py under the
interpreter — determinism the low-precision resident master depends on.

The int8 per-row scales come back as a (rows, 128) row-broadcast array
(every lane of a row carries the row's scale) because a (rows, 1)
output is not a legal TPU tile; ops.py slices lane 0.  Wire-byte
accounting prices scales at 4 bytes/row regardless.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.quantize.ref import _GOLDEN, _M1, _M2

_LANES = 128


def _iota2(shape2d):
    return (
        jax.lax.broadcasted_iota(jnp.int32, shape2d, 0) * _LANES
        + jax.lax.broadcasted_iota(jnp.int32, shape2d, 1)
    )


def _hash_u32(idx, seed):
    # identical expression order to ref._hash_u32 (bit-match contract)
    x = idx.astype(jnp.uint32) + seed.astype(jnp.uint32) * jnp.uint32(_GOLDEN)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_M1)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(_M2)
    x = x ^ (x >> 16)
    return x


def _grid(rows: int, block_rows: int):
    block_rows = min(block_rows, rows)
    return (pl.cdiv(rows, block_rows),), block_rows


def _sr_bf16_kernel(seed_ref, x_ref, o_ref, *, n_valid, block_rows):
    shape = (block_rows, _LANES)
    base = pl.program_id(0) * block_rows * _LANES
    idx = base + _iota2(shape)
    seed = seed_ref[0, 0]
    r = _hash_u32(idx, seed) & jnp.uint32(0xFFFF)
    bits = jax.lax.bitcast_convert_type(
        x_ref[...].astype(jnp.float32), jnp.uint32
    )
    rounded = (bits + r) & jnp.uint32(0xFFFF0000)
    y = jax.lax.bitcast_convert_type(rounded, jnp.float32)
    y = jnp.where(idx < n_valid, y, 0.0)
    o_ref[...] = y.astype(jnp.bfloat16)


def stochastic_round_bf16_pallas(
    x: jax.Array,
    seed,
    n_valid: Optional[int] = None,
    *,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    padded = x.shape[0]
    assert padded % _LANES == 0, padded
    rows = padded // _LANES
    n_valid = padded if n_valid is None else n_valid
    grid, block_rows = _grid(rows, block_rows)
    seed_row = jnp.full((1, _LANES), jnp.asarray(seed, jnp.uint32))
    out = pl.pallas_call(
        functools.partial(
            _sr_bf16_kernel, n_valid=n_valid, block_rows=block_rows
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, _LANES), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.bfloat16),
        interpret=interpret,
        name="stochastic_round_bf16",
    )(seed_row, x.reshape(rows, _LANES))
    return out.reshape(padded)


def _quant_int8_kernel(x_ref, q_ref, s_ref, *, n_valid, block_rows):
    shape = (block_rows, _LANES)
    base = pl.program_id(0) * block_rows * _LANES
    idx = base + _iota2(shape)
    x = jnp.where(idx < n_valid, x_ref[...].astype(jnp.float32), 0.0)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    # reciprocal multiply, same expression as ref.py (bit-match contract)
    scale = jnp.where(absmax > 0.0, absmax * jnp.float32(1.0 / 127.0), 1.0)
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = jnp.broadcast_to(scale, shape)


def quantize_int8_pallas(
    x: jax.Array,
    n_valid: Optional[int] = None,
    *,
    block_rows: int = 256,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    padded = x.shape[0]
    assert padded % _LANES == 0, padded
    rows = padded // _LANES
    n_valid = padded if n_valid is None else n_valid
    grid, block_rows = _grid(rows, block_rows)
    row_spec = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
    q, s = pl.pallas_call(
        functools.partial(
            _quant_int8_kernel, n_valid=n_valid, block_rows=block_rows
        ),
        grid=grid,
        in_specs=[row_spec],
        out_specs=[row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, _LANES), jnp.int8),
            jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="quantize_int8",
    )(x.reshape(rows, _LANES))
    return q.reshape(padded), s[:, 0]


def _dequant_int8_kernel(q_ref, s_ref, o_ref, *, n_valid, block_rows):
    shape = (block_rows, _LANES)
    base = pl.program_id(0) * block_rows * _LANES
    idx = base + _iota2(shape)
    y = q_ref[...].astype(jnp.float32) * s_ref[...]
    o_ref[...] = jnp.where(idx < n_valid, y, 0.0)


def dequantize_int8_pallas(
    q: jax.Array,
    scale: jax.Array,
    n_valid: Optional[int] = None,
    *,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    padded = q.shape[0]
    assert padded % _LANES == 0, padded
    rows = padded // _LANES
    n_valid = padded if n_valid is None else n_valid
    grid, block_rows = _grid(rows, block_rows)
    row_spec = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
    s2 = jnp.broadcast_to(scale[:, None], (rows, _LANES))
    out = pl.pallas_call(
        functools.partial(
            _dequant_int8_kernel, n_valid=n_valid, block_rows=block_rows
        ),
        grid=grid,
        in_specs=[row_spec, row_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        interpret=interpret,
        name="dequantize_int8",
    )(q.reshape(rows, _LANES), s2)
    return out.reshape(padded)
