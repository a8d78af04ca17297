"""Distributed flash-decode: attention of ONE query position against a
KV cache whose *sequence* dimension is sharded over the 'model' mesh axis.

Decode is cache-bandwidth-bound; sequence-sharding the cache parallelizes
the reads — but left to the SPMD partitioner, the einsum+softmax graph
all-gathers the whole cache every step (qwen3-4b decode_32k baseline:
72 GiB of all-gather per decoded token).  The correct schedule is the
classic distributed online softmax, written here as an explicit shard_map:

    per shard:  s = q·k_loc, m_loc = max(s), then
    global:     m = pmax(m_loc),  l = psum(sum e^{s-m}),
                out = psum(e^{s-m}·v_loc) / l

Collective traffic per step drops to O(B·H·D) (the partial accumulators)
— ~300 KB instead of the cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_NEG = -1e30


def sharded_flash_decode(
    q: jax.Array,          # [B, 1, H, D]   (replicated over 'model')
    k: jax.Array,          # [B, S, KV, D]  (S sharded over 'model')
    v: jax.Array,          # [B, S, KV, Dv]
    length,                # scalar or [B] — number of valid positions
    *,
    softcap: float = 0.0,
    axis: str = "model",
) -> jax.Array:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or axis not in getattr(mesh, "axis_names", ()):
        raise ValueError("sharded_flash_decode needs a mesh with 'model'")
    n_shards = dict(mesh.shape)[axis]
    b, sq, h, d = q.shape
    s_total = k.shape[1]
    kvh = k.shape[2]
    assert sq == 1 and s_total % n_shards == 0
    s_loc = s_total // n_shards
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))

    # Global KV positions enter as an operand sharded like the cache
    # instead of being derived from jax.lax.axis_index, so the body
    # needs no PartitionId inside the partial-manual region.
    positions = jnp.arange(s_total, dtype=jnp.int32)

    def body(qb, kb, vb, lenb, kpos):
        q5 = qb.reshape(b, sq, kvh, h // kvh, d).astype(jnp.float32)
        q5 = q5 / jnp.sqrt(d)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q5, kb.astype(jnp.float32))
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        valid = kpos[None, :] < lenb[:, None]               # [B, s_loc]
        s = jnp.where(valid[:, None, None, None, :], s, _NEG)
        m_loc = jnp.max(s, axis=-1)                         # [B,KVH,G,1]
        m = jax.lax.pmax(m_loc, axis)
        p = jnp.exp(s - m[..., None])
        l = jax.lax.psum(jnp.sum(p, axis=-1), axis)
        acc = jax.lax.psum(
            jnp.einsum("bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32)), axis
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        dv = vb.shape[-1]
        return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dv)

    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(None, axis, None, None), P(None, axis, None, None),
                  P(), P(axis)),
        out_specs=P(),
        axis_names={axis},
        check_vma=False,
    )(q, k, v, length, positions)
    return out.astype(q.dtype)
