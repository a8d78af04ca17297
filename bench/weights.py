"""Seeded weights and batches, made on the device from ``--seed``.

The benchmark makes its own initial weights, so the reference starts
from values the program never made.  Each leaf of the program's
parameter tree (the shapes come from ``DeftRuntime.checkpoint_struct``)
is drawn from its own key, rounded to bfloat16 (the compute type) and
held in float32 (the master type), by the kind that the configuration's
architecture module (``bench/archs``, ``leaf_kind``) gives its name:

* ``embed`` normal(0, 0.02);
* ``matrix`` normal(0, 1/sqrt(fan_in)), fan_in its second-to-last axis;
* ``rms_scale``, an RMSNorm weight, which the program writes as
  ``1 + scale``: ``scale`` ~ normal(0, 0.02); ``ln_scale``, a LayerNorm
  ``scale``: 1 + normal(0, 0.02); ``ln_bias``, its ``bias``:
  normal(0, 0.02).

The batch stream has the distribution of the program's synthetic
stream (``data/pipeline.make_batch``): a Markov chain that follows a
seeded permutation of the vocabulary with probability ``follow_p`` and
otherwise draws a Zipf(``zipf_exponent``) token.  Tokens are drawn by
inverse CDF, so a whole pool costs one scan over the sequence.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench import spec


def base_key(seed: int) -> jax.Array:
    """A key from any non-negative seed (PRNGKey keeps 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)


def _path_names(path) -> tuple:
    out = []
    for p in path:
        out.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return tuple(out)


def init_params(struct, cfg: Dict[str, Any], key: jax.Array):
    """The params tree of ``struct`` (a ShapeDtypeStruct tree), drawn
    from ``key`` (:func:`base_key` of the seed); call under ``jax.jit``
    with the key as an argument, so one compiled program serves every
    seed."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(struct)
    leaf_kind = spec.arch(cfg).leaf_kind
    leaves = []
    for i, (path, s) in enumerate(flat):
        names = _path_names(path)
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, s.shape, jnp.float32)
        kind = leaf_kind(names, len(s.shape), cfg)
        if kind == "embed":
            x = 0.02 * z
        elif kind == "matrix":
            x = z / jnp.sqrt(jnp.float32(s.shape[-2]))
        elif kind == "ln_scale":
            x = 1.0 + 0.02 * z
        elif kind in ("rms_scale", "ln_bias"):
            x = 0.02 * z
        else:
            raise ValueError(f"{'/'.join(names)}: no draw for kind {kind!r}")
        # reduce_precision, not a round trip through bf16: XLA may drop
        # a convert pair as excess precision, and did, in some programs
        # and not in others
        leaves.append(jax.lax.reduce_precision(x, exponent_bits=8,
                                               mantissa_bits=7))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def token_pool(key: jax.Array, rows: int, seq: int, vocab: int,
               zipf_exponent: float, follow_p: float) -> jax.Array:
    """[rows, seq] int32 tokens of the Zipf/Markov stream drawn from
    ``key``; call under ``jax.jit`` with static sizes."""
    key = jax.random.fold_in(key, 0x5EED)
    k_perm, k_first, k_follow, k_draw = jax.random.split(key, 4)
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    w = jnp.exp(-zipf_exponent * jnp.log(ranks))
    cdf = jnp.cumsum(w / jnp.sum(w))
    perm = jax.random.permutation(k_perm, vocab)

    def zipf(u):
        return jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1)

    first = zipf(jax.random.uniform(k_first, (rows,)))
    follow = jax.random.bernoulli(k_follow, follow_p, (seq - 1, rows))
    draws = zipf(jax.random.uniform(k_draw, (seq - 1, rows)))

    def step(tok, xs):
        f, d = xs
        nxt = jnp.where(f, perm[tok], d)
        return nxt, nxt

    _, rest = jax.lax.scan(step, first, (follow, draws))
    return jnp.concatenate([first[None], rest], axis=0).T.astype(jnp.int32)
