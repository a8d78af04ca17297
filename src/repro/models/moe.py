"""Mixture-of-Experts FFN: a dropless expert layer that holds a share of
the experts.

Every token is scored by the router over all ``n_experts`` (the router's
logits accumulate in f32): a softmax, the greedy top-k, renormalised over
the k only where ``norm_topk_prob`` says.  The layer's parameters hold
``held`` experts, ids 0 .. held - 1 of the router's (all of them by
default; fewer where the program is one chip's share of an
expert-parallel layer):

* dispatch — the (token, choice) pairs whose expert is held are sorted
  by expert (a stable sort of T*k keys, the others keyed last) and their
  tokens gathered into a buffer with static room for T * min(k, held)
  rows, the most a layer can receive, so no token is dropped at any
  load;
* experts — each held expert's gate/up/down runs as a grouped matmul
  (``jax.lax.ragged_dot``) over exactly the rows routed to it; on a TPU
  its work is bounded by the routed rows rounded up to the kernel's
  tile, not by the buffer;
* combine — each token gathers its chosen rows back, weighted by its
  routing weights (zero for an expert held elsewhere), in f32; the
  shared experts, a dense gated FFN, are added once.

Rows of the buffer past the routed ones are never read: the gather
sends them the row of zeros after the last token, and the combine reads
only routed rows, so what a grouped matmul leaves in them cannot reach
the output or a gradient.

Backward: both row gathers (:func:`take_rows`) are differentiated by
gathers through the map the other way, which ``_dispatch`` already
builds, and not by autodiff's scatter-adds.  The dispatch's cotangent
reaches each token as the sum of its k buffer rows, read through
``pos`` (a choice held elsewhere reads a zero row); the combine's
reaches each buffer row from the one pair that reads it, through the
sorted ``order`` (a row past the routed ones reads a zero row).  A
scatter-add would send every padding row (80-90% of the buffer at one
chip's share of the experts) into one sentinel row, and a TPU serialises
updates that collide.

Expert parallelism: where the ambient mesh splits the held experts over
auto axes (the 'experts' rule of a plain jit), dispatch, experts and
combine run per device in a shard_map: each device takes its own slice
of the experts (ids offset by its index on the expert axes) for its own
tokens, and the partial outputs are summed across the expert shards (an
all-reduce of activations).  The expert weights never leave their
device.

The auxiliary loss is alpha * sum_i f_i * P_i over all ``n_experts``,
with f_i = E / (k * T) * count_i (the top-k choices of expert i) and P_i
the mean router probability of expert i, over the T tokens of each
sequence with ``seq_aux`` (DeepSeek-V2) or of the whole batch otherwise
(the Switch form), averaged over the sequences.

The layer also returns per-step counters (:data:`STAT_KEYS`, f32):
``moe_rows``, the rows routed to held experts; ``moe_max_rows``, the
most rows one held expert takes on one device; ``moe_dropped``, the held
pairs the buffer has no row for, 0 by construction (the buffer has a
row for every held pair; the counter checks that invariant).  The work
runs under the named scope ``moe`` with ``moe_router``,
``moe_dispatch``, ``moe_experts`` and ``moe_combine`` inside it.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import dense_init, gated_act
from repro.sharding import constrain, spec_for

STAT_KEYS = ("moe_rows", "moe_max_rows", "moe_dropped")


def zero_stats(cfg) -> Dict[str, jax.Array]:
    """The counters a model with expert layers reports ({} without)."""
    if cfg.moe is None:
        return {}
    return {k: jnp.zeros((), jnp.float32) for k in STAT_KEYS}


def add_stats(a: Dict[str, jax.Array], b: Dict[str, jax.Array]
              ) -> Dict[str, jax.Array]:
    """Counters of two layers together: the largest expert's rows by
    maximum, the rest by sum."""
    out = dict(a)
    for k, v in b.items():
        if k not in out:
            out[k] = v
        elif k == "moe_max_rows":
            out[k] = jnp.maximum(out[k], v)
        else:
            out[k] = out[k] + v
    return out


def init_moe(key, cfg, dtype=jnp.float32) -> Dict:
    me = cfg.moe
    d = cfg.d_model
    de = me.d_expert or cfg.d_ff
    keys = jax.random.split(key, 5)
    e, held = me.n_experts, me.held
    std = 1.0 / math.sqrt(d)
    p = {
        "router": dense_init(keys[0], d, e, dtype),
        # the held experts live under their own key so the sharding rules
        # can tell the [E, d, de] expert tensors from (scan-stacked)
        # dense FFNs
        "experts": {
            "gate": (jax.random.normal(keys[1], (held, d, de)) * std).astype(dtype),
            "up": (jax.random.normal(keys[2], (held, d, de)) * std).astype(dtype),
            "down": (
                jax.random.normal(keys[3], (held, de, d)) / math.sqrt(de)
            ).astype(dtype),
        },
    }
    if me.n_shared_experts:
        ks = jax.random.split(keys[4], 3)
        ds = de * me.n_shared_experts
        p["shared"] = {
            "gate": dense_init(ks[0], d, ds, dtype),
            "up": dense_init(ks[1], d, ds, dtype),
            "down": dense_init(ks[2], ds, d, dtype),
        }
    return p


def route(router: jax.Array, xt: jax.Array, me
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(probs [T, E] f32, top-k weights [T, k] f32, top-k experts [T, k])."""
    logits = jnp.dot(xt, router, preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, me.experts_per_token)
    if me.norm_topk_prob:
        top_p = top_p / jnp.maximum(
            jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)
    return probs, top_p, top_e


def aux_loss(probs: jax.Array, top_e: jax.Array, me, groups: int
             ) -> jax.Array:
    """alpha * sum_i f_i P_i over ``groups`` equal runs of tokens,
    averaged over the runs."""
    e, k = me.n_experts, me.experts_per_token
    t = probs.shape[0] // groups
    counts = jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=1)
    f = counts.reshape(groups, t, e).sum(1) * (e / (k * t))
    mean_p = jnp.mean(probs.reshape(groups, t, e), axis=1)
    return me.router_aux_coef * jnp.mean(jnp.sum(f * mean_p, axis=-1))


@jax.custom_vjp
def take_rows(x: jax.Array, idx: jax.Array, inv: jax.Array) -> jax.Array:
    """``concatenate([x, 0])[idx]``: rows of ``x`` [n, d], index n for a
    row of zeros, whose backward is a gather too.  ``inv`` [n, J] lists,
    for each row of ``x``, the output rows that read it, and the output's
    row count (a zero row) in its unused entries; the gradient of row s
    is then ``sum_j g[inv[s, j]]``, summed in f32 where J > 1.  Autodiff
    would make a scatter-add of ``g`` into ``x``'s rows plus one, which
    on a TPU serialises the updates that collide, and every output row
    that reads the zero row collides."""
    return jnp.concatenate([x, jnp.zeros((1,) + x.shape[1:], x.dtype)])[idx]


def _take_rows_fwd(x, idx, inv):
    return take_rows(x, idx, inv), inv


def _take_rows_bwd(inv, g):
    g = jnp.concatenate([g, jnp.zeros((1,) + g.shape[1:], g.dtype)])
    if inv.shape[1] == 1:
        return g[inv[:, 0]], None, None
    # one [n, d] f32 sum of J gathers, not a [n, J, d] gather
    acc = g[inv[:, 0]].astype(jnp.float32)
    for j in range(1, inv.shape[1]):
        acc = acc + g[inv[:, j]]
    return acc.astype(g.dtype), None, None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _dispatch(top_e: jax.Array, held: int, offset):
    """Sorted rows of the pairs whose expert is one of ids ``offset`` ..
    ``offset + held - 1``.  Returns (the pair t * k + j held in each
    buffer row, T * k for a row past the routed ones; rows per held
    expert; buffer row of each pair, the buffer's size for a pair held
    elsewhere; counters).  Each is the other's inverse on the routed
    rows."""
    t, k = top_e.shape
    rows = t * min(k, held)
    local = top_e.reshape(-1) - offset
    mine = (local >= 0) & (local < held)
    key = jnp.where(mine, local, held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((held,), jnp.int32).at[key].add(1, mode="drop")
    n_mine = jnp.sum(sizes)
    pair = jnp.where(jnp.arange(rows) < n_mine, order[:rows], t * k)
    # each pair's row in the buffer: the inverse of the sort
    where = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    pos = jnp.where(mine, where, rows).reshape(t, k)
    stats = {
        "moe_rows": n_mine.astype(jnp.float32),
        "moe_max_rows": jnp.max(sizes).astype(jnp.float32),
        "moe_dropped": jnp.sum(mine & (where >= rows)).astype(jnp.float32),
    }
    return pair, sizes, pos, stats


def routed_experts(xt: jax.Array, top_p: jax.Array, top_e: jax.Array,
                   ex: Dict, offset, act: str
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The part of the layer's output [T, d] (f32) that the experts ``ex``
    ([held, ...] each), router ids ``offset`` onward, compute for the
    tokens ``xt``, and the counters."""
    (t, k), d = top_e.shape, xt.shape[-1]
    with jax.named_scope("moe_dispatch"):
        pair, sizes, pos, stats = _dispatch(top_e, ex["gate"].shape[0],
                                            offset)
        # rows past the routed ones read token T, a row of zeros; a
        # token's rows in the buffer are its pos, where a choice held
        # elsewhere names the buffer's size, a row of zeros too
        xs = take_rows(xt, pair // k, pos)
    with jax.named_scope("moe_experts"):
        gate = jax.lax.ragged_dot(xs, ex["gate"], sizes)
        up = jax.lax.ragged_dot(xs, ex["up"], sizes)
        ys = jax.lax.ragged_dot(gated_act(act, gate, up), ex["down"], sizes)
    with jax.named_scope("moe_combine"):
        w = jnp.where(pos < ys.shape[0], top_p, 0.0)
        # a routed row is read by its pair alone, a row past them by none
        g = take_rows(ys, pos.reshape(-1), pair[:, None]).reshape(t, k, d)
        y = jnp.einsum("tk,tkd->td", w, g,
                       preferred_element_type=jnp.float32)
    return y, stats


def _axes(spec) -> Tuple[str, ...]:
    if spec is None:
        return ()
    return (spec,) if isinstance(spec, str) else tuple(spec)


def _on_expert_shards(fn, xt, top_p, top_e, ex):
    """``fn(xt, top_p, top_e, ex, offset)`` over the held experts, per
    device where the ambient mesh splits them over auto axes (see the
    module's docstring), else as is."""
    mesh = jax.sharding.get_abstract_mesh()
    auto = frozenset(a for a, t in zip(mesh.axis_names, mesh.axis_types)
                     if t != jax.sharding.AxisType.Manual)
    held = ex["gate"].shape[0]
    e_axes = _axes(spec_for(("experts",), (held,))[0]) if auto else ()
    n = math.prod(mesh.shape[a] for a in e_axes)
    if n == 1:
        return fn(xt, top_p, top_e, ex, 0)
    t_axes = _axes(spec_for(("batch",), xt.shape[:1])[0])
    tok, exs = P(t_axes or None, None), P(e_axes, None, None)

    def shard(xt, top_p, top_e, ex, first):
        y, stats = fn(xt, top_p, top_e, ex, first[0])
        return y[None], {k: v[None] for k, v in stats.items()}

    # each shard's first expert id arrives as its slice of the ids (an
    # axis_index here would name the enclosing shard_map's manual axes)
    ys, stats = jax.shard_map(
        shard, in_specs=(tok, tok, tok, jax.tree.map(lambda _: exs, ex),
                         P(e_axes)),
        out_specs=(P(e_axes, t_axes or None, None), P(t_axes + e_axes)),
        axis_names=auto, check_vma=False)(
            xt, top_p, top_e, ex, jnp.arange(0, held, held // n))
    return jnp.sum(ys, axis=0), {
        k: (jnp.max if k == "moe_max_rows" else jnp.sum)(v)
        for k, v in stats.items()}


def apply_moe(
    p: Dict,
    x: jax.Array,                 # [B, S, d]
    *,
    cfg,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Returns (output [B,S,d], aux load-balance loss, counters)."""
    me = cfg.moe
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    with jax.named_scope("moe"):
        with jax.named_scope("moe_router"):
            probs, top_p, top_e = route(p["router"], xt, me)
            aux = aux_loss(probs, top_e, me, b if me.seq_aux else 1)
        y, stats = _on_expert_shards(
            functools.partial(routed_experts, act=cfg.ffn_activation),
            xt, top_p, top_e, p["experts"])
        y = y.astype(x.dtype)
        if me.n_shared_experts:
            with jax.named_scope("moe_experts"):
                sh = p["shared"]
                # 'batch' on the flattened token dim (batch-major) — None
                # would force replication (see common.apply_ffn)
                g = constrain(xt @ sh["gate"], ("batch", "ff"))
                u = constrain(xt @ sh["up"], ("batch", "ff"))
                y = y + gated_act(cfg.ffn_activation, g, u) @ sh["down"]
    return constrain(y.reshape(b, s, d), ("batch", None, "embed")), aux, stats
