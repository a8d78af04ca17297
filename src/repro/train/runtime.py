"""DeftRuntime: the production DeFT execution engine.

Replaces the ad-hoc per-phase step-fn list of ``train/steps.py`` with a
runtime that owns the whole compiled-phase lifecycle (see DESIGN.md):

* **Bucket-fused collectives** — gradients are packed per bucket into one
  contiguous f32 buffer using the static :class:`BucketLayout` (offsets /
  sizes precomputed at plan time), so each phase issues exactly ONE
  ``psum`` (or one hierarchical reduce-scatter chain on the secondary
  link) per *synced bucket* instead of one per parameter leaf.  The
  ``cur``/``fut`` gradient-generation accumulators are per-bucket flat
  buffers; accumulate / zero / rotate act on whole buffers and the
  leaf tree is only reassembled in update phases.
* **Buffer donation** — every phase executable (and the DDP baseline via
  :func:`make_ddp_step`) donates the train state, so params, optimizer
  moments and both accumulators update in place instead of being copied
  each step.
* **AOT phase cache** — phases are deduped by ``PhaseSpec`` signature and
  lowered + compiled ahead of the first step; ``step(i)`` dispatches the
  cached executable for ``i % period`` and the runtime exposes compile /
  dispatch timing stats.
* **Flat-resident state** (default, DESIGN.md §8) — params and optimizer
  moments live as per-bucket flat f32 buffers for the whole period, not
  as trees: the forward unflattens with static slice/reshape views, and
  update phases apply the optimizer with ONE fused bucket-update kernel
  per bucket (Pallas on TPU, lax fallback elsewhere — see
  ``kernels/bucket_update``) instead of per-leaf ``apply_updates`` over
  hundreds of tiny tensors.  The tree form exists only at checkpoint /
  eval boundaries (:meth:`DeftRuntime.params_tree` /
  :meth:`DeftRuntime.state_to_tree`).

The per-leaf path in ``train/steps.py`` is kept as the semantic
reference (tests prove flat == fused-tree == per-leaf == the gradient-
accumulation reference) and as the benchmark baseline; the PR-1
tree-state fused path remains available via ``flat_state=False``.
"""
from __future__ import annotations

import concurrent.futures
import contextlib
import dataclasses
import functools
import re
import threading
import time
import warnings
from typing import (Any, Callable, Dict, FrozenSet, List, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.precision import WIRE_BYTES
from repro.core.scheduler import DeftSchedule, PhaseSpec
from repro.kernels.bucket_update import (
    BucketSegments,
    apply_bucket_updates,
    build_segments,
    init_flat_opt_state,
)
from repro.kernels.quantize import (
    cast_compute,
    dequantize_int8,
    quantize_dequantize_int8,
    quantize_int8,
    stochastic_round_bf16,
)
from repro.models.model import init_params, loss_fn
from repro.obs.hlo_scopes import collective_scopes
from repro.obs.trace import Tracer
from repro.optim.optimizers import OptimizerSpec, apply_updates, init_opt_state
from repro.sharding import (
    logical_rules,
    rules_deft_manual_dp,
    rules_deft_rs_manual_pod,
)
from repro.train.bucketing import (
    BucketLayout,
    LayoutTransition,
    build_layout_transition,
    flatten_buckets,
    repack_buffers,
    unflatten_buckets,
)
from repro.train.chains import chain_all_gather, chain_reduce_scatter
from repro.train.streaming import lazy_param_tree
from repro.train.steps import (
    TrainState,
    _batch_specs,
    _dp_sizes,
    _state_specs,
    _sync_primary,
    _sync_secondary,
    ddp_train_step,
)


def init_fused_accumulators(
    layout: BucketLayout, accum_devices: int
) -> Dict[str, Tuple[jax.Array, ...]]:
    """Per-bucket flat f32 accumulators with a leading device axis."""
    zeros = lambda: tuple(
        jnp.zeros((accum_devices, s), jnp.float32) for s in layout.buf_sizes
    )
    return {"cur": zeros(), "fut": zeros()}


# ---------------------------------------------------------------------------
# Shared per-bucket routing (identical for tree-state and flat-state paths)
# ---------------------------------------------------------------------------
def _sync_scope(phase: PhaseSpec, b: int, gen: str):
    """Named scope of bucket ``b``'s gradient sync of generation ``gen``
    ('cur': the older one, 'new': this step's): one path component,
    ``deft_sync.b<b>.<primary|secondary>.<gen>``, so a collective in the
    compiled program and the device trace names its bucket, its planned
    link and its generation."""
    link = "secondary" if phase.secondary[b] else "primary"
    return jax.named_scope(f"deft_sync.b{b}.{link}.{gen}")


def _gather_scope(b: int, secondary: bool):
    """Named scope of bucket ``b``'s param or trailing all-gather."""
    link = "secondary" if secondary else "primary"
    return jax.named_scope(f"deft_gather.b{b}.{link}")


def _route_and_sync(phase: PhaseSpec, g_flat, cur, fut, sync):
    """DeFT generation bookkeeping on per-bucket flat buffers.

    Returns (gen, new_fut, cur_synced): the merged fresh generation (or
    None when not rotating), the next future accumulator, and the older
    generation with this phase's scheduled collectives applied.  The
    bookkeeping runs under the scope ``deft_route``, each sync under its
    own :func:`_sync_scope`.
    """
    def synced(x, b, gen):
        with _sync_scope(phase, b, gen):
            return sync(x, b)

    if phase.rotate:
        # fresh generation merges with the future accumulator (Cases 3/4)
        with jax.named_scope("deft_route"):
            gen = [g + f for g, f in zip(g_flat, fut)]
        gen = [
            synced(x, b, "new") if phase.route_new[b] == "sync" else x
            for b, x in enumerate(gen)
        ]
        with jax.named_scope("deft_route"):
            new_fut = [jnp.zeros_like(f) for f in fut]
    else:
        # Cases 1/2: fresh gradients accumulate locally
        gen = None
        with jax.named_scope("deft_route"):
            new_fut = [f + g for f, g in zip(fut, g_flat)]

    # older generation buckets scheduled this phase (fwd Case 1 + bwd 2/3)
    cur_synced = [
        synced(c, b, "cur") if phase.sync_cur[b] else c
        for b, c in enumerate(cur)
    ]
    return gen, new_fut, cur_synced


def _fused_metrics(loss, parts, phase: PhaseSpec, dp_axes, n_dp: int):
    """Loss and aux parts ride ONE fused psum, stacked to a vector,
    under the scope ``deft_metrics``."""
    part_keys = sorted(parts)
    with jax.named_scope("deft_metrics"):
        stacked = jnp.stack([loss] + [parts[k] for k in part_keys])
        stacked = jax.lax.psum(stacked, dp_axes) / n_dp
    return {
        "loss": stacked[0],
        **{k: stacked[1 + j] for j, k in enumerate(part_keys)},
        "updated": jnp.asarray(phase.do_update),
        "k": jnp.asarray(phase.update_k, jnp.int32),
    }


def _cast_compute(params, compute_dtype):
    """Mixed-precision boundary of the flat engines: the master buffers
    are cast to the compute dtype at the static slice/reshape views, so
    the forward/backward runs in (e.g.) bf16 while the optimizer state
    stays full-precision (DESIGN.md §8).  Routed through the ONE cast
    site in kernels/quantize/ops.py (DESIGN.md §13) — both directions:
    a bf16sr resident master upcasts through the same call."""
    if compute_dtype is None:
        return params
    return jax.tree.map(lambda x: cast_compute(x, compute_dtype), params)


# ---------------------------------------------------------------------------
# Wire-precision edges (DESIGN.md §13)
# ---------------------------------------------------------------------------
def _layout_wire(layout: BucketLayout) -> Tuple[str, ...]:
    """Per-bucket wire dtype names; all-f32 when the layout carries no
    :class:`PrecisionPolicy`."""
    if layout.precision is None:
        return ("f32",) * layout.n_buckets
    return tuple(layout.precision.wire)


def _wire_sync(x: jax.Array, wire: str, collective) -> jax.Array:
    """Run a gradient-sum ``collective`` at a bucket's wire precision.

    * ``bf16`` genuinely halves the wire bytes: the reduction runs on
      bf16 values and the result is promoted back to f32 for routing and
      the optimizer.
    * ``int8`` projects the local contribution onto the blockwise int8
      grid and runs the sum in f32 — an int8 ring sum would overflow at
      the first hop, so this is value-exact emulation of the quantized
      wire (the knapsack and obs account the int8 representation's
      bytes; DESIGN.md §13).
    """
    if wire == "bf16":
        return collective(x.astype(jnp.bfloat16)).astype(jnp.float32)
    if wire == "int8":
        return collective(quantize_dequantize_int8(x.astype(jnp.float32)))
    return collective(x)


def _wire_gather(
    span: jax.Array, wire: str, gather, fwd_dtype
) -> jax.Array:
    """One param all-gather at a bucket's wire precision (the AG edge of
    the sharded flat engine).  ``fwd_dtype`` is what the forward reads
    (compute dtype, f32 by default): the gathered buffer is decoded back
    to it, so the wire dtype is invisible downstream.  int8 genuinely
    gathers int8 values plus the per-row f32 scales and dequantizes."""
    if wire == "int8":
        q, s = quantize_int8(span.astype(jnp.float32))
        full = dequantize_int8(gather(q), gather(s))
        return cast_compute(full, fwd_dtype)
    if wire == "bf16":
        return cast_compute(
            gather(cast_compute(span, jnp.bfloat16)), fwd_dtype
        )
    return gather(cast_compute(span, fwd_dtype))


# ---------------------------------------------------------------------------
# Fused DeFT phase body
# ---------------------------------------------------------------------------
def _deft_body_fused(
    state: TrainState,
    batch: Dict[str, jax.Array],
    *,
    cfg: ArchConfig,
    opt_spec: OptimizerSpec,
    phase: PhaseSpec,
    layout: BucketLayout,
    dp_axes: Tuple[str, ...],
    dp_sizes: Dict[str, int],
    rules: Dict,
    remat: bool,
    loss_chunk: int = 0,
    unroll: bool = False,
    secondary_chain: Optional[Tuple[int, ...]] = None,
) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """One DeFT phase over per-bucket flat buffers, inside shard_map.

    ``cur``/``fut`` arrive with the leading device axis stripped to 1 by
    the manual mapping; we work on index [0] and re-add it on return.
    Every tensor this body syncs is a whole bucket buffer — there is no
    per-leaf collective and no tree flatten/unflatten outside the update
    branch.  With ``secondary_chain`` the secondary-assigned buckets run
    their all-reduce over that device-order ring chain (DESIGN.md §14)
    instead of the shared mesh axis.
    """
    n_dp = 1
    for a in dp_axes:
        n_dp *= dp_sizes[a]
    params, opt = state["params"], state["opt"]
    with jax.named_scope("deft_model"), logical_rules(rules):
        (loss, parts), grads = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, batch, remat=remat,
                              loss_chunk=loss_chunk, unroll=unroll),
            has_aux=True,
        )(params)

    g_leaves, treedef = jax.tree_util.tree_flatten(grads)
    with jax.named_scope("deft_grads"):
        g_flat = flatten_buckets(layout, g_leaves)     # one buffer per bucket
    cur = [c[0] for c in state["cur"]]
    fut = [f[0] for f in state["fut"]]

    def sync(x: jax.Array, b: int) -> jax.Array:
        if phase.secondary[b]:
            return _sync_secondary(x, dp_axes, dp_sizes,
                                   chain=secondary_chain)
        return _sync_primary(x, dp_axes)

    gen, new_fut, cur_synced = _route_and_sync(phase, g_flat, cur, fut, sync)

    if phase.do_update:
        src = cur_synced if phase.update_source == "cur" else gen
        scale = 1.0 / (n_dp * phase.update_k)
        with jax.named_scope("deft_update"):
            grad_tree = jax.tree_util.tree_unflatten(
                treedef, unflatten_buckets(layout, src)
            )
            params, opt = apply_updates(opt_spec, params, grad_tree, opt,
                                        grad_scale=scale)
        with jax.named_scope("deft_route"):
            if phase.update_source == "cur" and gen is not None:
                new_cur = gen
            else:
                new_cur = [jnp.zeros_like(c) for c in cur_synced]
    elif phase.rotate:
        new_cur = gen
    else:
        new_cur = cur_synced

    metrics = _fused_metrics(loss, parts, phase, dp_axes, n_dp)
    new_state = {
        "params": params,
        "opt": opt,
        "cur": tuple(c[None] for c in new_cur),
        "fut": tuple(f[None] for f in new_fut),
    }
    return new_state, metrics


# ---------------------------------------------------------------------------
# Flat-resident DeFT phase body (params/opt as per-bucket flat buffers)
# ---------------------------------------------------------------------------
def _deft_body_flat(
    state: TrainState,
    batch: Dict[str, jax.Array],
    *,
    cfg: ArchConfig,
    opt_spec: OptimizerSpec,
    phase: PhaseSpec,
    layout: BucketLayout,
    segments: BucketSegments,
    treedef,
    dp_axes: Tuple[str, ...],
    dp_sizes: Dict[str, int],
    rules: Dict,
    remat: bool,
    loss_chunk: int = 0,
    unroll: bool = False,
    update_impl: Optional[str] = None,
    compute_dtype=None,
    master_dtype: Optional[str] = None,
    secondary_chain: Optional[Tuple[int, ...]] = None,
) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """One DeFT phase with params and optimizer moments resident as
    per-bucket flat f32 buffers (DESIGN.md §8).

    The forward reads params through static slice/reshape views of the
    buffers (no per-leaf copies survive fusion); the update phase applies
    the optimizer with one fused bucket-update kernel per bucket and the
    accumulator zeroing rides the same launch.  No per-leaf O(num_params)
    op sequence exists anywhere in the steady-state step.
    """
    n_dp = 1
    for a in dp_axes:
        n_dp *= dp_sizes[a]
    pbuf, opt = state["pbuf"], state["opt"]
    with jax.named_scope("deft_model"):
        params = jax.tree_util.tree_unflatten(
            treedef, unflatten_buckets(layout, pbuf)
        )
        params = _cast_compute(params, compute_dtype)
        with logical_rules(rules):
            (loss, parts), grads = jax.value_and_grad(
                lambda p: loss_fn(p, cfg, batch, remat=remat,
                                  loss_chunk=loss_chunk, unroll=unroll),
                has_aux=True,
            )(params)

    with jax.named_scope("deft_grads"):
        g_flat = flatten_buckets(layout, jax.tree_util.tree_leaves(grads))
    cur = [c[0] for c in state["cur"]]
    fut = [f[0] for f in state["fut"]]
    wire = _layout_wire(layout)

    def sync(x: jax.Array, b: int) -> jax.Array:
        if phase.secondary[b]:
            coll = lambda y: _sync_secondary(y, dp_axes, dp_sizes,
                                             chain=secondary_chain)
        else:
            coll = lambda y: _sync_primary(y, dp_axes)
        return _wire_sync(x, wire[b], coll)

    gen, new_fut, cur_synced = _route_and_sync(phase, g_flat, cur, fut, sync)

    if phase.do_update:
        src = cur_synced if phase.update_source == "cur" else gen
        # the consumed accumulator is replaced by the fresh generation
        # (rotate) or comes back zeroed fused from the update launch
        zero_grads = (phase.update_source == "new") or (gen is None)
        scale = 1.0 / (n_dp * phase.update_k)
        with jax.named_scope("deft_update"):
            pbuf, opt, zeroed = apply_bucket_updates(
                opt_spec, segments, pbuf, src, opt,
                grad_scale=scale, zero_grads=zero_grads, impl=update_impl,
                master_dtype=master_dtype,
            )
        if phase.update_source == "cur" and gen is not None:
            new_cur = gen
        else:
            new_cur = list(zeroed)
    elif phase.rotate:
        new_cur = gen
    else:
        new_cur = cur_synced

    metrics = _fused_metrics(loss, parts, phase, dp_axes, n_dp)
    new_state = {
        "pbuf": tuple(pbuf),
        "opt": opt,
        "cur": tuple(c[None] for c in new_cur),
        "fut": tuple(f[None] for f in new_fut),
    }
    return new_state, metrics


# ---------------------------------------------------------------------------
# Sharded flat-resident DeFT phase body (FSDP/RS engine, DESIGN.md §8)
# ---------------------------------------------------------------------------
def _deft_body_flat_rs(
    state: TrainState,
    batch: Dict[str, jax.Array],
    *,
    cfg: ArchConfig,
    opt_spec: OptimizerSpec,
    phase: PhaseSpec,
    layout: BucketLayout,
    segments: BucketSegments,
    treedef,
    dp_axes: Tuple[str, ...],
    shard_axis: str,
    dp_sizes: Dict[str, int],
    rules: Dict,
    remat: bool,
    loss_chunk: int = 0,
    unroll: bool = False,
    update_impl: Optional[str] = None,
    compute_dtype=None,
    master_dtype: Optional[str] = None,
    gather_reuse: Optional[Tuple[bool, ...]] = None,
    decoupled: bool = False,
    secondary_chain: Optional[Tuple[int, ...]] = None,
    ag_links: Optional[Tuple[bool, ...]] = None,
) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """One DeFT phase with params and optimizer moments SHARDED over
    ``shard_axis``: each device holds one contiguous 1/N span of every
    flat bucket buffer (``layout.shard_sizes``), ZeRO-style.

    * the forward all-gathers the updated param shards into full flat
      buffers and reads the tree through the usual static views;
      with ``gather_reuse[b]`` set (the gather-skip path, DESIGN.md §9)
      bucket ``b``'s gather is skipped and the full buffer is read from
      the ``pgather`` cache the previous phase stored — valid exactly
      when no update touched the params since that stored gather, a
      per-bucket generation tag that is STATIC per cycle position
      (updates are scheduled, not data-dependent), so the skip costs
      zero runtime bookkeeping;
    * with ``decoupled`` (DESIGN.md §12) the gathers are not issued as
      one up-front burst: each bucket's all-gather is traced at its
      first forward leaf access via the lazy param view, streaming AG
      traffic against forward compute (composes with ``gather_reuse`` —
      a skipped bucket reads the cache and emits no AG at all);
    * scheduled syncs are hierarchical by construction — reduce-scatter
      over ``shard_axis`` into shard-local buffers, all-reduce over the
      outer (pod/DCN) axes, all-gather back ONLY when the synced buffer
      must be stored full (a later phase consumes it).  A bucket synced
      and consumed in the same phase feeds its shard-local reduction
      straight to the update kernel with no trailing all-gather;
    * the fused bucket-update kernels run on the shard-local p/m/v spans
      (segment maps sliced per shard, clip norm psum'd across shards),
      so optimizer state stays 1/N-resident for the whole run.

    ``cur``/``fut`` stay full-length per-device accumulators: an
    unsynchronized generation holds contributions to EVERY span, which a
    later reduce-scatter folds into the owning shard.

    With ``secondary_chain`` (DESIGN.md §14) the per-link plan becomes
    executable: a bucket the scheduler assigned to the secondary link
    (``phase.secondary[b]``) runs its shard-axis reduce-scatter and any
    trailing all-gather over that device-order ring chain; a bucket whose
    streamed param AG was placed on the secondary link
    (``ag_links[b]``, from ``AgItem.link``) gathers over the chain too.
    The outer pod all-reduce is untouched — chain collectives are
    bitwise-equal to the single-axis ones they replace (train/chains.py),
    so routing never perturbs training.
    """
    n_dp = 1
    for a in dp_axes:
        n_dp *= dp_sizes[a]
    outer_axes = tuple(a for a in dp_axes if a != shard_axis)
    shard_id = jax.lax.axis_index(shard_axis)
    spans = layout.shard_sizes

    pbuf_sh, opt = state["pbuf"], state["opt"]
    # ZeRO forward: re-materialize full param buffers from the shards.
    # Mixed precision casts each span down BEFORE the gather — the cast
    # is elementwise so the params are bit-identical, and the param
    # all-gather (the engine's dominant per-phase comm term) moves half
    # the bytes in bf16 instead of shipping f32 and casting after.
    # Buckets flagged in ``gather_reuse`` skip the collective entirely
    # and read the previous phase's stored gather (bit-identical: params
    # did not change in between, by the static schedule).  With a
    # precision policy on the layout, each bucket's gather runs at its
    # wire dtype (bf16 half-width; int8 values + per-row scales) and is
    # decoded back to the forward dtype after the collective (§13).
    wire = _layout_wire(layout)
    fwd_dtype = compute_dtype if compute_dtype is not None else jnp.float32
    chained = lambda b: (
        secondary_chain is not None and ag_links is not None and ag_links[b]
    )
    ag_ = lambda x: jax.lax.all_gather(x, shard_axis, axis=0, tiled=True)
    ag_chain = lambda x: chain_all_gather(x, shard_axis, secondary_chain)

    def gather_bucket(b: int) -> jax.Array:
        with _gather_scope(b, bool(ag_links and ag_links[b])):
            return _wire_gather(
                pbuf_sh[b], wire[b], ag_chain if chained(b) else ag_,
                fwd_dtype,
            )

    cache = state.get("pgather")
    reuse = gather_reuse if (cache is not None and gather_reuse) \
        else (False,) * layout.n_buckets
    nb_ = layout.n_buckets
    if decoupled:
        # Decoupled AG streaming (DESIGN.md §12): no up-front gather
        # burst.  Params are a lazy per-bucket view — bucket ``b``'s
        # all-gather is traced at the FIRST forward leaf access, so the
        # jaxpr interleaves one AG per bucket with the forward blocks
        # that consume it (matching the planner's deadline items).
        # Gradients come from the zeros trick: differentiate w.r.t. a
        # full-size zero buffer added onto each gathered bucket — the
        # transpose of the disjoint leaf slices scatter-adds every leaf
        # cotangent into its span, i.e. ``flatten_buckets`` of the leaf
        # grads, bit-for-bit (cast commutes with concat elementwise),
        # without ever differentiating through the collective.
        zbufs = tuple(
            jnp.zeros((s,), fwd_dtype) for s in layout.buf_sizes
        )

        def run(z):
            gathered: Dict[int, jax.Array] = {}
            full: Dict[int, jax.Array] = {}

            def full_buf(b: int) -> jax.Array:
                if b not in full:
                    g = cache[b] if reuse[b] else gather_bucket(b)
                    gathered[b] = g
                    full[b] = g + z[b]
                return full[b]

            params = lazy_param_tree(treedef, layout, full_buf)
            loss, parts = loss_fn(params, cfg, batch, remat=remat,
                                  loss_chunk=loss_chunk, unroll=unroll)
            # a bucket the forward never read still needs its gather
            # for the pgather cache; its z-gradient stays zero
            for b in range(nb_):
                full_buf(b)
            return loss, (parts, tuple(gathered[b] for b in range(nb_)))

        with jax.named_scope("deft_model"), logical_rules(rules):
            (loss, (parts, pbuf_t)), gz = jax.value_and_grad(
                run, has_aux=True
            )(zbufs)
        pbuf = list(pbuf_t)
        with jax.named_scope("deft_grads"):
            g_flat = [g.astype(jnp.float32) for g in gz]
    else:
        pbuf = [
            cache[b] if reuse[b] else gather_bucket(b)
            for b in range(nb_)
        ]
        with jax.named_scope("deft_model"):
            params = jax.tree_util.tree_unflatten(
                treedef, unflatten_buckets(layout, pbuf)
            )
            with logical_rules(rules):
                (loss, parts), grads = jax.value_and_grad(
                    lambda p: loss_fn(p, cfg, batch, remat=remat,
                                      loss_chunk=loss_chunk, unroll=unroll),
                    has_aux=True,
                )(params)

        with jax.named_scope("deft_grads"):
            g_flat = flatten_buckets(
                layout, jax.tree_util.tree_leaves(grads))
    cur = [c[0] for c in state["cur"]]
    fut = [f[0] for f in state["fut"]]

    def rs_shard(x: jax.Array, b: int) -> jax.Array:
        """Shard-local half of the hierarchical sync: reduce-scatter over
        the fast shard axis, all-reduce across the outer axes — run at
        bucket ``b``'s wire precision (§13).  A secondary-assigned bucket
        rides the secondary link's ring chain when one is configured
        (§14); the outer pod all-reduce stays on its own fabric either
        way, so the chain never has to split a joint-axis reduction."""
        on_chain = secondary_chain is not None and phase.secondary[b]

        def coll(v: jax.Array) -> jax.Array:
            if on_chain:
                y = chain_reduce_scatter(v, shard_axis, secondary_chain)
            else:
                y = jax.lax.psum_scatter(
                    v, shard_axis, scatter_dimension=0, tiled=True
                )
            if outer_axes:
                y = jax.lax.psum(y, outer_axes)
            return y

        return _wire_sync(x, wire[b], coll)

    def gather(y: jax.Array, b: int) -> jax.Array:
        """Trailing all-gather of a synced-and-stored bucket — on the
        same link its reduce-scatter used."""
        with _gather_scope(b, phase.secondary[b]):
            if secondary_chain is not None and phase.secondary[b]:
                return chain_all_gather(y, shard_axis, secondary_chain)
            return jax.lax.all_gather(y, shard_axis, axis=0, tiled=True)

    def slice_shard(x: jax.Array, b: int) -> jax.Array:
        """This device's span of an already-summed full buffer."""
        return jax.lax.dynamic_slice(x, (shard_id * spans[b],), (spans[b],))

    # --- routing: same generation bookkeeping as _route_and_sync, but
    # the shard-local reduction is kept alongside so the update path can
    # consume it without paying the all-gather --------------------------
    consumed_new = phase.do_update and phase.update_source == "new"
    consumed_cur = phase.do_update and phase.update_source == "cur"
    nb = layout.n_buckets
    gen_sh: List[Optional[jax.Array]] = [None] * nb
    cur_sh: List[Optional[jax.Array]] = [None] * nb
    if phase.rotate:
        with jax.named_scope("deft_route"):
            gen_pre = [g + f for g, f in zip(g_flat, fut)]
        gen = []
        for b, x in enumerate(gen_pre):
            if phase.route_new[b] == "sync":
                with _sync_scope(phase, b, "new"):
                    gen_sh[b] = rs_shard(x, b)
                # stored full only when this generation survives the
                # phase (it becomes new_cur); a consumed one stays 1/N
                gen.append(x if consumed_new else gather(gen_sh[b], b))
            else:
                gen.append(x)
        with jax.named_scope("deft_route"):
            new_fut = [jnp.zeros_like(f) for f in fut]
    else:
        gen = None
        with jax.named_scope("deft_route"):
            new_fut = [f + g for f, g in zip(fut, g_flat)]
    cur_synced = []
    for b, c in enumerate(cur):
        if phase.sync_cur[b]:
            with _sync_scope(phase, b, "cur"):
                cur_sh[b] = rs_shard(c, b)
            cur_synced.append(c if consumed_cur else gather(cur_sh[b], b))
        else:
            cur_synced.append(c)

    if phase.do_update:
        src = cur_synced if consumed_cur else gen
        src_shards = cur_sh if consumed_cur else gen_sh
        # shard-local merged gradient: the fresh reduce-scatter result
        # where this phase synced the bucket, else this device's span of
        # the stored (already-summed) accumulator
        with jax.named_scope("deft_route"):
            src_sh = [
                src_shards[b] if src_shards[b] is not None
                else slice_shard(src[b], b)
                for b in range(nb)
            ]
        scale = 1.0 / (n_dp * phase.update_k)
        with jax.named_scope("deft_update"):
            pbuf_sh, opt, _ = apply_bucket_updates(
                opt_spec, segments, pbuf_sh, src_sh, opt,
                grad_scale=scale, zero_grads=False, impl=update_impl,
                shard_id=shard_id,
                norm_psum=lambda t: jax.lax.psum(t, shard_axis),
                master_dtype=master_dtype,
            )
        pbuf_sh = list(pbuf_sh)
        with jax.named_scope("deft_route"):
            if consumed_cur and gen is not None:
                new_cur = gen
            else:
                new_cur = [jnp.zeros_like(c) for c in cur_synced]
    elif phase.rotate:
        new_cur = gen
    else:
        new_cur = cur_synced

    metrics = _fused_metrics(loss, parts, phase, dp_axes, n_dp)
    new_state = {
        "pbuf": tuple(pbuf_sh),
        "opt": opt,
        "cur": tuple(c[None] for c in new_cur),
        "fut": tuple(f[None] for f in new_fut),
    }
    if cache is not None:
        # store this phase's gathered buffers for the next phase's skip
        # decision (stale after an update — the static reuse mask never
        # reads a stale entry)
        new_state["pgather"] = tuple(pbuf)
    return new_state, metrics


# ---------------------------------------------------------------------------
# shard_map wrappers (fused variants of steps.deft_phase_step / _rs_)
# ---------------------------------------------------------------------------
# steps._state_specs is layout-agnostic (params/opt replicated, cur/fut
# split on the leading device axis) and works unchanged on the fused
# tuple-shaped accumulators.
_fused_state_specs = _state_specs



def _flat_state_specs(state: TrainState, dp_axes: Tuple[str, ...]):
    """Manual-axis specs for the flat-resident state: param buffers and
    optimizer moments replicated over DP, accumulators split on their
    leading device axis."""
    rep = jax.tree.map(
        lambda _: P(), {"pbuf": state["pbuf"], "opt": state["opt"]}
    )
    acc = jax.tree.map(
        lambda _: P(dp_axes if len(dp_axes) > 1 else dp_axes[0]),
        {"cur": state["cur"], "fut": state["fut"]},
    )
    return {**rep, **acc}


def _flat_rs_state_specs(
    state: TrainState, dp_axes: Tuple[str, ...], shard_axis: str
):
    """Manual-axis specs for the SHARDED flat-resident state: param and
    moment buffers split over the shard axis (each device holds one
    contiguous span), the step counter replicated, accumulators split on
    their leading device axis as usual."""
    shard = jax.tree.map(
        lambda x: P() if x.ndim == 0 else P(shard_axis),
        {"pbuf": state["pbuf"], "opt": state["opt"]},
    )
    acc = jax.tree.map(
        lambda _: P(dp_axes if len(dp_axes) > 1 else dp_axes[0]),
        {"cur": state["cur"], "fut": state["fut"]},
    )
    out = {**shard, **acc}
    if "pgather" in state:
        # the gather cache holds full (post-all-gather) buffers — the
        # same value on every device, i.e. replicated
        out["pgather"] = jax.tree.map(lambda _: P(), state["pgather"])
    return out


def _shard_phase(body, specs_fn, state, batch, mesh, dp_axes):
    """The one shard_map invocation every phase wrapper shares (state
    specs from ``specs_fn``, batch split over DP, fused metric specs)."""
    state_specs = specs_fn(state, dp_axes)
    # size-1 axes go manual too: a Pallas kernel (attention, bucket
    # update) cannot be partitioned over an auto axis, and a size-1 axis
    # shards nothing
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    manual = set(dp_axes) | {a for a, n in shape.items() if n == 1}
    new_state, metrics = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(state_specs, _batch_specs(batch, dp_axes)),
        # every metric (the loss's parts, a model's expert counters
        # included) is a replicated scalar after the fused psum
        out_specs=(state_specs, P()),
        axis_names=manual,
        check_vma=False,
    )(state, batch)
    # pin the state's output placement to its input placement: left to
    # the partitioner, an auto (non-manual) axis may come back split (a
    # bucket sharded over 'model'), and a donated buffer aliases only an
    # output of the same sharding
    new_state = jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, s)),
        new_state, state_specs,
    )
    return new_state, metrics


def deft_phase_step_flat(
    state: TrainState,
    batch: Dict[str, jax.Array],
    *,
    cfg: ArchConfig,
    opt_spec: OptimizerSpec,
    phase: PhaseSpec,
    layout: BucketLayout,
    segments: BucketSegments,
    treedef,
    mesh,
    multi_pod: bool = False,
    remat: bool = True,
    loss_chunk: int = 0,
    unroll: bool = False,
    update_impl: Optional[str] = None,
    compute_dtype=None,
    master_dtype: Optional[str] = None,
    secondary_chain: Optional[Tuple[int, ...]] = None,
) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """Flat-resident DeFT phase with explicit DP (params replicated)."""
    dp_axes = ("pod", "data") if multi_pod else ("data",)
    body = functools.partial(
        _deft_body_flat,
        cfg=cfg,
        opt_spec=opt_spec,
        phase=phase,
        layout=layout,
        segments=segments,
        treedef=treedef,
        dp_axes=dp_axes,
        dp_sizes=_dp_sizes(mesh, dp_axes),
        rules=rules_deft_manual_dp(),
        remat=remat,
        loss_chunk=loss_chunk,
        unroll=unroll,
        update_impl=update_impl,
        compute_dtype=compute_dtype,
        master_dtype=master_dtype,
        secondary_chain=secondary_chain,
    )
    return _shard_phase(body, _flat_state_specs, state, batch, mesh, dp_axes)


def deft_rs_phase_step_flat(
    state: TrainState,
    batch: Dict[str, jax.Array],
    *,
    cfg: ArchConfig,
    opt_spec: OptimizerSpec,
    phase: PhaseSpec,
    layout: BucketLayout,
    segments: BucketSegments,
    treedef,
    mesh,
    remat: bool = True,
    loss_chunk: int = 0,
    unroll: bool = False,
    update_impl: Optional[str] = None,
    compute_dtype=None,
    master_dtype: Optional[str] = None,
    gather_reuse: Optional[Tuple[bool, ...]] = None,
    decoupled: bool = False,
    secondary_chain: Optional[Tuple[int, ...]] = None,
    ag_links: Optional[Tuple[bool, ...]] = None,
) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """Sharded flat-resident DeFT phase (the FSDP/RS engine): manual over
    every DP axis, param/moment buffers split 1/N over the innermost
    ('data') axis, hierarchical RS -> pod all-reduce -> AG syncs.

    Unlike the tree-state RS path (manual over 'pod' only, FSDP left to
    XLA), the whole DP hierarchy is explicit here, so the engine also
    runs on single-pod meshes — 'pod' is simply absent from the sync.
    """
    shard_axis = "data"
    assert shard_axis in mesh.axis_names, "sharded flat engine needs 'data'"
    dp_axes = (
        ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    )
    body = functools.partial(
        _deft_body_flat_rs,
        cfg=cfg,
        opt_spec=opt_spec,
        phase=phase,
        layout=layout,
        segments=segments,
        treedef=treedef,
        dp_axes=dp_axes,
        shard_axis=shard_axis,
        dp_sizes=_dp_sizes(mesh, dp_axes),
        rules=rules_deft_manual_dp(),
        remat=remat,
        loss_chunk=loss_chunk,
        unroll=unroll,
        update_impl=update_impl,
        compute_dtype=compute_dtype,
        master_dtype=master_dtype,
        gather_reuse=gather_reuse,
        decoupled=decoupled,
        secondary_chain=secondary_chain,
        ag_links=ag_links,
    )
    specs_fn = lambda s, axes: _flat_rs_state_specs(s, axes, shard_axis)
    return _shard_phase(body, specs_fn, state, batch, mesh, dp_axes)


def deft_phase_step_fused(
    state: TrainState,
    batch: Dict[str, jax.Array],
    *,
    cfg: ArchConfig,
    opt_spec: OptimizerSpec,
    phase: PhaseSpec,
    layout: BucketLayout,
    mesh,
    multi_pod: bool = False,
    remat: bool = True,
    loss_chunk: int = 0,
    unroll: bool = False,
    secondary_chain: Optional[Tuple[int, ...]] = None,
) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """Fused DeFT phase with explicit DP (params replicated over DP)."""
    dp_axes = ("pod", "data") if multi_pod else ("data",)
    body = functools.partial(
        _deft_body_fused,
        cfg=cfg,
        opt_spec=opt_spec,
        phase=phase,
        layout=layout,
        dp_axes=dp_axes,
        dp_sizes=_dp_sizes(mesh, dp_axes),
        rules=rules_deft_manual_dp(),
        remat=remat,
        loss_chunk=loss_chunk,
        unroll=unroll,
        secondary_chain=secondary_chain,
    )
    return _shard_phase(body, _fused_state_specs, state, batch, mesh, dp_axes)


def deft_rs_phase_step_fused(
    state: TrainState,
    batch: Dict[str, jax.Array],
    *,
    cfg: ArchConfig,
    opt_spec: OptimizerSpec,
    phase: PhaseSpec,
    layout: BucketLayout,
    mesh,
    remat: bool = True,
    loss_chunk: int = 0,
    unroll: bool = False,
) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """Fused DeFT hierarchical path (FSDP archs): manual over 'pod' only."""
    assert "pod" in mesh.axis_names, "DeFT-RS needs the multi-pod mesh"
    dp_axes = ("pod",)
    body = functools.partial(
        _deft_body_fused,
        cfg=cfg,
        opt_spec=opt_spec,
        phase=phase,
        layout=layout,
        dp_axes=dp_axes,
        dp_sizes=_dp_sizes(mesh, dp_axes),
        rules=rules_deft_rs_manual_pod(),
        remat=remat,
        loss_chunk=loss_chunk,
        unroll=unroll,
    )
    return _shard_phase(body, _fused_state_specs, state, batch, mesh, dp_axes)


# ---------------------------------------------------------------------------
# Collective accounting (static, from the phase spec)
# ---------------------------------------------------------------------------
# an HLO instruction that calls a Pallas TPU kernel; group 1 is the name
# the kernel was given (``pallas_call(name=...)``)
_TPU_KERNEL_RE = re.compile(
    r"%([\w-]+?)(?:\.\d+)* = [^\n]*custom_call_target=\"tpu_custom_call\""
)


def phase_collectives(phase: PhaseSpec) -> Dict[str, int]:
    """Collectives one fused phase issues, by construction: one primary
    psum per primary-synced bucket, one reduce-scatter chain per
    secondary-synced bucket, plus the single fused metrics psum.

    On the sharded flat engine every sync is one hierarchical chain
    (these counts still bound the per-bucket syncs), plus one param
    all-gather per bucket for the ZeRO forward — see DESIGN.md §8."""
    n = len(phase.route_new)
    synced = [
        (phase.route_new[b] == "sync" and phase.rotate) or phase.sync_cur[b]
        for b in range(n)
    ]
    primary = sum(1 for b in range(n) if synced[b] and not phase.secondary[b])
    secondary = sum(1 for b in range(n) if synced[b] and phase.secondary[b])
    return {"primary": primary, "secondary": secondary, "metrics": 1}


# ---------------------------------------------------------------------------
# The runtime
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class PhaseStats:
    """Per-unique-phase lifecycle stats."""

    lower_s: float = 0.0
    compile_s: float = 0.0
    dispatches: int = 0
    dispatch_s: float = 0.0


def _abstractify(x):
    """Shape/dtype/sharding snapshot of a (possibly soon-donated) array;
    passes ShapeDtypeStructs and non-array leaves through unchanged."""
    if isinstance(x, jax.ShapeDtypeStruct) or not hasattr(x, "dtype"):
        return x
    sharding = getattr(x, "sharding", None)
    try:
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
    except TypeError:  # older jax: no sharding kwarg
        return jax.ShapeDtypeStruct(x.shape, x.dtype)


class _PhaseEntry:
    """One unique (layout, PhaseSpec, gather-mask) executable lifecycle:
    the donated jitted callable, its AOT-compiled executable (once built)
    and stats.  Entries live in the runtime's *persistent* phase cache —
    a replanned schedule that reuses a PhaseSpec under the same layout
    reuses its compiled executable verbatim, including across layout
    swaps that later return to a previously-seen layout."""

    __slots__ = ("spec", "jitted", "compiled", "batch_sharding", "stats")

    def __init__(self, spec: PhaseSpec, jitted: Callable):
        self.spec = spec
        self.jitted = jitted
        self.compiled: Optional[Callable] = None
        # the executable's own batch input sharding: a batch arriving
        # committed elsewhere (host, or the mesh before an elastic
        # migration) is placed onto it before dispatch
        self.batch_sharding: Any = None
        self.stats = PhaseStats()


@dataclasses.dataclass
class _PendingSwap:
    """A fully-compiled staged schedule, armed for the next cycle
    boundary.  ``layout`` is None for the classic same-layout hot-swap;
    otherwise ``repack`` is the AOT-compiled single-pass gather/scatter
    that re-flattens the donated train state from the installed layout
    into ``layout`` (DESIGN.md §9)."""

    schedule: DeftSchedule
    layout: Optional[BucketLayout] = None
    segments: Optional[BucketSegments] = None
    transition: Optional[LayoutTransition] = None
    repack: Optional[Callable] = None


_UNSET: Any = object()


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Engine configuration of a :class:`DeftRuntime` — every knob that
    used to be a loose ``DeftRuntime(...)`` kwarg, as one frozen value.

    Known-illegal combinations raise at CONSTRUCTION (``validate``), not
    deep inside phase dispatch: ``gather_skip``/``decoupled`` need the
    sharded flat engine, mixed precision needs flat-resident buffers.
    :meth:`DeftRuntime.spawn` derives sibling runtimes via
    :meth:`replace`, so elastic/degraded-mode dispatch composes overrides
    on a validated base instead of re-threading ten kwargs.

    ``flat_state``/``gather_skip`` keep their tri-state semantics: None
    means "resolve the default" (flat state on; gather skip on for the
    sharded flat engine when the schedule has a reusable position).
    ``decoupled`` (DESIGN.md §12) selects the streamed-AG forward on the
    sharded flat engine: per-bucket all-gathers traced at first forward
    use instead of the up-front ZeRO gather burst.
    """

    multi_pod: bool = False
    fsdp: bool = False
    remat: bool = True
    loss_chunk: int = 0
    unroll: bool = False
    donate: bool = True
    flat_state: Optional[bool] = None
    update_impl: Optional[str] = None
    compute_dtype: Any = None
    gather_skip: Optional[bool] = None
    decoupled: bool = False
    # resident master dtype (DESIGN.md §13): None/'f32' keeps the f32
    # master buffers; 'bf16sr' stores them bf16 and writes updates back
    # through seeded stochastic rounding (flat engines only)
    master_dtype: Optional[str] = None
    # secondary-link device-order ring chain (DESIGN.md §14): a
    # permutation of the 'data'-axis positions (launch.mesh.ring_chain).
    # None (default) keeps every collective on the mesh axis — the
    # pre-§14 behavior bit-for-bit.  When set, secondary-assigned
    # RS/AG items execute as ppermute chains over this ordering.
    secondary_chain: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        if self.secondary_chain is not None:
            # normalize (lists hash-break the frozen config) before
            # validate sees it
            object.__setattr__(
                self, "secondary_chain",
                tuple(int(p) for p in self.secondary_chain),
            )
        self.validate()

    @property
    def resolved_flat_state(self) -> bool:
        return True if self.flat_state is None else bool(self.flat_state)

    @property
    def sharded_flat(self) -> bool:
        """The FSDP/RS engine: flat buffers sharded 1/N over 'data'."""
        return bool(self.fsdp and self.resolved_flat_state)

    def validate(self) -> None:
        if self.loss_chunk < 0:
            raise ValueError(f"loss_chunk={self.loss_chunk} must be >= 0")
        if self.gather_skip and not self.sharded_flat:
            raise ValueError(
                "gather_skip only applies to the sharded flat engine "
                "(fsdp=True, flat_state=True) — the other engines never "
                "all-gather params"
            )
        if self.decoupled and not self.sharded_flat:
            raise ValueError(
                "decoupled AG streaming only applies to the sharded flat "
                "engine (fsdp=True, flat_state=True) — the other engines "
                "have no per-bucket param all-gather to stream "
                "(DESIGN.md §12)"
            )
        if self.compute_dtype is not None and self.flat_state is False:
            raise ValueError(
                "compute_dtype (mixed precision) needs the flat engine: "
                "tree-state params are resident at their init dtype — "
                "drop flat_state=False or drop compute_dtype (DESIGN.md §8)"
            )
        if self.update_impl is not None and self.flat_state is False:
            raise ValueError(
                "update_impl selects a fused bucket-update kernel — only "
                "the flat engine runs those; flat_state=False applies "
                "per-leaf updates"
            )
        if self.master_dtype not in (None, "f32", "bf16sr"):
            raise ValueError(
                f"master_dtype={self.master_dtype!r}: expected None, "
                f"'f32' or 'bf16sr'"
            )
        if self.master_dtype == "bf16sr" and self.flat_state is False:
            raise ValueError(
                "master_dtype='bf16sr' needs the flat engine: the "
                "stochastic-rounding write-back rides the fused "
                "bucket-update kernels (DESIGN.md §13)"
            )
        if self.secondary_chain is not None:
            chain = self.secondary_chain
            if sorted(chain) != list(range(len(chain))):
                raise ValueError(
                    f"secondary_chain={chain} is not a permutation of "
                    f"0..{len(chain) - 1} — build it with "
                    f"launch.mesh.ring_chain"
                )
            if self.fsdp and self.flat_state is False:
                raise ValueError(
                    "secondary_chain needs a 'data'-axis sync to reroute; "
                    "the tree-state RS engine is manual over 'pod' only "
                    "(DESIGN.md §14) — use the flat engines"
                )
            if self.multi_pod and not self.sharded_flat:
                raise ValueError(
                    "secondary_chain on a multi-pod mesh needs the "
                    "sharded flat engine: its shard-axis reduce-scatter "
                    "is separate from the pod all-reduce, so the chain "
                    "swaps in bitwise-exactly.  The replicated engines "
                    "sync with ONE joint ('pod','data') psum whose "
                    "reduction order a per-axis chain cannot reproduce "
                    "(DESIGN.md §14)"
                )

    @property
    def resolved_master(self) -> str:
        return self.master_dtype or "f32"

    def replace(self, **overrides) -> "RuntimeConfig":
        """A new validated config with ``overrides`` applied."""
        return dataclasses.replace(self, **overrides)


class DeftRuntime:
    """Owns the per-phase executables of one (evolving) DeFT schedule.

    Lifecycle (DESIGN.md §5/§7):

    1. construction dedupes ``schedule.phases`` by spec signature and
       builds one donated jitted callable per *unique* phase;
    2. :meth:`compile` lowers + compiles each unique phase ahead of time
       against concrete (or abstract) state/batch, recording timings;
    3. :meth:`step` dispatches the step's cycle phase through the AOT
       cache (falling back to the jitted callable if :meth:`compile` was
       skipped — first dispatch then pays the compile);
    4. :meth:`prepare_swap` stages a replanned schedule: unseen phases
       are lowered + compiled (optionally on a background thread while
       training continues), previously-seen phases are reused from the
       persistent cache, and the new schedule is installed atomically at
       the next cycle boundary.  Over the same :class:`BucketLayout` the
       donated train state carries across untouched (every buffer keeps
       its shape and sharding); with ``layout=`` the state is re-packed
       through a compiled :class:`LayoutTransition` at that boundary
       (DESIGN.md §9), so a replan may change the bucket partition or
       the shard count mid-run with no restart.

    All phase executables donate the train state: callers MUST treat the
    state passed to :meth:`step` as consumed and continue with the
    returned one.
    """

    def __init__(
        self,
        cfg: ArchConfig,
        opt_spec: OptimizerSpec,
        schedule: DeftSchedule,
        layout: BucketLayout,
        mesh,
        *,
        config: Optional[RuntimeConfig] = None,
        tracer: Optional[Tracer] = None,
        ag_plan: Any = None,
        multi_pod: Any = _UNSET,
        fsdp: Any = _UNSET,
        remat: Any = _UNSET,
        loss_chunk: Any = _UNSET,
        unroll: Any = _UNSET,
        donate: Any = _UNSET,
        flat_state: Any = _UNSET,
        update_impl: Any = _UNSET,
        compute_dtype: Any = _UNSET,
        gather_skip: Any = _UNSET,
        decoupled: Any = _UNSET,
    ):
        # engine knobs arrive either as one validated RuntimeConfig or as
        # the legacy loose kwargs (kept working; they build the config) —
        # mixing the two is ambiguous and refused
        legacy = {
            k: v
            for k, v in dict(
                multi_pod=multi_pod, fsdp=fsdp, remat=remat,
                loss_chunk=loss_chunk, unroll=unroll, donate=donate,
                flat_state=flat_state, update_impl=update_impl,
                compute_dtype=compute_dtype, gather_skip=gather_skip,
                decoupled=decoupled,
            ).items()
            if v is not _UNSET
        }
        if config is None:
            config = RuntimeConfig(**legacy)
        elif legacy:
            raise ValueError(
                f"pass engine knobs through config=RuntimeConfig(...) OR "
                f"as legacy kwargs, not both (got config= and "
                f"{sorted(legacy)})"
            )
        self.config = config
        self.cfg = cfg
        self.opt_spec = opt_spec
        self.layout = layout
        self.mesh = mesh
        self.fsdp = config.fsdp
        self.multi_pod = config.multi_pod
        self.donate = config.donate
        self._remat = config.remat
        self._loss_chunk = config.loss_chunk
        self._unroll = config.unroll
        # flat-resident state (DESIGN.md §8): the default everywhere.
        # On the FSDP/RS path the flat engine SHARDS the param/moment
        # buffers 1/N over 'data' (shard-aware BucketLayout) instead of
        # replicating them, so the memory-bound archs keep their ZeRO
        # residency and still get the fused bucket-update kernels.
        self.flat_state = config.resolved_flat_state
        self.update_impl = config.update_impl
        # mixed precision (flat engines only): forward/backward in
        # compute_dtype against the f32 master buffers
        self.compute_dtype = config.compute_dtype
        # precision as a layout dimension (DESIGN.md §13): per-bucket
        # wire dtypes ride layout.precision; the resident-master dtype is
        # config-owned and must agree with what the layout declares
        lp_master = (
            layout.precision.master if layout.precision is not None else None
        )
        if (config.master_dtype is not None and lp_master is not None
                and config.master_dtype != lp_master):
            raise ValueError(
                f"master dtype disagreement: config.master_dtype="
                f"{config.master_dtype!r} but the layout's precision "
                f"policy says {lp_master!r}"
            )
        self.master_dtype = config.master_dtype or lp_master or "f32"
        self._master_jdtype = (
            jnp.bfloat16 if self.master_dtype == "bf16sr" else jnp.float32
        )
        quantized = layout.precision is not None and (
            not layout.precision.all_f32
        )
        if (quantized or self.master_dtype != "f32") \
                and not config.resolved_flat_state:
            raise ValueError(
                "a non-f32 PrecisionPolicy needs the flat engines: the "
                "tree-state path has no per-bucket wire edges "
                "(DESIGN.md §13) — drop flat_state=False"
            )
        self._validate_precision_layout(layout)
        # decoupled AG streaming (DESIGN.md §12): per-bucket forward
        # all-gathers at first use instead of the up-front ZeRO burst
        self.decoupled = config.decoupled
        self._treedef = None
        self._segments: Optional[BucketSegments] = None
        shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        # secondary-link ring chain (DESIGN.md §14): a permutation of the
        # 'data'-axis positions; the AG-link plan says which streamed
        # param gathers ride it (gradient syncs follow phase.secondary)
        if config.secondary_chain is not None:
            n_data = int(shape.get("data", 0))
            if len(config.secondary_chain) != n_data:
                raise ValueError(
                    f"secondary_chain covers "
                    f"{len(config.secondary_chain)} positions but the "
                    f"mesh 'data' axis is {n_data}-way — build it with "
                    f"launch.mesh.ring_chain({n_data}, link)"
                )
        self._ag_plan = ag_plan
        if self.flat_state:
            params_abs = jax.eval_shape(
                lambda: init_params(jax.random.PRNGKey(0), cfg)
            )
            leaves, self._treedef = jax.tree_util.tree_flatten(params_abs)
            assert tuple(tuple(l.shape) for l in leaves) == layout.shapes, (
                "BucketLayout does not match this config's parameter tree"
            )
            self._segments = build_segments(layout, opt_spec)
        if self.flat_state and self.fsdp:
            n_shards = int(shape["data"])
            if layout.shards != n_shards:
                raise ValueError(
                    f"sharded flat engine: BucketLayout was built with "
                    f"shard_count={layout.shards} but the mesh 'data' axis "
                    f"is {n_shards}-way — build the layout with "
                    f"build_bucket_layout(..., shard_count={n_shards})"
                )
        if self.fsdp:
            # tree state: manual over 'pod' only (FSDP left to XLA);
            # sharded flat state: the whole DP hierarchy is explicit
            if self.flat_state:
                self.dp_axes: Tuple[str, ...] = (
                    ("pod", "data") if "pod" in mesh.axis_names
                    else ("data",)
                )
            else:
                self.dp_axes = ("pod",)
        else:
            self.dp_axes = ("pod", "data") if self.multi_pod else ("data",)
        self.accum_devices = 1
        for a in self.dp_axes:
            self.accum_devices *= int(shape[a])
        # ZeRO gather skip (DESIGN.md §9): default ON for the sharded
        # flat engine — phases not preceded by an update reuse the
        # previous phase's stored param gather instead of re-all-gathering.
        # The cache rides the donated state, so it is only worth carrying
        # when the installed schedule actually HAS a reusable position;
        # otherwise every phase would haul an unread (hence undonatable)
        # full-param cache through each step for nothing.
        # illegal combinations already refused by RuntimeConfig.validate
        self._gather_skip = bool(
            config.gather_skip if config.gather_skip is not None
            else (self.fsdp and self.flat_state
                  and self._schedule_has_reuse(schedule))
        )

        # persistent phase cache: (layout, PhaseSpec, gather-mask) ->
        # executable entry.  Survives hot-swaps — including layout
        # changes; schedules only reference into it.
        self._entries: Dict[Tuple, _PhaseEntry] = {}
        # jitted repack callables, keyed per transition (repack_state)
        self._repack_cache: Dict[LayoutTransition, Callable] = {}
        # hot-swap state
        self._cycle_base = 0               # step at which the cycle restarts
        self._pending: Optional[_PendingSwap] = None
        self._swap_gen = 0                 # stale background builds don't publish
        self._swap_thread: Optional[threading.Thread] = None
        self.replans = 0                   # schedules staged via prepare_swap
        self.hot_swaps = 0                 # schedules actually installed
        self.layout_swaps = 0              # hot-swaps that re-packed state
        self.swap_failures = 0             # background compile attempts failed
        self.last_swap_error: Optional[str] = None
        # observability (DESIGN.md §11): control-plane events (swaps,
        # repacks, compile failures) always record into the tracer — the
        # legacy ``swap_log`` dicts are reconstructed from those events —
        # but per-step phase/place/launch spans enter the ring only when
        # a tracer was explicitly attached.  Their profiler annotations
        # are always opened: an inactive one costs one check.
        self.tracer = tracer if tracer is not None else Tracer(capacity=8192)
        self.trace_steps = tracer is not None
        self.last_phase = 0                # cycle phase of the last dispatch
        self.last_dispatch_first = False   # last dispatch was an entry's first
        self._install(schedule)

    # ---- precision (DESIGN.md §13) --------------------------------------
    def _validate_precision_layout(self, layout: BucketLayout) -> None:
        """Refuse a layout whose precision policy this runtime cannot
        execute: int8 wire needs 128-lane-aligned buffers (and shard
        spans, on the sharded engine) for the blockwise quantize grid,
        and the layout's master dtype must match the runtime's."""
        p = layout.precision
        if p is not None and p.master != self.master_dtype \
                and not (p.master == "f32" and self.master_dtype == "f32"):
            raise ValueError(
                f"layout precision master {p.master!r} != runtime "
                f"master_dtype {self.master_dtype!r}"
            )
        if p is None:
            return
        for b, w in enumerate(p.wire):
            if w != "int8":
                continue
            if layout.buf_sizes[b] % 128 != 0:
                raise ValueError(
                    f"int8 wire on bucket {b}: buffer size "
                    f"{layout.buf_sizes[b]} is not a 128-lane multiple"
                )
            if self.flat_state and self.fsdp \
                    and layout.shard_sizes[b] % 128 != 0:
                raise ValueError(
                    f"int8 wire on bucket {b}: shard span "
                    f"{layout.shard_sizes[b]} is not a 128-lane multiple"
                )

    def _wire_bytes_split_of_phase(
        self, phase: PhaseSpec
    ) -> Tuple[int, int]:
        """Planned (primary, secondary) wire bytes of one phase's
        scheduled gradient syncs under the installed layout's precision
        policy (int8 counts the quantized values plus 4 bytes per
        128-lane row of scales).  The per-link split follows
        ``phase.secondary`` — what the obs layer's per-link attribution
        audits each link's measured traffic against (DESIGN.md §14)."""
        wire = _layout_wire(self.layout)
        primary = secondary = 0
        for b in range(len(phase.route_new)):
            synced = (
                (phase.route_new[b] == "sync" and phase.rotate)
                or phase.sync_cur[b]
            )
            if not synced:
                continue
            n = self.layout.buf_sizes[b]
            if wire[b] == "int8":
                bts = n + 4 * (n // 128)
            else:
                bts = n * WIRE_BYTES[wire[b]]
            if phase.secondary[b]:
                secondary += bts
            else:
                primary += bts
        return primary, secondary

    def _wire_bytes_of_phase(self, phase: PhaseSpec) -> int:
        """Total planned wire bytes of one phase (both links)."""
        return sum(self._wire_bytes_split_of_phase(phase))

    # ---- schedule installation ------------------------------------------
    @staticmethod
    def _schedule_has_reuse(schedule: DeftSchedule) -> bool:
        """True when at least one cycle position can skip its param
        gather (a phase whose predecessor did not update)."""
        return any(
            not schedule.phases[t - 1].do_update
            for t in range(1, schedule.period)
        )

    def _gather_reuse_masks(
        self, schedule: DeftSchedule
    ) -> List[Optional[Tuple[bool, ...]]]:
        """Per cycle position, the per-bucket gather-skip mask of the
        sharded flat engine (None when the skip is off).  A bucket's
        stored gather is valid iff no update touched its params since
        the previous phase stored it — with the fused whole-state update
        that is simply "the previous phase did not update"; position 0
        always gathers (a swap or a fresh/restored cycle lands there
        with an unwarmed cache)."""
        if not self._gather_skip:
            return [None] * schedule.period
        masks: List[Optional[Tuple[bool, ...]]] = []
        for t, ph in enumerate(schedule.phases):
            nb = len(ph.route_new)
            fresh = t == 0 or schedule.phases[t - 1].do_update
            masks.append(((not fresh),) * nb)
        return masks

    def _ag_link_masks(
        self, schedule: DeftSchedule
    ) -> List[Optional[Tuple[bool, ...]]]:
        """Per cycle position, the per-bucket secondary-AG mask of the
        sharded flat engine (DESIGN.md §14): True where the streamed
        param all-gather was planned onto the secondary link
        (``AgItem.link >= 1``), so the executable routes that bucket's
        gather over the configured ring chain.  All-None without an AG
        plan or a chain — the pre-§14 executables, byte-for-byte."""
        if (self._ag_plan is None
                or self.config.secondary_chain is None
                or not (self.fsdp and self.flat_state)):
            return [None] * schedule.period
        per_phase: Dict[int, Dict[int, bool]] = {}
        for item in self._ag_plan.items:
            d = per_phase.setdefault(item.phase, {})
            d[item.bucket] = d.get(item.bucket, False) or item.link >= 1
        masks: List[Optional[Tuple[bool, ...]]] = []
        for t, ph in enumerate(schedule.phases):
            nb = len(ph.route_new)
            hot = per_phase.get(t)
            if not hot or not any(hot.values()):
                masks.append(None)
                continue
            masks.append(tuple(
                bool(hot.get(b, False)) for b in range(nb)
            ))
        return masks

    def _schedule_keys(
        self,
        schedule: DeftSchedule,
        layout: Optional[BucketLayout] = None,
    ) -> List[Tuple]:
        """Entry-cache keys, one per cycle position: the executable
        identity is (layout, PhaseSpec, gather-skip mask, AG-link
        mask)."""
        layout = layout or self.layout
        masks = self._gather_reuse_masks(schedule)
        ag_masks = self._ag_link_masks(schedule)
        return [
            (layout, ph, masks[t], ag_masks[t])
            for t, ph in enumerate(schedule.phases)
        ]

    def _make_jitted(
        self,
        phase: PhaseSpec,
        layout: BucketLayout,
        segments: Optional[BucketSegments],
        gather_reuse: Optional[Tuple[bool, ...]],
        ag_links: Optional[Tuple[bool, ...]] = None,
    ) -> Callable:
        if self.flat_state:
            step_impl = (
                deft_rs_phase_step_flat if self.fsdp
                else deft_phase_step_flat
            )
        else:
            step_impl = (
                deft_rs_phase_step_fused if self.fsdp
                else deft_phase_step_fused
            )
        kw = dict(
            cfg=self.cfg,
            opt_spec=self.opt_spec,
            phase=phase,
            layout=layout,
            mesh=self.mesh,
            remat=self._remat,
            loss_chunk=self._loss_chunk,
            unroll=self._unroll,
        )
        if self.flat_state:
            kw.update(
                segments=segments,
                treedef=self._treedef,
                update_impl=self.update_impl,
                compute_dtype=self.compute_dtype,
                master_dtype=(
                    self.master_dtype if self.master_dtype != "f32"
                    else None
                ),
            )
        if self.flat_state and self.fsdp:
            kw["gather_reuse"] = gather_reuse
            kw["decoupled"] = self.decoupled
        chain = self.config.secondary_chain
        if chain is not None:
            # validate() refused the one engine that cannot take it (the
            # tree-state RS path), so every reachable step_impl accepts it
            kw["secondary_chain"] = chain
            if self.flat_state and self.fsdp:
                kw["ag_links"] = ag_links
        if not self.fsdp:
            kw["multi_pod"] = self.multi_pod
        # keep_unused: a state buffer a phase does not read (an
        # accumulator it only overwrites) must stay an argument, or its
        # donation cannot alias the output and the phase allocates a
        # second copy of it
        return jax.jit(
            functools.partial(step_impl, **kw),
            donate_argnums=(0,) if self.donate else (),
            keep_unused=True,
        )

    def _ensure_entries(
        self,
        schedule: DeftSchedule,
        layout: Optional[BucketLayout] = None,
        segments: Optional[BucketSegments] = None,
    ) -> Tuple[List[_PhaseEntry], int]:
        """Create cache entries for the schedule's unseen executables
        under ``layout`` (default: the installed one).  Returns (entries
        needing compile, number reused from cache)."""
        layout = layout or self.layout
        segments = segments if segments is not None else self._segments
        fresh: List[_PhaseEntry] = []
        reused = 0
        for key in self._schedule_keys(schedule, layout):
            if key in self._entries:
                reused += 1
                continue
            _, phase, mask, ag_mask = key
            entry = _PhaseEntry(
                phase,
                self._make_jitted(phase, layout, segments, mask, ag_mask),
            )
            self._entries[key] = entry
            fresh.append(entry)
        return fresh, reused

    def _install(self, schedule: DeftSchedule) -> None:
        self._ensure_entries(schedule)
        self.schedule = schedule
        self._unique: List[Tuple] = []
        # entry objects resolved ONCE here: hashing a full BucketLayout
        # (thousands of nested ints) on every step() dispatch would put
        # tens of microseconds of pure-Python work on the hot path
        self._unique_entries: List[_PhaseEntry] = []
        index_of: Dict[Tuple, int] = {}
        keys = self._schedule_keys(schedule)
        for key in keys:
            if key not in index_of:
                index_of[key] = len(self._unique)
                self._unique.append(key)
                self._unique_entries.append(self._entries[key])
        self.phase_of_step: Tuple[int, ...] = tuple(
            index_of[key] for key in keys
        )
        # static per-cycle-position span attributes (DESIGN.md §11):
        # resolved at install so the traced dispatch path stays cheap
        masks = self._gather_reuse_masks(schedule)
        self._reuse_of_step: Tuple[bool, ...] = tuple(
            m is not None and any(m) for m in masks
        )
        self._coll_of_step: Tuple[Dict[str, int], ...] = tuple(
            phase_collectives(ph) for ph in schedule.phases
        )
        # planned wire bytes per cycle position under the installed
        # layout's precision policy (§13) — the obs layer's measured-vs-
        # planned bytes attribution reads these off the spans; split
        # per link (§14) so each link's traffic audits separately
        self._wire_bytes_split_of_step: Tuple[Tuple[int, int], ...] = tuple(
            self._wire_bytes_split_of_phase(ph) for ph in schedule.phases
        )
        self._wire_bytes_of_step: Tuple[int, ...] = tuple(
            p + s for p, s in self._wire_bytes_split_of_step
        )

    # ---- state ----------------------------------------------------------
    @property
    def period(self) -> int:
        return self.schedule.period

    @property
    def wire_bytes_per_phase(self) -> Tuple[int, ...]:
        """Planned bytes on the wire per cycle phase under the installed
        layout's precision (what ``obs.wire_bytes_report`` audits the
        trace against)."""
        return self._wire_bytes_of_step

    @property
    def wire_bytes_split_per_phase(self) -> Tuple[Tuple[int, int], ...]:
        """Planned (primary, secondary) wire bytes per cycle phase (§14)
        — the per-link audit vector ``obs.wire_bytes_report`` takes as
        ``planned_split``."""
        return self._wire_bytes_split_of_step

    @property
    def n_unique_phases(self) -> int:
        return len(self._unique)

    @property
    def n_cached_phases(self) -> int:
        """Unique phases ever compiled/jitted, across all installed
        schedules (the persistent cache's size)."""
        return len(self._entries)

    def reset_cycle(self, step: int) -> None:
        """Restart the schedule cycle at ``step``: a restored run begins
        a fresh cycle there (position 0, which always re-gathers), so
        resuming at an arbitrary global step keeps phase bookkeeping
        aligned."""
        self._cycle_base = step

    def phase_in_cycle(self, i: int) -> int:
        """Cycle phase step ``i`` will dispatch.  Correct across swaps:
        a staged schedule installs exactly at a boundary, where both the
        old and the new cycle agree the phase is 0."""
        return (i - self._cycle_base) % self.period

    def phase_executable(self, offset: int) -> Callable:
        """The donated executable behind cycle phase ``offset`` — the
        AOT-compiled one when :meth:`compile` ran, else the jitted
        callable.  Public handle for benchmarks/tools that dispatch one
        phase directly without the :meth:`step` bookkeeping."""
        entry = self._unique_entries[self.phase_of_step[offset]]
        return entry.compiled if entry.compiled is not None else entry.jitted

    @property
    def swap_log(self) -> List[Dict[str, Any]]:
        """Compat shim (DESIGN.md §11): the legacy swap-log dict list,
        reconstructed from the trace events that replaced it.  Install
        entries come from ``swap-install`` events; compile failures from
        the ``swap-compile`` events carrying an ``event`` attr (the
        successful-compile *span* has none and is not part of the log)."""
        out: List[Dict[str, Any]] = []
        for sp in self.tracer.spans(("swap-install", "swap-compile")):
            args = sp.args
            if sp.kind == "swap-install" or "event" in args:
                out.append({"step": sp.step, **args})
        return out

    def init_state(self, key, dtype=jnp.float32) -> TrainState:
        """Fresh train state, committed to the shardings the phase
        executables expect — params/opt replicated, accumulators split on
        their leading device axis.  Committed placement is what lets XLA
        alias the donated input buffers (an uncommitted array would be
        resharded at dispatch and could not be updated in place).

        Flat-state runtimes return ``{pbuf, opt, cur, fut}`` — params
        and moments as per-bucket flat f32 buffers (the master copy; see
        :meth:`params_tree` / :meth:`state_to_tree` for the checkpoint /
        eval boundary).  On the sharded FSDP/RS engine the buffers are
        committed split over 'data' (each device holds its span), so
        optimizer state is 1/N-resident from step 0.

        A non-f32 ``dtype`` on a flat runtime selects the *initialization
        rounding* of the mixed-precision path: params are drawn at
        ``dtype`` (matching the tree-path init bit-for-bit) and promoted
        into the f32 master; the runtime must have been built with
        ``compute_dtype=dtype`` so the forward casts back down at the
        buffer views."""
        if self.flat_state and dtype != jnp.float32 \
                and dtype != self.compute_dtype:
            raise ValueError(
                f"flat_state keeps an f32 master copy; init dtype={dtype} "
                f"needs the runtime built with compute_dtype={dtype} so "
                f"the forward runs at that precision (got "
                f"compute_dtype={self.compute_dtype}) — or use "
                f"flat_state=False for non-f32 resident params "
                f"(DESIGN.md §8)"
            )
        params = init_params(key, self.cfg, dtype=dtype)
        dp = self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0]
        rep = NamedSharding(self.mesh, P())
        split = NamedSharding(self.mesh, P(dp))

        def zeros_at(make, sharding):
            # zeros are born in their placement: built whole on one
            # device first, the accumulators of every device (or the
            # replicated moments plus their copies) can outgrow its HBM
            return jax.jit(make, out_shardings=sharding)()

        if self.flat_state:
            # flat master copy — one buffer per bucket (flatten promotes
            # a low-precision init to f32; a bf16sr master rounds back
            # down through the seeded stochastic-rounding kernel)
            pbuf = tuple(
                flatten_buckets(self.layout, jax.tree_util.tree_leaves(params))
            )
            del params
            if self.master_dtype == "bf16sr":
                pbuf = tuple(
                    self._round_master(p, b) for b, p in enumerate(pbuf)
                )
            # sharded engine: commit buffers split over 'data' so every
            # device materializes only its 1/N span
            buf = NamedSharding(self.mesh, P("data")) if self.fsdp else rep
            state = {"pbuf": jax.device_put(pbuf, buf)}
            del pbuf
            make_opt = functools.partial(
                init_flat_opt_state, self.opt_spec, self.layout.buf_sizes)
            state["opt"] = zeros_at(make_opt, jax.tree.map(
                lambda x: rep if x.ndim == 0 else buf,
                jax.eval_shape(make_opt)))
            state.update(zeros_at(functools.partial(
                init_fused_accumulators, self.layout, self.accum_devices),
                split))
            if self._gather_skip:
                state["pgather"] = jax.device_put(
                    self._init_pgather(self.layout), rep
                )
            return state
        state = {
            "params": jax.device_put(params, rep),
            "opt": jax.device_put(init_opt_state(self.opt_spec, params), rep),
        }
        state.update(zeros_at(functools.partial(
            init_fused_accumulators, self.layout, self.accum_devices), split))
        return state

    def _round_master(self, buf: jax.Array, b: int) -> jax.Array:
        """One flat f32 buffer rounded into the bf16sr resident master
        (seeded, deterministic); nearest rounding for buffers the 128-
        lane kernels cannot tile."""
        if buf.shape[0] % 128 == 0:
            return stochastic_round_bf16(buf, jnp.uint32(b + 1))
        return buf.astype(jnp.bfloat16)

    def _init_pgather(self, layout: BucketLayout) -> Tuple[jax.Array, ...]:
        """Cold gather cache for ``layout``: zeros in the compute dtype.
        Safe because cycle position 0 (where every fresh/restored/swapped
        cycle starts) always re-gathers — the cache is never read before
        a phase stored it."""
        dt = self.compute_dtype or jnp.float32
        return tuple(jnp.zeros((s,), dt) for s in layout.buf_sizes)

    # ---- checkpoint / eval boundary (tree <-> flat) ---------------------
    def params_tree(self, state: TrainState):
        """Parameter pytree view of a train state.  For flat-state
        runtimes this is THE unflatten boundary — steady-state steps
        never materialize the tree; call this only at checkpoint / eval
        / debug points."""
        if not self.flat_state:
            return state["params"]
        # under this runtime's own mesh: the caller's ambient mesh may be
        # another one (an elastic migration moved the state)
        with jax.set_mesh(self.mesh):
            return jax.tree_util.tree_unflatten(
                self._treedef, unflatten_buckets(self.layout, state["pbuf"])
            )

    def state_to_tree(self, state: TrainState) -> TrainState:
        """Checkpoint-friendly tree form {params, opt{step,m[,v]}} of a
        train state.  Params and moments become layout-agnostic pytrees;
        the ``cur``/``fut`` accumulators (and the ``pgather`` cache of
        the gather-skip engine) stay per-bucket flat buffers BOUND TO
        this runtime's layout — :meth:`tree_to_state` routes them through
        a :class:`LayoutTransition` when restoring under a different
        layout (``src_layout``)."""
        if not self.flat_state:
            return state
        unflat = lambda bufs: jax.tree_util.tree_unflatten(
            self._treedef, unflatten_buckets(self.layout, bufs)
        )
        with jax.set_mesh(self.mesh):
            opt: Dict[str, Any] = {"step": state["opt"]["step"],
                                   "m": unflat(state["opt"]["m"])}
            if "v" in state["opt"]:
                opt["v"] = unflat(state["opt"]["v"])
        out = {"params": self.params_tree(state), "opt": opt,
               "cur": state["cur"], "fut": state["fut"]}
        if "pgather" in state:
            # the gather cache is part of a mid-cycle resume's state: a
            # reuse-phase position would otherwise read a cold cache
            out["pgather"] = state["pgather"]
        return out

    def tree_to_state(
        self,
        tree_state: TrainState,
        src_layout: Optional[BucketLayout] = None,
    ) -> TrainState:
        """Inverse of :meth:`state_to_tree` — restore a checkpointed tree
        into the runtime's resident representation.

        ``src_layout`` names the :class:`BucketLayout` the checkpoint was
        written under; when it differs from this runtime's layout the
        flat accumulators are routed through the
        :class:`LayoutTransition` span remap (params/moments are
        layout-agnostic trees and simply re-flatten), so a run can be
        resumed under a different partition or shard count than it was
        saved with.  A cross-layout restore resets the gather cache —
        the restored run starts a fresh cycle at position 0, which
        always re-gathers."""
        cur, fut = tree_state["cur"], tree_state["fut"]
        cross = src_layout is not None and src_layout != self.layout
        if cross:
            tr = build_layout_transition(src_layout, self.layout)
            cur = tuple(repack_buffers(tr, cur))
            fut = tuple(repack_buffers(tr, fut))
        if not self.flat_state:
            return {**tree_state, "cur": cur, "fut": fut}
        flat = lambda t: tuple(
            flatten_buckets(self.layout, jax.tree_util.tree_leaves(t))
        )
        opt: Dict[str, Any] = {"step": tree_state["opt"]["step"],
                               "m": flat(tree_state["opt"]["m"])}
        if "v" in tree_state["opt"]:
            opt["v"] = flat(tree_state["opt"]["v"])
        pbuf = flat(tree_state["params"])
        if self.master_dtype == "bf16sr":
            # checkpointed bf16 values promote exactly through flatten;
            # the plain downcast restores them bit-for-bit
            pbuf = tuple(p.astype(jnp.bfloat16) for p in pbuf)
        out = {"pbuf": pbuf, "opt": opt, "cur": cur, "fut": fut}
        if self._gather_skip:
            if not cross and "pgather" in tree_state:
                out["pgather"] = tree_state["pgather"]
            else:
                out["pgather"] = self._init_pgather(self.layout)
        return out

    def checkpoint_struct(
        self,
        src_layout: Optional[BucketLayout] = None,
        *,
        with_pgather: Optional[bool] = None,
    ) -> TrainState:
        """ShapeDtypeStruct pytree of :meth:`state_to_tree` output as
        written under ``src_layout`` (default: this runtime's layout) —
        the ``like`` argument :func:`repro.checkpoint.checkpoint.restore`
        needs to verify shapes of a checkpoint possibly written under a
        DIFFERENT layout before :meth:`tree_to_state` re-packs it.

        ``with_pgather`` says whether the checkpoint carries the
        gather-skip cache; the default reads it only for a same-layout
        restore on a gather-skip runtime (a cross-layout restore resets
        the cache anyway, so the saved one — if any — is left unread)."""
        if not self.flat_state:
            raise ValueError("checkpoint_struct needs a flat-state runtime")
        lay = src_layout or self.layout
        cross = lay != self.layout
        if with_pgather is None:
            with_pgather = self._gather_skip and not cross
        tree = lambda dt: jax.tree_util.tree_unflatten(
            self._treedef,
            [jax.ShapeDtypeStruct(s, dt) for s in lay.shapes],
        )
        opt: Dict[str, Any] = {
            "step": jax.ShapeDtypeStruct((), jnp.int32),
            "m": tree(jnp.float32),
        }
        if self.opt_spec.name == "adamw":
            opt["v"] = tree(jnp.float32)
        acc = lambda: tuple(
            jax.ShapeDtypeStruct((self.accum_devices, n), jnp.float32)
            for n in lay.buf_sizes
        )
        out = {"params": tree(self._master_jdtype), "opt": opt,
               "cur": acc(), "fut": acc()}
        if with_pgather:
            dt = self.compute_dtype or jnp.float32
            out["pgather"] = tuple(
                jax.ShapeDtypeStruct((n,), dt) for n in lay.buf_sizes
            )
        return out

    # ---- AOT phase cache ------------------------------------------------
    def _compile_entries(
        self, entries: Sequence[_PhaseEntry], state, batch
    ) -> Dict[str, float]:
        # lowering traces Python and runs one phase at a time; the XLA
        # compiles release the GIL and run side by side
        todo = []
        with jax.set_mesh(self.mesh):
            for i, entry in enumerate(entries):
                if entry.compiled is not None:
                    continue
                t0 = time.perf_counter()
                lowered = entry.jitted.lower(state, batch)
                entry.stats.lower_s = time.perf_counter() - t0
                todo.append((i, entry, lowered))

        def compile_one(item):
            i, entry, lowered = item
            t0 = time.perf_counter()
            entry.compiled = lowered.compile()
            entry.batch_sharding = entry.compiled.input_shardings[0][1]
            entry.stats.compile_s = time.perf_counter() - t0
            return f"phase{i}", entry.stats.lower_s + entry.stats.compile_s

        with concurrent.futures.ThreadPoolExecutor(max(len(todo), 1)) as pool:
            return dict(pool.map(compile_one, todo))

    def compile(self, state: TrainState, batch) -> Dict[str, float]:
        """Lower + compile every unique phase of the installed schedule
        ahead of the first step.

        ``state``/``batch`` may be concrete arrays or ShapeDtypeStructs.
        Returns {phase_index: seconds} wall-clock compile times.
        """
        return self._compile_entries(self._unique_entries, state, batch)

    # ---- layout re-pack -------------------------------------------------
    @staticmethod
    @contextlib.contextmanager
    def _partial_donation_ok():
        """A repack between different bucket counts cannot alias every
        donated src buffer into a dst buffer (the allocation sizes
        changed — that is the point); XLA's partial-donation warning is
        expected there, not a lost optimization, so it is silenced for
        the repack compile only."""
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            yield

    def _state_placement(self, rep, buf, split):
        """(replicated, buffer, accumulator) sharding choices shared by
        repack outputs and swap-state avals."""
        return {
            "rep": rep,
            "buf": buf if (self.flat_state and self.fsdp) else rep,
            "split": split,
        }

    def _repack_jitted(self, transition: LayoutTransition) -> Callable:
        """Donated jitted single-pass gather/scatter applying a
        :class:`LayoutTransition` to a whole train state: params/moment
        buffers and both accumulator stacks re-flatten span-by-span;
        byte-identical buckets pass through so XLA aliases their donated
        buffers instead of copying.  Output shardings re-commit the
        dst-layout placement (on the sharded engine a shard-count change
        is just a different split of the same global buffers)."""
        hit = self._repack_cache.get(transition)
        if hit is not None:
            return hit
        dst = transition.dst
        dp = self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0]
        place = self._state_placement(
            NamedSharding(self.mesh, P()),
            NamedSharding(self.mesh, P("data")),
            NamedSharding(self.mesh, P(dp)),
        )
        rep, buf, split = place["rep"], place["buf"], place["split"]
        flat_state = self.flat_state
        gather_skip = self._gather_skip
        compute_dtype = self.compute_dtype or jnp.float32
        adam = self.opt_spec.name == "adamw"

        def fn(state):
            out: Dict[str, Any] = {}
            if flat_state:
                out["pbuf"] = tuple(repack_buffers(transition, state["pbuf"]))
                opt: Dict[str, Any] = {
                    "step": state["opt"]["step"],
                    "m": tuple(repack_buffers(transition, state["opt"]["m"])),
                }
                if "v" in state["opt"]:
                    opt["v"] = tuple(
                        repack_buffers(transition, state["opt"]["v"])
                    )
                out["opt"] = opt
            else:
                out["params"] = state["params"]
                out["opt"] = state["opt"]
            out["cur"] = tuple(repack_buffers(transition, state["cur"]))
            out["fut"] = tuple(repack_buffers(transition, state["fut"]))
            if gather_skip:
                # the gather cache is layout-bound and derived: reset cold
                # (post-swap cycle position 0 always re-gathers)
                out["pgather"] = tuple(
                    jnp.zeros((n,), compute_dtype) for n in dst.buf_sizes
                )
            return out

        out_sh: Dict[str, Any] = {"cur": split, "fut": split}
        if flat_state:
            out_sh["pbuf"] = buf
            opt_sh: Dict[str, Any] = {"step": rep, "m": buf}
            if adam:
                opt_sh["v"] = buf
            out_sh["opt"] = opt_sh
        else:
            out_sh["params"] = rep
            out_sh["opt"] = rep
        if gather_skip:
            out_sh["pgather"] = rep
        jitted = jax.jit(
            fn,
            donate_argnums=(0,) if self.donate else (),
            out_shardings=out_sh,
        )
        self._repack_cache[transition] = jitted
        return jitted

    def repack_state(
        self, state: TrainState, transition: LayoutTransition
    ) -> TrainState:
        """Re-flatten a train state between two bucket layouts in ONE
        jitted gather/scatter pass (DESIGN.md §9).  Pure data movement —
        the returned state is bit-identical to flatten(unflatten(state))
        under the dst layout.  Consumes ``state`` when donation is on.
        Normally driven by the staged swap in :meth:`step`; public for
        cross-layout checkpoint restores, tests and benchmarks."""
        if transition.dst.shapes != self.layout.shapes:
            raise ValueError(
                "transition targets a different parameter tree than this "
                "runtime's layout"
            )
        with self._partial_donation_ok(), self.tracer.span(
            "repack", "deft.repack",
            moved_elems=transition.moved_elems,
            n_buckets=transition.dst.n_buckets,
        ):
            return self._repack_jitted(transition)(state)

    def _swap_state_struct(self, state_abs, layout: BucketLayout):
        """Abstract post-repack train state under ``layout`` — what the
        staged schedule's fresh phases are compiled against while the old
        cycle keeps training on the old layout."""
        dp = self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0]
        place = self._state_placement(
            NamedSharding(self.mesh, P()),
            NamedSharding(self.mesh, P("data")),
            NamedSharding(self.mesh, P(dp)),
        )
        rep, buf, split = place["rep"], place["buf"], place["split"]

        def sds(shape, dtype, sharding):
            try:
                return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            except TypeError:   # older jax: no sharding kwarg
                return jax.ShapeDtypeStruct(shape, dtype)

        out = dict(state_abs)
        out["cur"] = tuple(
            sds((self.accum_devices, n), jnp.float32, split)
            for n in layout.buf_sizes
        )
        out["fut"] = tuple(
            sds((self.accum_devices, n), jnp.float32, split)
            for n in layout.buf_sizes
        )
        if self.flat_state:
            bufs = lambda dt: tuple(
                sds((n,), dt, buf) for n in layout.buf_sizes
            )
            out["pbuf"] = bufs(self._master_jdtype)
            opt: Dict[str, Any] = {"step": state_abs["opt"]["step"],
                                   "m": bufs(jnp.float32)}
            if "v" in state_abs["opt"]:
                opt["v"] = bufs(jnp.float32)
            out["opt"] = opt
        if self._gather_skip:
            dt = self.compute_dtype or jnp.float32
            out["pgather"] = tuple(
                sds((n,), dt, rep) for n in layout.buf_sizes
            )
        return out

    # ---- hot-swap -------------------------------------------------------
    def prepare_swap(
        self,
        schedule: DeftSchedule,
        state: TrainState,
        batch,
        *,
        background: bool = False,
        layout: Optional[BucketLayout] = None,
        ag_plan: Any = _UNSET,
        retries: int = 2,
        retry_backoff_s: float = 0.05,
    ) -> Dict[str, Any]:
        """Stage a replanned schedule for installation at the next cycle
        boundary.

        Unseen executables are lowered + compiled against the current
        state/batch shapes (``lower`` only reads avals — it never consumes
        the donated buffers); executables already in the persistent cache
        are reused.  With ``background=True`` the compile happens on a
        daemon thread while training keeps stepping the old schedule; the
        swap arms only once compilation finishes, so :meth:`step` never
        blocks on a half-built schedule.

        A compile failure NEVER silently strands the staged swap: the
        exception is recorded in ``swap_log`` (``event:
        'swap-compile-failed'``) and counted in ``swap_failures``, then
        the build retries up to ``retries`` times with linear backoff
        (``retry_backoff_s * attempt``; already-compiled phases are not
        recompiled).  When the budget is exhausted the swap is abandoned
        — training keeps stepping the installed schedule and a later
        :meth:`prepare_swap` starts clean (DESIGN.md §10).

        With ``layout`` (a different :class:`BucketLayout` over the SAME
        parameter tree — a new bucket partition and/or shard count) the
        swap becomes a layout-changing one (DESIGN.md §9): a
        :class:`LayoutTransition` is compiled alongside, the staged
        phases compile against the POST-repack state avals and segment
        maps of the new layout, and :meth:`step` runs the single-pass
        re-pack at the cycle boundary before dispatching phase 0 of the
        new schedule — an adaptive repartition needs no restart and no
        checkpoint round-trip.

        For a same-layout swap the install is a pure Python pointer flip
        at ``(i - cycle_base) % period == 0``: the donated train state
        carries across untouched because every buffer keeps its shape
        and sharding.
        """
        # a replanned AG stream (DESIGN.md §14) re-derives the per-bucket
        # secondary-AG masks for the staged executables; _UNSET keeps the
        # current plan.  Takes effect immediately for key derivation —
        # the installed schedule's entries were resolved at install and
        # never re-keyed, so running dispatch is unaffected.
        if ag_plan is not _UNSET:
            self._ag_plan = ag_plan
        new_layout: Optional[BucketLayout] = None
        transition: Optional[LayoutTransition] = None
        new_segments: Optional[BucketSegments] = None
        if layout is not None and layout != self.layout:
            # a hot-swap may change per-bucket WIRE precision (it is just
            # a new layout identity; an all-identical repack aliases the
            # state across) but never the resident master dtype — that
            # would need a state-wide cast, not a repack
            self._validate_precision_layout(layout)
            if self.flat_state and self.fsdp:
                shape = dict(zip(self.mesh.axis_names,
                                 self.mesh.devices.shape))
                if layout.shards != int(shape["data"]):
                    raise ValueError(
                        f"layout swap on the sharded engine: proposed "
                        f"layout has shard_count={layout.shards} but the "
                        f"mesh 'data' axis is {shape['data']}-way"
                    )
            new_layout = layout
            transition = build_layout_transition(self.layout, new_layout)
            if self.flat_state:
                new_segments = build_segments(new_layout, self.opt_spec)
        fresh, reused = self._ensure_entries(
            schedule, new_layout, new_segments
        )
        self.replans += 1
        info: Dict[str, Any] = {
            "new_phases": len(fresh),
            "reused_phases": reused,
            "background": background,
            "layout_change": new_layout is not None,
        }
        if new_layout is not None:
            info["n_buckets"] = (self.layout.n_buckets, new_layout.n_buckets)
            info["shards"] = (self.layout.shards, new_layout.shards)
            info["moved_elems"] = transition.moved_elems
        # snapshot avals NOW: the caller keeps training, and donation
        # deletes the concrete state buffers under the background thread
        state_abs = jax.tree.map(_abstractify, state)
        batch_abs = jax.tree.map(_abstractify, batch)
        if new_layout is not None:
            compile_state_abs = self._swap_state_struct(state_abs, new_layout)
        else:
            compile_state_abs = state_abs
        self._swap_gen += 1
        gen = self._swap_gen
        self._pending = None   # a newer replan supersedes any armed one

        def _build() -> None:
            t0 = time.perf_counter()
            tr0 = self.tracer.now()
            attempt = 0
            while True:
                try:
                    self._compile_entries(fresh, compile_state_abs, batch_abs)
                    repack = None
                    if transition is not None:
                        # AOT-compile the repack pass too: the
                        # cycle-boundary install must not pay a
                        # trace+compile on the hot path
                        with jax.set_mesh(self.mesh), \
                                self._partial_donation_ok():
                            repack = self._repack_jitted(transition).lower(
                                state_abs
                            ).compile()
                    break
                except Exception as e:   # noqa: BLE001 — surfaced, retried
                    attempt += 1
                    self.swap_failures += 1
                    err = f"{type(e).__name__}: {e}"
                    self.last_swap_error = err
                    retrying = attempt <= retries and self._swap_gen == gen
                    # failures SURFACE in the trace (and through the
                    # swap_log shim) — a background-thread exception must
                    # never silently strand a staged swap
                    self.tracer.instant(
                        "swap-compile", "swap-compile-failed",
                        step=None, event="swap-compile-failed",
                        error=err, attempt=attempt, retrying=retrying,
                    )
                    if not retrying:
                        # abandoned; old schedule keeps running.  Close
                        # the books so callers reading `info` can tell
                        # an abandoned build from one that never started
                        elapsed = time.perf_counter() - t0
                        info["compile_s"] = elapsed
                        info["compile_attempts"] = attempt
                        info["abandoned"] = True
                        self.tracer.instant(
                            "swap-compile", "swap-abandoned",
                            step=None, event="swap-abandoned",
                            error=err, attempts=attempt,
                            elapsed_s=elapsed,
                            superseded=self._swap_gen != gen,
                        )
                        return
                    time.sleep(retry_backoff_s * attempt)
            info["compile_s"] = time.perf_counter() - t0
            info["compile_attempts"] = attempt + 1
            self.tracer.add(
                "swap-compile", "swap-compile", tr0, self.tracer.now(),
                new_phases=len(fresh), reused_phases=reused,
                background=background,
                layout_change=new_layout is not None,
                attempts=attempt + 1,
            )
            # publish last — step() sees the schedule only fully compiled —
            # and only if no NEWER prepare_swap superseded this one (a slow
            # older compile must not overwrite a fresher staged schedule)
            if self._swap_gen == gen:
                self._pending = _PendingSwap(
                    schedule=schedule,
                    layout=new_layout,
                    segments=new_segments,
                    transition=transition,
                    repack=repack,
                )

        if background:
            self._swap_thread = threading.Thread(
                target=_build, name="deft-swap-compile", daemon=True
            )
            self._swap_thread.start()
        else:
            _build()
        return info

    def swap_ready(self) -> bool:
        """A staged schedule is compiled and armed for the next cycle
        boundary."""
        return self._pending is not None

    def wait_swap_ready(self, timeout: Optional[float] = None) -> bool:
        """Block until a background prepare_swap finishes compiling."""
        if self._swap_thread is not None:
            self._swap_thread.join(timeout)
        return self.swap_ready()

    # ---- elastic / degraded-mode dispatch -------------------------------
    def spawn(
        self,
        *,
        mesh=None,
        schedule: Optional[DeftSchedule] = None,
        layout: Optional[BucketLayout] = None,
        fsdp: Optional[bool] = None,
        gather_skip: Optional[bool] = None,
        donate: Optional[bool] = None,
        decoupled: Optional[bool] = None,
        config: Optional[RuntimeConfig] = None,
        tracer: Optional[Tracer] = None,
    ) -> "DeftRuntime":
        """Sibling runtime: same arch/optimizer/engine config, overriding
        mesh, schedule, layout and/or engine.  The elastic control plane
        builds these for mesh scale-down/up and for the
        sharded->replicated degraded-mode fallback (DESIGN.md §10);
        state moves over via :func:`repro.elastic.coordinator.migrate_state`.
        The phase cache is NOT shared — executables are mesh-bound.

        Overrides compose through :meth:`RuntimeConfig.replace` on this
        runtime's config (so an illegal combination is refused before any
        compile); pass ``config=`` for a full replacement instead of
        per-knob overrides."""
        new_mesh = self.mesh if mesh is None else mesh
        if config is None:
            fsdp_r = self.fsdp if fsdp is None else fsdp
            dec_r = self.decoupled if decoupled is None else decoupled
            if decoupled is None and not (fsdp_r and self.flat_state):
                # an inherited decoupled flag dies with the RS engine
                # (degraded-mode replicated fallback has no param AG)
                dec_r = False
            config = self.config.replace(
                multi_pod=(self.multi_pod if mesh is None
                           else "pod" in new_mesh.axis_names),
                fsdp=fsdp_r,
                donate=self.donate if donate is None else donate,
                decoupled=dec_r,
                # the sibling re-resolves its gather-skip default against
                # its own schedule unless explicitly pinned
                gather_skip=gather_skip,
            )
        elif any(v is not None for v in (fsdp, gather_skip, donate,
                                         decoupled)):
            raise ValueError(
                "spawn: pass config= OR per-knob overrides, not both"
            )
        return DeftRuntime(
            self.cfg,
            self.opt_spec,
            self.schedule if schedule is None else schedule,
            self.layout if layout is None else layout,
            new_mesh,
            config=config,
            ag_plan=self._ag_plan,
            # the sibling inherits the event stream by default: one trace
            # spans an elastic migration end to end
            tracer=(tracer if tracer is not None
                    else (self.tracer if self.trace_steps else None)),
        )

    # ---- dispatch -------------------------------------------------------
    def step(
        self, i: int, state: TrainState, batch
    ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        """Run training step ``i`` (cycle phase ``(i - cycle_base) %
        period``).  Consumes ``state`` when donation is on.  If a staged
        schedule is armed and ``i`` lands on a cycle boundary, it is
        installed first and ``i`` becomes step 0 of the new cycle; a
        layout-changing swap additionally re-packs the donated state
        through the staged transition before dispatching (the one-time
        repack cost is recorded in ``swap_log``).

        The whole call is the host span ``deft.phase``; inside it
        ``deft.place`` puts the batch on the executable's sharding and
        ``deft.launch`` calls the executable (DESIGN.md §11).  They reach
        an active profiler session's host plane always, and the tracer's
        ring only when a tracer was attached."""
        tracing = self.trace_steps
        span = self.tracer.span
        with span("phase", "deft.phase", record=tracing, step=i) as attrs:
            if self._pending is not None \
                    and (i - self._cycle_base) % self.period == 0:
                state = self._install_pending(i, state)
            off = (i - self._cycle_base) % self.period
            self.last_phase = off
            entry = self._unique_entries[self.phase_of_step[off]]
            # an entry's first dispatch carries residual lazy work (jit
            # trace+compile on the fallback branch, executable warm-up
            # even when AOT-compiled) — tag it so telemetry can skip it
            first = entry.stats.dispatches == 0
            self.last_dispatch_first = first
            clock = self.tracer.now if tracing else time.perf_counter
            t0 = clock()
            if entry.compiled is not None:
                with span("place", "deft.place", record=tracing, step=i,
                          phase=off):
                    batch = jax.device_put(batch, entry.batch_sharding)
                with span("launch", "deft.launch", record=tracing, step=i,
                          phase=off):
                    out = entry.compiled(state, batch)
            else:  # compile() skipped — trace under the mesh on first hit
                with span("launch", "deft.launch", record=tracing, step=i,
                          phase=off), jax.set_mesh(self.mesh):
                    out = entry.jitted(state, batch)
            entry.stats.dispatches += 1
            entry.stats.dispatch_s += clock() - t0
            if tracing:
                attrs.update(self._record_dispatch(i, off, first))
        return out

    def _record_dispatch(self, i: int, off: int, first: bool
                         ) -> Dict[str, Any]:
        """Record the update and gather-skip marks of traced step ``i``
        at cycle phase ``off`` and return its ``phase`` span's
        attributes: its update, its scheduled syncs per link and the
        wire bytes they ship under the installed precision (what
        ``obs.wire_bytes_report`` audits against the plan)."""
        spec = self._unique_entries[self.phase_of_step[off]].spec
        coll = self._coll_of_step[off]
        wb_p, wb_s = self._wire_bytes_split_of_step[off]
        if spec.do_update:
            self.tracer.instant(
                "update-apply", f"update-k{spec.update_k}",
                step=i, phase=off, k=spec.update_k,
                source=spec.update_source,
            )
        if self._reuse_of_step[off]:
            self.tracer.instant("gather-skip", "gather-skip", step=i,
                                phase=off)
        return {
            "phase": off, "first": first, "update": spec.do_update,
            "primary": coll["primary"], "secondary": coll["secondary"],
            "wire_bytes": self._wire_bytes_of_step[off],
            "wire_bytes_primary": wb_p, "wire_bytes_secondary": wb_s,
            "precision": (
                self.layout.precision.describe()
                if self.layout.precision is not None else "f32"
            ),
        }

    def _install_pending(self, i: int, state: TrainState) -> TrainState:
        """Install the armed schedule at cycle boundary ``i`` (host span
        ``deft.swap-install``); a layout-changing swap first re-packs the
        donated state through the staged transition (``deft.repack``)."""
        pending, self._pending = self._pending, None
        repack_s = None
        if pending.layout is not None:
            t0 = time.perf_counter()
            with self.tracer.span(
                "repack", "deft.repack", step=i,
                moved_elems=pending.transition.moved_elems,
                n_buckets=pending.layout.n_buckets,
            ):
                state = pending.repack(state)
                jax.block_until_ready(jax.tree_util.tree_leaves(state))
            repack_s = time.perf_counter() - t0
            self.layout = pending.layout
            self._segments = pending.segments
            self.layout_swaps += 1
        with self.tracer.span("swap-install", "deft.swap-install",
                              step=i) as attrs:
            self._install(pending.schedule)
            self._cycle_base = i
            self.hot_swaps += 1
            attrs.update(
                period=pending.schedule.period,
                updates_per_period=pending.schedule.updates_per_period,
                n_buckets=self.layout.n_buckets,
                shards=self.layout.shards,
                repack_s=repack_s,
                precision=(
                    self.layout.precision.describe()
                    if self.layout.precision is not None else "f32"
                ),
            )
        return state

    # ---- reporting ------------------------------------------------------
    def phase_kernels(self) -> List[Dict[str, Any]]:
        """Per AOT-compiled unique phase: whether it applies the update
        (``do_update``) and the Pallas kernels its executable calls
        (``kernels``), read from the compiled HLO, so a kernel that was
        not taken is absent rather than assumed."""
        return [
            {"do_update": e.spec.do_update,
             "kernels": sorted(set(_TPU_KERNEL_RE.findall(
                 e.compiled.as_text())))}
            for e in self._unique_entries if e.compiled is not None
        ]

    def phase_collective_scopes(self) -> List[Dict[str, FrozenSet[str]]]:
        """Per AOT-compiled unique phase: the sync, gather and metrics
        scopes of each instruction that is or calls a collective, read
        from the compiled HLO (``obs.hlo_scopes``), so a collective XLA
        rewrote or combined still names its buckets, links and
        generations."""
        return [collective_scopes([e.compiled.as_text()])
                for e in self._unique_entries if e.compiled is not None]

    def collectives_per_phase(self) -> List[Dict[str, int]]:
        """Static per-schedule-phase collective counts (fused path)."""
        return [phase_collectives(p) for p in self.schedule.phases]

    def stats(self) -> Dict[str, Any]:
        entries = list(self._entries.values())
        per_phase = [dataclasses.asdict(e.stats) for e in entries]
        total_compile = sum(
            e.stats.lower_s + e.stats.compile_s for e in entries
        )
        total_dispatch = sum(e.stats.dispatch_s for e in entries)
        n = sum(e.stats.dispatches for e in entries)
        coll = self.collectives_per_phase()
        from repro.kernels.bucket_update import default_bucket_update_impl

        return {
            "period": self.period,
            "unique_phases": self.n_unique_phases,
            "cached_phases": self.n_cached_phases,
            "flat_state": self.flat_state,
            "sharded_state": bool(self.flat_state and self.fsdp),
            "shards": self.layout.shards,
            "compute_dtype": (
                jnp.dtype(self.compute_dtype).name
                if self.compute_dtype is not None else "float32"
            ),
            "update_impl": (
                (self.update_impl or default_bucket_update_impl())
                if self.flat_state else "per-leaf"
            ),
            "wire_precision": (
                self.layout.precision.describe()
                if self.layout.precision is not None else "f32"
            ),
            "master_dtype": self.master_dtype,
            "planned_wire_bytes_per_cycle": sum(self._wire_bytes_of_step),
            "accum_devices": self.accum_devices,
            "n_buckets": self.layout.n_buckets,
            "n_leaves": self.layout.n_leaves,
            "compile_s_total": total_compile,
            "steps_dispatched": n,
            "dispatch_s_total": total_dispatch,
            "replans": self.replans,
            "hot_swaps": self.hot_swaps,
            "layout_swaps": self.layout_swaps,
            "swap_failures": self.swap_failures,
            "last_swap_error": self.last_swap_error,
            "gather_skip": self._gather_skip,
            "decoupled": self.decoupled,
            "swap_log": list(self.swap_log),
            "trace": self.tracer.stats(),
            "collectives_per_phase": coll,
            "max_collectives_in_a_phase": max(
                (c["primary"] + c["secondary"] for c in coll), default=0
            ),
            "phases": per_phase,
        }


# ---------------------------------------------------------------------------
# Convenience constructors
# ---------------------------------------------------------------------------
def make_ddp_step(
    cfg: ArchConfig,
    opt_spec: OptimizerSpec,
    *,
    fsdp: bool = False,
    multi_pod: bool = False,
    donate: bool = True,
    **kw,
) -> Callable:
    """Donated jitted DDP baseline step (params/opt update in place)."""
    return jax.jit(
        functools.partial(
            ddp_train_step, cfg=cfg, opt_spec=opt_spec,
            fsdp=fsdp, multi_pod=multi_pod, **kw,
        ),
        donate_argnums=(0,) if donate else (),
    )
