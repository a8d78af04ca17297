"""Gradient bucketing over actual parameter-tree leaves.

The analytical profiler (core/profiler.py) buckets by *layer* for the
paper-figure studies; the JAX train step needs buckets over the real
pytree leaves (scan-stacked weights), ordered input->output the way DDP's
reverse-registration order would see them:

    embed -> encoder -> prefix blocks -> stack (pattern positions) ->
    tail blocks -> final_norm -> head

One stacked leaf covers every period of that weight, so leaf-bucket
counts land in the paper's "< 20 items" knapsack regime naturally.
``assign_buckets`` greedily fills buckets to ``partition_elems``;
``leaf_bucket_times`` derives each bucket's fwd/bwd/comm seconds from the
same HardwareModel the Solver uses, with MoE leaves weighted by their
active fraction (top-k / n_experts).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.bucket import BucketTimes
from repro.core.precision import PrecisionPolicy
from repro.core.profiler import HardwareModel

_GROUP_ORDER = {
    "embed": 0,
    "encoder": 1,
    "prefix": 2,
    "stack": 3,
    "tail": 4,
    "final_norm": 5,
    "head": 6,
}


def _path_keys(path) -> Tuple[str, ...]:
    keys = []
    for p in path:
        if hasattr(p, "key"):
            keys.append(str(p.key))
        elif hasattr(p, "idx"):
            keys.append(str(p.idx))
        else:
            keys.append(str(p))
    return tuple(keys)


def ordered_leaf_indices(params) -> List[int]:
    """Indices into tree_flatten(params) leaf order, re-ordered to model
    input->output traversal."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    keyed = []
    for i, (path, leaf) in enumerate(flat):
        keys = _path_keys(path)
        group = _GROUP_ORDER.get(keys[0], 9)
        sub = 0
        if keys[0] in ("prefix", "stack", "tail") and len(keys) > 1:
            try:
                sub = int(keys[1])
            except ValueError:
                sub = 0
        keyed.append((group, sub, i))
    keyed.sort(key=lambda t: (t[0], t[1]))
    return [i for (_, _, i) in keyed]


def leaf_active_fraction(cfg: ArchConfig, keys: Tuple[str, ...]) -> float:
    """Fraction of a leaf's elements doing matmul work per token (MoE
    routed experts: top-k of E, the router's width, for each expert the
    layer holds)."""
    if cfg.moe and "experts" in keys and keys[-1] in ("gate", "up", "down"):
        return cfg.moe.experts_per_token / cfg.moe.n_experts
    return 1.0


def greedy_fill_partition(
    order: Sequence[int],
    elems: Sequence[int],
    partition_elems: int,
) -> Tuple[Tuple[int, ...], int]:
    """THE greedy model-order fill: walk ``order``, open a new bucket
    whenever the running element count reaches ``partition_elems``.
    Shared by :func:`assign_buckets` (params tree) and
    :meth:`LeafTimeModel.partition` (frozen atoms) so the online
    repartitioner's candidate grid can never drift from the partitions
    the real layouts are built with."""
    bucket_of = [0] * len(elems)
    b, acc = 0, 0
    for idx in order:
        bucket_of[idx] = b
        acc += elems[idx]
        if acc >= partition_elems:
            b += 1
            acc = 0
    # if the last bucket ended exactly on a boundary, b overshoots by one
    n_buckets = max(set(bucket_of)) + 1
    return tuple(bucket_of), n_buckets


def assign_buckets(
    params,
    cfg: ArchConfig,
    partition_elems: int = 50_000_000,
) -> Tuple[Tuple[int, ...], int]:
    """Greedy fill in model order.  Returns (bucket_of_leaf aligned with
    tree_flatten leaf order, n_buckets); bucket 0 is input-most."""
    leaves = jax.tree_util.tree_flatten(params)[0]
    return greedy_fill_partition(
        ordered_leaf_indices(params),
        [int(np.prod(l.shape)) for l in leaves],
        partition_elems,
    )


# ---------------------------------------------------------------------------
# Static leaf -> flat-buffer layout (fused-bucket collectives)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class BucketLayout:
    """Static mapping between parameter-tree leaves and per-bucket flat
    f32 buffers (DESIGN.md §Fused buffers).

    Each bucket owns one contiguous buffer holding every leaf assigned to
    it, in ``tree_flatten`` leaf order.  All offsets/sizes are Python ints
    computed once at plan time, so flatten/unflatten trace to static
    concatenate/slice/reshape ops and each bucket syncs as ONE collective.

    bucket_of_leaf: leaf index (tree_flatten order) -> bucket id.
    n_buckets:      number of buckets (== number of flat buffers).
    leaves:         per bucket, the leaf indices it holds (ascending).
    offsets:        per bucket, the start offset of each leaf's span.
    sizes:          per bucket, total element count of its *valid* span.
    shapes:         per leaf (tree_flatten order), the original shape.
    padded_sizes:   per bucket, the allocated buffer length — ``sizes``
                    rounded up to ``pad_multiple`` so the buffer reshapes
                    to (rows, 128) lanes for the Pallas bucket-update
                    kernels (DESIGN.md §8).  The tail [size, padded) is
                    always zero: flatten pads zeros, collectives reduce
                    zeros, and the update kernels mask it.  Empty tuple
                    means "no padding" (legacy hand-built layouts).
    shards:         shard count of the sharded flat engine (DESIGN.md
                    §8, sharded layout): every allocated buffer length is
                    additionally a multiple of ``shards * pad_multiple``,
                    so the buffer splits into ``shards`` equal contiguous
                    spans and every span is itself a lane-aligned kernel
                    operand.  1 (the default) is the replicated engine.
    precision:      per-bucket wire precision policy (DESIGN.md §13);
                    ``None`` means all-f32.  Part of layout identity on
                    purpose: the runtime's phase cache keys on the
                    layout, so a precision change is a cycle-boundary
                    layout swap — while :func:`build_layout_transition`
                    ignores it, making the precision-only repack a pure
                    aliasing pass (zero data movement).
    """

    bucket_of_leaf: Tuple[int, ...]
    n_buckets: int
    leaves: Tuple[Tuple[int, ...], ...]
    offsets: Tuple[Tuple[int, ...], ...]
    sizes: Tuple[int, ...]
    shapes: Tuple[Tuple[int, ...], ...]
    padded_sizes: Tuple[int, ...] = ()
    shards: int = 1
    precision: Optional[PrecisionPolicy] = None

    def __post_init__(self):
        if self.precision is not None:
            self.precision.validate(self.n_buckets)

    @property
    def n_leaves(self) -> int:
        return len(self.bucket_of_leaf)

    def wire(self, b: int) -> str:
        """Wire dtype name of bucket ``b`` ("f32" without a policy)."""
        return "f32" if self.precision is None else self.precision.wire[b]

    @property
    def master_dtype(self) -> str:
        return "f32" if self.precision is None else self.precision.master

    def with_precision(
        self, precision: Optional[PrecisionPolicy]
    ) -> "BucketLayout":
        """Same partition/sharding, different precision policy — the
        layout a precision-only hot-swap targets."""
        return dataclasses.replace(self, precision=precision)

    @property
    def total_elems(self) -> int:
        return sum(self.sizes)

    @property
    def buf_sizes(self) -> Tuple[int, ...]:
        """Allocated per-bucket buffer lengths (padded when available)."""
        return self.padded_sizes or self.sizes

    @property
    def shard_sizes(self) -> Tuple[int, ...]:
        """Per bucket, the length of one device's contiguous shard span
        (``buf_sizes[b] // shards``; a lane multiple by construction).
        Shard ``s`` of bucket ``b`` covers the global index range
        ``[s * shard_sizes[b], (s + 1) * shard_sizes[b])``."""
        return tuple(n // self.shards for n in self.buf_sizes)


# One f32 lane row: the bucket-update kernels reshape buffers to
# (rows, PAD_MULTIPLE) tiles (kernels/bucket_update/kernel.py re-checks
# the two constants agree on every trace, so they cannot drift apart
# silently).
PAD_MULTIPLE = 128


def build_bucket_layout(
    params,
    bucket_of_leaf: Sequence[int],
    n_buckets: int,
    *,
    pad_multiple: int = PAD_MULTIPLE,
    shard_count: int = 1,
    precision: Optional[PrecisionPolicy] = None,
) -> BucketLayout:
    """Precompute the per-bucket flat-buffer layout for a parameter tree.

    ``shard_count > 1`` builds the shard-aware layout of the sharded flat
    engine (DESIGN.md §8): every buffer is padded to a multiple of
    ``shard_count * pad_multiple`` so it splits into ``shard_count``
    equal, lane-aligned spans — each span a valid kernel operand and a
    valid tiled reduce-scatter / all-gather shard.
    """
    if pad_multiple <= 0 or pad_multiple % PAD_MULTIPLE:
        raise ValueError(
            f"pad_multiple={pad_multiple} must be a positive multiple of "
            f"{PAD_MULTIPLE} (the bucket-update kernels' lane width) — a "
            f"smaller value would only fail deep inside the flat engine's "
            f"first update-phase compile"
        )
    if shard_count < 1:
        raise ValueError(f"shard_count={shard_count} must be >= 1")
    unit = pad_multiple * shard_count
    flat = jax.tree_util.tree_flatten(params)[0]
    assert len(flat) == len(bucket_of_leaf)
    shapes = tuple(tuple(l.shape) for l in flat)
    leaves: List[List[int]] = [[] for _ in range(n_buckets)]
    for i, b in enumerate(bucket_of_leaf):
        leaves[b].append(i)
    offsets: List[Tuple[int, ...]] = []
    sizes: List[int] = []
    padded: List[int] = []
    for b in range(n_buckets):
        offs, acc = [], 0
        for i in leaves[b]:
            offs.append(acc)
            acc += int(np.prod(shapes[i], dtype=np.int64)) if shapes[i] else 1
        offsets.append(tuple(offs))
        sizes.append(acc)
        # sharded layouts allocate one unit even for an empty bucket so
        # every shard span is a non-empty kernel / collective operand
        if acc:
            padded.append(-(-acc // unit) * unit)
        else:
            padded.append(unit if shard_count > 1 else 0)
    return BucketLayout(
        bucket_of_leaf=tuple(bucket_of_leaf),
        n_buckets=n_buckets,
        leaves=tuple(tuple(g) for g in leaves),
        offsets=tuple(offsets),
        sizes=tuple(sizes),
        shapes=shapes,
        padded_sizes=tuple(padded),
        shards=shard_count,
        precision=precision,
    )


def flatten_buckets(layout: BucketLayout, leaf_vals) -> List[jax.Array]:
    """Pack leaf values (tree_flatten order) into per-bucket flat f32
    buffers, zero-padded to the layout's allocated length.  Traced:
    static concatenation, no data-dependent shapes."""
    out = []
    buf_sizes = layout.buf_sizes
    for b in range(layout.n_buckets):
        parts = [
            leaf_vals[i].astype(jnp.float32).reshape(-1)
            for i in layout.leaves[b]
        ]
        pad = buf_sizes[b] - layout.sizes[b]
        if pad:
            parts.append(jnp.zeros((pad,), jnp.float32))
        out.append(
            parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        )
    return out


def unflatten_buckets(layout: BucketLayout, flats) -> List[jax.Array]:
    """Inverse of :func:`flatten_buckets`: per-bucket flat buffers back to
    leaf values (tree_flatten order, f32)."""
    leaf_vals: List[jax.Array] = [None] * layout.n_leaves  # type: ignore
    for b in range(layout.n_buckets):
        flat = flats[b]
        for i, off in zip(layout.leaves[b], layout.offsets[b]):
            shape = layout.shapes[i]
            n = int(np.prod(shape, dtype=np.int64)) if shape else 1
            leaf_vals[i] = jax.lax.slice(flat, (off,), (off + n,)).reshape(shape)
    assert all(v is not None for v in leaf_vals)
    return leaf_vals


def leaf_bucket_times(
    params,
    cfg: ArchConfig,
    bucket_of_leaf: Sequence[int],
    n_buckets: int,
    hw: HardwareModel,
    seq_len: int,
    per_device_batch: int,
) -> BucketTimes:
    """Analytical fwd/bwd/comm seconds per leaf-bucket."""
    model = build_leaf_time_model(params, cfg, hw, seq_len, per_device_batch)
    return model.bucket_times(bucket_of_leaf, n_buckets)


# ---------------------------------------------------------------------------
# Per-leaf time model (repartitioning input)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class LeafTimeModel:
    """Per-leaf timing atoms from which bucket times for ANY partition of
    the same parameter tree can be regenerated.

    ``leaf_bucket_times`` bakes the partition into its output; the online
    repartitioning path (adapt/repartition.py) instead needs "what would
    this OTHER partition's BucketTimes be under the calibrated hardware"
    — so the per-leaf fwd seconds and element counts are frozen once (a
    pure-Python tuple dataclass; jax is only touched at construction) and
    every candidate partition re-aggregates them.

    ``comm_scale`` folds in the uniform coverage-rate rescale the train
    driver applies (build_schedule's synthetic-CR knob), so regenerated
    times stay comparable with the times the installed plan was solved
    from.  ``bucket_times(..., comp_scale=, comm_scale=)`` additionally
    applies calibration scales on top (adapt/calibrate.py semantics).
    """

    order: Tuple[int, ...]       # model-order traversal of flat leaf idx
    fwd_s: Tuple[float, ...]     # per leaf (flat idx), analytic fwd seconds
    elems: Tuple[int, ...]       # per leaf (flat idx), element count
    hw: HardwareModel
    comm_scale: float = 1.0      # uniform CR rescale folded into comm

    @property
    def n_leaves(self) -> int:
        return len(self.fwd_s)

    def with_comm_scale(self, scale: float) -> "LeafTimeModel":
        return dataclasses.replace(self, comm_scale=scale)

    def with_coverage_rate(
        self,
        bucket_of_leaf: Sequence[int],
        n_buckets: int,
        coverage_rate: float,
    ) -> "LeafTimeModel":
        """Fold the synthetic-CR rescale into the model so that
        ``bucket_times(bucket_of_leaf, n_buckets)`` hits ``coverage_rate``
        — the ONE place the rescale math lives, keeping candidate pricing
        commensurable with the times the installed plan was solved from
        (see :func:`coverage_rescale`)."""
        t = self.bucket_times(bucket_of_leaf, n_buckets)
        return self.with_comm_scale(
            self.comm_scale * coverage_rescale(t, coverage_rate)
        )

    def partition(
        self, partition_elems: int
    ) -> Tuple[Tuple[int, ...], int]:
        """Greedy model-order fill at ``partition_elems`` — literally
        :func:`assign_buckets`' walk (shared via
        :func:`greedy_fill_partition`), without the params tree."""
        return greedy_fill_partition(self.order, self.elems,
                                     partition_elems)

    def bucket_times(
        self,
        bucket_of_leaf: Sequence[int],
        n_buckets: int,
        *,
        comp_scale: float = 1.0,
        comm_scale: float = 1.0,
        precision: Optional[PrecisionPolicy] = None,
    ) -> BucketTimes:
        """BucketTimes of an arbitrary partition of this tree, optionally
        under calibrated effective scales.  ``precision`` prices each
        bucket's comm at its policy wire width (§13) — the latency term
        inside ``allreduce_time`` stays fixed."""
        if precision is not None:
            precision.validate(n_buckets)
        fwd = [0.0] * n_buckets
        comm_elems = [0] * n_buckets
        for i, b in enumerate(bucket_of_leaf):
            fwd[b] += self.fwd_s[i]
            comm_elems[b] += self.elems[i]
        fwd = [f * comp_scale for f in fwd]
        bwd = [2.0 * f for f in fwd]
        c_scale = self.comm_scale * comm_scale
        comm = [
            self.hw.allreduce_time(
                e,
                bytes_per_elem=(
                    None if precision is None
                    else precision.wire_bytes_per_elem(b)
                ),
            ) * c_scale
            for b, e in enumerate(comm_elems)
        ]
        return BucketTimes(tuple(fwd), tuple(bwd), tuple(comm))


def build_leaf_time_model(
    params,
    cfg: ArchConfig,
    hw: HardwareModel,
    seq_len: int,
    per_device_batch: int,
) -> LeafTimeModel:
    """Freeze the per-leaf timing atoms of a parameter tree (shapes only —
    an ``eval_shape`` tree works)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    tokens = per_device_batch * seq_len
    fwd_s: List[float] = []
    elems: List[int] = []
    for path, leaf in flat:
        keys = _path_keys(path)
        n = int(np.prod(leaf.shape, dtype=np.int64)) if leaf.shape else 1
        active = leaf_active_fraction(cfg, keys)
        flops = 2.0 * n * active * tokens if len(leaf.shape) >= 2 else 0.0
        fwd_s.append(hw.compute_time(flops))
        elems.append(n)
    return LeafTimeModel(
        order=tuple(ordered_leaf_indices(params)),
        fwd_s=tuple(fwd_s),
        elems=tuple(elems),
        hw=hw,
    )


def coverage_rescale(times: BucketTimes, coverage_rate: float) -> float:
    """The uniform comm multiplier that pins ``times`` to a target
    coverage rate — shared by the train driver's synthetic-CR knob, the
    repartitioning leaf model and the examples, so the copies cannot
    drift apart and silently bias candidate pricing."""
    return (
        coverage_rate
        * (times.fwd_total + times.bwd_total)
        / max(times.comm_total, 1e-12)
    )


# ---------------------------------------------------------------------------
# Layout transitions (cycle-boundary re-pack between two BucketLayouts)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SpanCopy:
    """One contiguous copy of a layout transition: ``length`` elements
    from offset ``src_off`` of src bucket ``src_bucket`` land at offset
    ``dst_off`` of the dst bucket this copy belongs to."""

    src_bucket: int
    src_off: int
    dst_off: int
    length: int


@dataclasses.dataclass(frozen=True)
class LayoutTransition:
    """Static per-leaf span remap between two :class:`BucketLayout`\\ s of
    the SAME parameter tree (DESIGN.md §9).

    Built once at replan time (pure Python over the two layouts' offset
    tables), consumed by :func:`repack_buffers` as a traced gather: every
    dst buffer is a static concatenation of slices of src buffers plus a
    zero tail.  Adjacent leaves contiguous in both layouts merge into one
    :class:`SpanCopy`, so a transition that only changes the shard count
    (identical partition, different padding unit) compiles to one slice
    per bucket.

    ``identical[b]`` marks dst buckets whose allocated buffer is
    byte-identical to one src buffer (same single full-range copy, same
    padded length): :func:`repack_buffers` passes those through untouched,
    which lets XLA alias the donated src buffer instead of copying it.
    """

    src: BucketLayout
    dst: BucketLayout
    copies: Tuple[Tuple[SpanCopy, ...], ...]   # per dst bucket
    identical: Tuple[bool, ...]                # per dst bucket

    @property
    def moved_elems(self) -> int:
        """Valid elements actually gathered (identical buckets excluded)."""
        return sum(
            c.length
            for b, spans in enumerate(self.copies)
            if not self.identical[b]
            for c in spans
        )

    def reverse(self) -> "LayoutTransition":
        return build_layout_transition(self.dst, self.src)


def build_layout_transition(
    src: BucketLayout, dst: BucketLayout
) -> LayoutTransition:
    """Compile the static span remap ``src`` -> ``dst``.

    Both layouts must cover the same leaf set (identical ``shapes``);
    everything else — bucket count, leaf->bucket assignment, padding,
    shard count — may differ.
    """
    if src.shapes != dst.shapes:
        raise ValueError(
            f"layout transition needs the same parameter tree on both "
            f"sides: src has {len(src.shapes)} leaves, dst "
            f"{len(dst.shapes)} (or shapes differ)"
        )
    # leaf idx -> (src bucket, src offset)
    src_pos: Dict[int, Tuple[int, int]] = {}
    for b in range(src.n_buckets):
        for i, off in zip(src.leaves[b], src.offsets[b]):
            src_pos[i] = (b, off)
    copies: List[Tuple[SpanCopy, ...]] = []
    identical: List[bool] = []
    for b in range(dst.n_buckets):
        spans: List[SpanCopy] = []
        run: Optional[List[int]] = None   # [src_bucket, src_off, dst_off, len]
        for i, d_off in zip(dst.leaves[b], dst.offsets[b]):
            sb, s_off = src_pos[i]
            shape = dst.shapes[i]
            n = int(np.prod(shape, dtype=np.int64)) if shape else 1
            if (
                run is not None
                and run[0] == sb
                and run[1] + run[3] == s_off
                and run[2] + run[3] == d_off
            ):
                run[3] += n
            else:
                if run is not None:
                    spans.append(SpanCopy(*run))
                run = [sb, s_off, d_off, n]
        if run is not None:
            spans.append(SpanCopy(*run))
        copies.append(tuple(spans))
        identical.append(
            len(spans) == 1
            and spans[0].src_off == 0
            and spans[0].dst_off == 0
            and spans[0].length == dst.sizes[b]
            and src.sizes[spans[0].src_bucket] == dst.sizes[b]
            and src.buf_sizes[spans[0].src_bucket] == dst.buf_sizes[b]
        )
    return LayoutTransition(
        src=src, dst=dst, copies=tuple(copies), identical=tuple(identical)
    )


def repack_buffers(
    transition: LayoutTransition, src_bufs: Sequence[jax.Array]
) -> List[jax.Array]:
    """Apply a layout transition to per-bucket buffers: the single traced
    gather pass of :meth:`DeftRuntime.repack_state`.

    Buffers are remapped along their LAST axis (1-D param/moment buffers
    and ``(accum_devices, size)`` accumulator stacks both work); leading
    axes pass through.  Byte-identical buckets are returned as the src
    array itself so a donating jit can alias instead of copying; the
    padded dst tail is zero by construction (src valid spans are copied,
    src tails — zero by the flat engines' invariant — are never read).
    """
    dst = transition.dst
    out: List[jax.Array] = []
    for b in range(dst.n_buckets):
        if transition.identical[b]:
            out.append(src_bufs[transition.copies[b][0].src_bucket])
            continue
        lead = src_bufs[0].shape[:-1]
        # pad fills match the src dtype — an f32 zero concatenated into
        # e.g. a bf16 buffer would silently promote the whole dst buffer
        dtype = src_bufs[0].dtype
        parts: List[jax.Array] = []
        cursor = 0
        for c in transition.copies[b]:
            if c.dst_off > cursor:   # cannot happen (offsets are dense)
                parts.append(
                    jnp.zeros(lead + (c.dst_off - cursor,), dtype)
                )
            parts.append(
                jax.lax.slice_in_dim(
                    src_bufs[c.src_bucket], c.src_off, c.src_off + c.length,
                    axis=len(lead),
                )
            )
            cursor = c.dst_off + c.length
        pad = dst.buf_sizes[b] - cursor
        if pad:
            parts.append(jnp.zeros(lead + (pad,), dtype))
        out.append(
            parts[0] if len(parts) == 1
            else jnp.concatenate(parts, axis=len(lead))
        )
    return out
