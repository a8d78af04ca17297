"""Compiled train steps: DDP baseline and DeFT per-phase executables.

The paper's runtime scheduler reorders NCCL launches under PyTorch eager
execution.  Under XLA there is no runtime launch order to reorder — the
compiler owns the intra-step schedule — so the *semantically meaningful*
part of a DeFT schedule is realized structurally in the compiled graph:

* each :class:`~repro.core.scheduler.PhaseSpec` of the periodic schedule
  becomes ONE jitted executable whose HLO contains an all-reduce for
  exactly the buckets that phase synchronizes — masked-out buckets have
  *no collective at all* and accumulate in device-local buffers;
* parameter updates fire only in phases with ``do_update`` (delayed
  updates), consuming the merged (k-batch) gradient with the gradient-
  accumulation scaling ``1/(n_dp * k)``;
* buckets assigned to the paper's *secondary link* (gloo/second NIC)
  synchronize via a hierarchical reduce-scatter -> (pod all-reduce) ->
  all-gather, exercising the slower DCN/host path concurrently with the
  primary ICI ring (see DESIGN.md §3 for the link-mapping adaptation).

Distribution modes
------------------
``ddp_train_step``      pjit auto-sharding; batch over ('pod','data'),
                        tensors over 'model'; XLA inserts one all-reduce
                        per gradient — the WFBP / PyTorch-DDP baseline.
``deft_phase_step``     ``jax.shard_map`` manual over the DP axes with
                        params replicated across them ('model' stays
                        auto); per-bucket explicit ``psum`` under the
                        phase masks.  Used by the non-FSDP archs.
``deft_rs_phase_step``  manual over 'pod' only: params/optimizer FSDP-
                        sharded over 'data' (XLA keeps the intra-pod
                        reduce-scatter every step); DeFT masks the
                        *inter-pod* gradient psums — the slow-link
                        schedule on a multi-pod mesh.  Used by the three
                        FSDP archs (deepseek-v2-236b, llama4-maverick,
                        llama-3.2-vision-90b) whose params cannot
                        replicate across DP.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.scheduler import DeftSchedule, PhaseSpec
from repro.models.model import init_params, loss_fn
from repro.optim.optimizers import OptimizerSpec, apply_updates, init_opt_state
from repro.sharding import (
    logical_rules,
    rules_deft_manual_dp,
    rules_deft_rs_manual_pod,
    rules_pjit,
)

# TrainState is a plain dict pytree (checkpoint-friendly):
#   params, opt, and (DeFT only) cur/fut gradient accumulators with a
#   leading device axis (size n_dp for manual-DP, n_pod for the RS path).
TrainState = Dict[str, Any]


def init_train_state(
    key,
    cfg: ArchConfig,
    opt_spec: OptimizerSpec,
    *,
    deft: bool = False,
    accum_devices: int = 1,
    dtype=jnp.float32,
    layout=None,
) -> TrainState:
    """Fresh train state.

    ``deft=True`` adds the cur/fut gradient-generation accumulators:
    per-bucket flat f32 buffers when a :class:`~repro.train.bucketing.
    BucketLayout` is given (the fused runtime layout, DESIGN.md §Fused
    buffers), else one buffer per parameter leaf (the legacy per-leaf
    path kept as semantic reference)."""
    params = init_params(key, cfg, dtype=dtype)
    state: TrainState = {"params": params, "opt": init_opt_state(opt_spec, params)}
    if deft and layout is not None:
        from repro.train.runtime import init_fused_accumulators

        state.update(init_fused_accumulators(layout, accum_devices))
    elif deft:
        zeros = lambda: jax.tree.map(
            lambda p: jnp.zeros((accum_devices,) + p.shape, jnp.float32), params
        )
        state["cur"] = zeros()
        state["fut"] = zeros()
    return state


# ---------------------------------------------------------------------------
# Baseline: pjit DDP (WFBP semantics — every bucket syncs, update every step)
# ---------------------------------------------------------------------------
def ddp_train_step(
    state: TrainState,
    batch: Dict[str, jax.Array],
    *,
    cfg: ArchConfig,
    opt_spec: OptimizerSpec,
    multi_pod: bool = False,
    fsdp: bool = False,
    remat: bool = True,
    loss_chunk: int = 0,
    unroll: bool = False,
    layout: str = "tp",
    microbatch: int = 0,
) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """WFBP/DDP baseline step.

    ``microbatch = M > 1`` splits the global batch into M sequential
    micro-batches accumulated in f32 under lax.scan — activation memory
    drops ~M-fold for one extra f32 gradient buffer (beyond-paper §Perf
    lever for the memory-bound giants; the gradient all-reduce still
    happens once per step, so DeFT's scheduling domain is unchanged)."""
    with logical_rules(rules_pjit(multi_pod, fsdp, layout)):
        if microbatch and microbatch > 1:
            m = microbatch

            def to_micro(x):
                return x.reshape((m, x.shape[0] // m) + x.shape[1:])

            mb = jax.tree.map(to_micro, batch)

            def micro(carry, bslice):
                gsum, lsum = carry
                (l, parts), g = jax.value_and_grad(
                    lambda p: loss_fn(p, cfg, bslice, remat=remat,
                                      loss_chunk=loss_chunk, unroll=unroll),
                    has_aux=True,
                )(state["params"])
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g
                )
                return (gsum, lsum + l), parts

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["params"]
            )
            (grads, loss), parts = jax.lax.scan(
                micro, (g0, jnp.zeros((), jnp.float32)), mb
            )
            loss = loss / m
            parts = jax.tree.map(lambda x: jnp.mean(x), parts)
            grads = jax.tree.map(lambda g: g / m, grads)
        else:
            (loss, parts), grads = jax.value_and_grad(
                lambda p: loss_fn(p, cfg, batch, remat=remat,
                                  loss_chunk=loss_chunk, unroll=unroll),
                has_aux=True,
            )(state["params"])
        grads = _anchor_grad_shardings(grads, cfg, multi_pod, layout)
    params, opt = apply_updates(opt_spec, state["params"], grads, state["opt"])
    metrics = {"loss": loss, **parts, "updated": jnp.ones((), jnp.bool_)}
    return {"params": params, "opt": opt}, metrics


def _anchor_grad_shardings(grads, cfg, multi_pod: bool, layout: str):
    """Pin every weight gradient to its parameter's sharding.

    Without this anchor the SPMD partitioner is free to compute dW by
    all-gathering the (global-batch!) f32 activation/cotangent pair and
    doing the contraction locally — observed on gemma2-2b train_4k as
    54 GiB of f32[256,4096,2304] all-gathers per step.  Constraining dW
    to the weight's sharding forces the local-contraction + psum form
    (the WFBP gradient all-reduce the paper schedules).  See
    EXPERIMENTS.md §Perf."""
    from repro.sharding.specs import param_rules, spec_tree

    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not getattr(mesh, "axis_names", ()):
        return grads
    specs = spec_tree(grads, param_rules(cfg.name, multi_pod, layout), mesh)
    return jax.tree.map(
        lambda g, s: jax.lax.with_sharding_constraint(g, s), grads, specs
    )


# ---------------------------------------------------------------------------
# DeFT phase step (shared body)
# ---------------------------------------------------------------------------
def _sync_primary(x: jax.Array, dp_axes: Tuple[str, ...]) -> jax.Array:
    return jax.lax.psum(x, dp_axes)


def _sync_secondary(
    x: jax.Array, dp_axes: Tuple[str, ...], dp_sizes: Dict[str, int],
    chain: Optional[Tuple[int, ...]] = None,
) -> jax.Array:
    """Slow-link sync for secondary-assigned buckets.

    With ``chain`` (the secondary link's device-order permutation from
    ``launch.mesh.ring_chain``) and a single DP axis, the all-reduce runs
    as ppermute rounds along that chain (``train.chains``) — genuinely
    distinct wires from the primary axis, bitwise-equal to ``psum``.
    Multi-axis DP keeps the chain off: splitting the joint psum into
    per-axis stages changes the float reduction grouping, and bitwise
    parity with the single-collective path is the contract.

    Without a chain: hierarchical reduce-scatter over the innermost DP
    axis, all-reduce over the outer (pod/DCN) axes, then all-gather.
    Falls back to a plain psum when the leading dim does not tile (the
    all-reduce is numerically identical, only the link shaping is
    lost)."""
    fast = dp_axes[-1]
    size = dp_sizes[fast]
    if chain is not None and len(dp_axes) == 1 and len(chain) == size:
        from repro.train.chains import chain_all_reduce

        return chain_all_reduce(x, fast, chain)
    if x.ndim >= 1 and x.shape[0] % size == 0 and x.shape[0] >= size:
        y = jax.lax.psum_scatter(x, fast, scatter_dimension=0, tiled=True)
        if len(dp_axes) > 1:
            y = jax.lax.psum(y, dp_axes[:-1])
        return jax.lax.all_gather(y, fast, axis=0, tiled=True)
    return jax.lax.psum(x, dp_axes)


def _zeros_like_tree(tree):
    return jax.tree.map(lambda x: jnp.zeros_like(x), tree)


def _deft_body(
    state: TrainState,
    batch: Dict[str, jax.Array],
    *,
    cfg: ArchConfig,
    opt_spec: OptimizerSpec,
    phase: PhaseSpec,
    bucket_of_leaf: Sequence[int],
    dp_axes: Tuple[str, ...],
    dp_sizes: Dict[str, int],
    rules: Dict,
    remat: bool,
    loss_chunk: int = 0,
    unroll: bool = False,
) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """One DeFT phase, executed inside a shard_map manual over dp_axes.

    cur/fut arrive with their leading device axis already stripped to 1 by
    the manual mapping; we work on index [0] and re-add the axis on return.
    """
    n_dp = 1
    for a in dp_axes:
        n_dp *= dp_sizes[a]
    params, opt = state["params"], state["opt"]
    with logical_rules(rules):
        (loss, parts), grads = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, batch, remat=remat,
                              loss_chunk=loss_chunk, unroll=unroll),
            has_aux=True,
        )(params)

    g_leaves, treedef = jax.tree_util.tree_flatten(grads)
    cur_leaves = [c[0] for c in jax.tree_util.tree_flatten(state["cur"])[0]]
    fut_leaves = [f[0] for f in jax.tree_util.tree_flatten(state["fut"])[0]]
    assert len(g_leaves) == len(bucket_of_leaf)

    def sync(x: jax.Array, b: int) -> jax.Array:
        if phase.secondary[b]:
            return _sync_secondary(x, dp_axes, dp_sizes)
        return _sync_primary(x, dp_axes)

    if phase.rotate:
        # fresh generation merges with the future accumulator (Cases 3/4)
        gen = [g.astype(jnp.float32) + f for g, f in zip(g_leaves, fut_leaves)]
        gen = [
            sync(x, bucket_of_leaf[i]) if phase.route_new[bucket_of_leaf[i]] == "sync" else x
            for i, x in enumerate(gen)
        ]
        new_fut = [jnp.zeros_like(f) for f in fut_leaves]
    else:
        # Cases 1/2: fresh gradients accumulate locally
        gen = None
        new_fut = [f + g.astype(jnp.float32) for f, g in zip(fut_leaves, g_leaves)]

    # older generation buckets scheduled this phase (fwd Case 1 + bwd Case 2/3)
    cur_synced = [
        sync(c, bucket_of_leaf[i]) if phase.sync_cur[bucket_of_leaf[i]] else c
        for i, c in enumerate(cur_leaves)
    ]

    updated = jnp.asarray(phase.do_update)
    if phase.do_update:
        src = cur_synced if phase.update_source == "cur" else gen
        grad_tree = jax.tree_util.tree_unflatten(treedef, src)
        scale = 1.0 / (n_dp * phase.update_k)
        params, opt = apply_updates(opt_spec, params, grad_tree, opt, grad_scale=scale)
        if phase.update_source == "cur":
            # the consumed generation is replaced by the fresh one (rotate)
            # or — in a forced-liveness non-rotate phase — left empty until
            # the next Case-4 rotation fills it from the future accumulator
            new_cur = gen if gen is not None else [
                jnp.zeros_like(c) for c in cur_synced
            ]
        else:
            new_cur = [jnp.zeros_like(c) for c in cur_synced]
    elif phase.rotate:
        # Case 4 with leftovers: the (empty) current generation is replaced
        new_cur = gen
    else:
        new_cur = cur_synced

    mean_loss = jax.lax.psum(loss, dp_axes) / n_dp
    metrics = {
        "loss": mean_loss,
        **{k: jax.lax.psum(v, dp_axes) / n_dp for k, v in parts.items()},
        "updated": updated,
        "k": jnp.asarray(phase.update_k, jnp.int32),
    }
    new_state = {
        "params": params,
        "opt": opt,
        "cur": jax.tree_util.tree_unflatten(treedef, [c[None] for c in new_cur]),
        "fut": jax.tree_util.tree_unflatten(treedef, [f[None] for f in new_fut]),
    }
    return new_state, metrics


# ---------------------------------------------------------------------------
# shard_map wrappers
# ---------------------------------------------------------------------------
def _dp_sizes(mesh, dp_axes: Tuple[str, ...]) -> Dict[str, int]:
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    return {a: int(shape[a]) for a in dp_axes}


def _state_specs(state: TrainState, dp_axes: Tuple[str, ...]):
    """Manual-axis in/out specs: params/opt replicated over dp, accumulators
    split on their leading device axis."""
    rep = jax.tree.map(lambda _: P(), {"params": state["params"], "opt": state["opt"]})
    acc = jax.tree.map(
        lambda _: P(dp_axes if len(dp_axes) > 1 else dp_axes[0]),
        {"cur": state["cur"], "fut": state["fut"]},
    )
    return {**rep, **acc}


def _batch_specs(batch: Dict[str, jax.Array], dp_axes: Tuple[str, ...]):
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    return jax.tree.map(lambda x: P(*((dp,) + (None,) * (x.ndim - 1))), batch)


def deft_phase_step(
    state: TrainState,
    batch: Dict[str, jax.Array],
    *,
    cfg: ArchConfig,
    opt_spec: OptimizerSpec,
    phase: PhaseSpec,
    bucket_of_leaf: Sequence[int],
    mesh,
    multi_pod: bool = False,
    remat: bool = True,
    loss_chunk: int = 0,
    unroll: bool = False,
) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """DeFT phase with explicit DP (params replicated over DP axes)."""
    dp_axes = ("pod", "data") if multi_pod else ("data",)
    dp_sizes = _dp_sizes(mesh, dp_axes)
    body = functools.partial(
        _deft_body,
        cfg=cfg,
        opt_spec=opt_spec,
        phase=phase,
        bucket_of_leaf=tuple(bucket_of_leaf),
        dp_axes=dp_axes,
        dp_sizes=dp_sizes,
        rules=rules_deft_manual_dp(),
        remat=remat,
        loss_chunk=loss_chunk,
        unroll=unroll,
    )
    in_specs = (_state_specs(state, dp_axes), _batch_specs(batch, dp_axes))
    out_state_specs = _state_specs(state, dp_axes)
    out_metric_specs = {
        "loss": P(), "ce": P(), "aux": P(), "updated": P(), "k": P()
    }
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(out_state_specs, out_metric_specs),
        axis_names=set(dp_axes),
        check_vma=False,
    )(state, batch)


def deft_rs_phase_step(
    state: TrainState,
    batch: Dict[str, jax.Array],
    *,
    cfg: ArchConfig,
    opt_spec: OptimizerSpec,
    phase: PhaseSpec,
    bucket_of_leaf: Sequence[int],
    mesh,
    remat: bool = True,
    loss_chunk: int = 0,
    unroll: bool = False,
) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """DeFT hierarchical path for FSDP archs: manual over 'pod' only.

    Params and optimizer state stay FSDP-sharded over 'data' (auto — XLA
    keeps the intra-pod reduce-scatter every step); the phase masks gate
    the *inter-pod* psums, i.e. DeFT schedules the slow DCN link.  Only
    meaningful on the multi-pod mesh.
    """
    assert "pod" in mesh.axis_names, "DeFT-RS needs the multi-pod mesh"
    dp_axes = ("pod",)
    dp_sizes = _dp_sizes(mesh, dp_axes)
    body = functools.partial(
        _deft_body,
        cfg=cfg,
        opt_spec=opt_spec,
        phase=phase,
        bucket_of_leaf=tuple(bucket_of_leaf),
        dp_axes=dp_axes,
        dp_sizes=dp_sizes,
        rules=rules_deft_rs_manual_pod(),
        remat=remat,
        loss_chunk=loss_chunk,
        unroll=unroll,
    )
    in_specs = (_state_specs(state, dp_axes), _batch_specs(batch, dp_axes))
    out_metric_specs = {
        "loss": P(), "ce": P(), "aux": P(), "updated": P(), "k": P()
    }
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(_state_specs(state, dp_axes), out_metric_specs),
        axis_names=set(dp_axes),
        check_vma=False,
    )(state, batch)


# ---------------------------------------------------------------------------
# Per-schedule step-function factory
# ---------------------------------------------------------------------------
def make_deft_step_fns(
    cfg: ArchConfig,
    opt_spec: OptimizerSpec,
    schedule: DeftSchedule,
    bucket_of_leaf: Sequence[int],
    mesh,
    *,
    multi_pod: bool = False,
    fsdp: bool = False,
    remat: bool = True,
    loss_chunk: int = 0,
) -> List[Callable]:
    """LEGACY per-leaf path: one jitted executable per distinct phase,
    one psum per parameter leaf, tree-shaped accumulators, no donation.

    Kept as the semantic reference and benchmark baseline; production
    code uses :class:`repro.train.runtime.DeftRuntime` (bucket-fused
    collectives, donated buffers, AOT phase cache)."""
    step_impl = deft_rs_phase_step if fsdp else deft_phase_step
    fns: List[Callable] = []
    seen: Dict[PhaseSpec, Callable] = {}
    for phase in schedule.phases:
        if phase not in seen:
            kw = dict(
                cfg=cfg,
                opt_spec=opt_spec,
                phase=phase,
                bucket_of_leaf=tuple(bucket_of_leaf),
                mesh=mesh,
                remat=remat,
                loss_chunk=loss_chunk,
            )
            if not fsdp:
                kw["multi_pod"] = multi_pod
            seen[phase] = jax.jit(functools.partial(step_impl, **kw))
        fns.append(seen[phase])
    return fns
