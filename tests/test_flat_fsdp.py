"""Sharded flat-state engine (DESIGN.md §8, sharded layout): the
FSDP/RS path of DeftRuntime with params and optimizer moments resident
as 1/N shard spans of the flat bucket buffers.

Covers, tier-1 (single device):

* shard-aware ``BucketLayout`` construction (padding to
  ``shard_count * 128``, span math, runtime validation);
* the sharded ``apply_bucket_updates`` path reassembling BITWISE against
  the full-buffer apply (clip off AND clip on with an emulated
  shard-norm psum — the update math is identical, only the collective
  sum order can differ on a real mesh);
* per-shard segment-map slicing;
* the jaxpr op-count claim: the sharded update path is O(buckets), the
  ZeRO-style per-leaf update over the same shard-sized state O(leaves);
* bf16 compute against the f32 master (mixed-precision satellite).

The true multi-device end-to-end equivalence run (4 forced host
devices, secondary-synced bucket, donation, tree-RS reference on
jax >= 0.5) lives in the ``multidevice``-marked subprocess test at the
bottom — wired into CI via the multidevice job.
"""
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import make_batch
from repro.kernels.bucket_update import (
    apply_bucket_updates,
    build_segments,
    init_flat_opt_state,
)
from repro.optim.optimizers import adamw, sgd_momentum
from repro.train.bucketing import (
    PAD_MULTIPLE,
    build_bucket_layout,
    flatten_buckets,
)

N_SHARDS = 4


def _tree():
    key = jax.random.PRNGKey(0)
    return {
        "w": jax.random.normal(key, (37, 9)),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (13,)),
        "h": jax.random.normal(jax.random.fold_in(key, 2), (200,)),
        "u": jax.random.normal(jax.random.fold_in(key, 3), (5, 7, 3)),
    }


def _sharded_layout(params, n_shards=N_SHARDS):
    return build_bucket_layout(params, (0, 1, 1, 0), 2,
                               shard_count=n_shards)


# ---------------------------------------------------------------------------
# Shard-aware layout construction
# ---------------------------------------------------------------------------
def test_shard_layout_padding_and_span_math():
    params = _tree()
    lay = _sharded_layout(params)
    assert lay.shards == N_SHARDS
    unit = N_SHARDS * PAD_MULTIPLE
    for b in range(lay.n_buckets):
        assert lay.buf_sizes[b] % unit == 0
        assert lay.buf_sizes[b] >= lay.sizes[b]
        assert lay.buf_sizes[b] - lay.sizes[b] < unit       # minimal pad
        assert lay.shard_sizes[b] == lay.buf_sizes[b] // N_SHARDS
        assert lay.shard_sizes[b] % PAD_MULTIPLE == 0       # kernel operand
    # the replicated layout of the same tree is a prefix of the sharded
    # one: identical leaf offsets/sizes, only the allocation grows
    rep = build_bucket_layout(params, (0, 1, 1, 0), 2)
    assert rep.offsets == lay.offsets and rep.sizes == lay.sizes
    assert all(p >= r for p, r in zip(lay.buf_sizes, rep.buf_sizes))
    # flatten fills the longer allocation with zero tails
    flat = flatten_buckets(lay, jax.tree.leaves(params))
    for b, f in enumerate(flat):
        assert f.shape == (lay.buf_sizes[b],)
        assert not np.any(np.asarray(f[lay.sizes[b]:]))


def test_shard_layout_rejects_bad_counts():
    with pytest.raises(ValueError, match="shard_count"):
        build_bucket_layout(_tree(), (0, 1, 1, 0), 2, shard_count=0)


def test_runtime_rejects_mismatched_shard_layout(single_mesh):
    """A DeftRuntime(fsdp=True) over a layout whose shard count does not
    match the mesh 'data' axis must fail loudly at construction, not
    deep inside the first compile."""
    from repro.configs import get_config, reduce_for_smoke
    from repro.train import DeftRuntime, init_train_state
    from test_train_steps import _schedule_for

    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    opt = adamw(1e-3)
    probe = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    bucket_of, nb, sched = _schedule_for(cfg, probe["params"], cr=0.5)
    lay = build_bucket_layout(probe["params"], bucket_of, nb,
                              shard_count=2)   # mesh data axis is 1
    with pytest.raises(ValueError, match="shard_count"):
        DeftRuntime(cfg, opt, sched, lay, single_mesh, fsdp=True)


# ---------------------------------------------------------------------------
# Sharded update path: bitwise reassembly against the full-buffer apply
# ---------------------------------------------------------------------------
SPECS = [
    adamw(1e-2, grad_clip=0.0, weight_decay=0.01),
    adamw(5.0, weight_decay=0.01),        # lr irrelevant; clip ENGAGES
    sgd_momentum(3e-2, momentum=0.85, weight_decay=0.02, grad_clip=0.0),
    adamw(1e-2, grad_clip=0.0, weight_decay=0.1, decay_mask="matrix",
          ndim1_lr_scale=0.5),            # mixed buckets -> segment maps
]
SPEC_IDS = ["adamw-noclip", "adamw-clip", "sgd-noclip", "adamw-segmented"]


def _shard_state(layout, bufs, s):
    spans = layout.shard_sizes
    return tuple(x[s * spans[b]:(s + 1) * spans[b]]
                 for b, x in enumerate(bufs))


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_sharded_apply_reassembles_bitwise(spec):
    """Each shard runs the fused kernels on its span (pre-masked tail,
    per-shard segment slices, emulated cross-shard norm psum); the
    concatenated result must equal the full-buffer apply bit-for-bit
    when clipping is off — the sharded engine changes residency, never
    update math.  With clipping ON the global norm is reduced
    shard-wise (different partial-sum grouping), so the clip factor can
    move by an ulp: tight tolerance there."""
    params = _tree()
    layout = _sharded_layout(params)
    grads = jax.tree.map(lambda p: p * 3.0, params)  # big: clip engages
    seg = build_segments(layout, spec)
    adam = spec.name == "adamw"

    pbuf = tuple(flatten_buckets(layout, jax.tree.leaves(params)))
    gbuf = tuple(flatten_buckets(layout, jax.tree.leaves(grads)))
    opt_f = init_flat_opt_state(spec, layout.buf_sizes)
    full_p, full_o, _ = apply_bucket_updates(
        spec, seg, pbuf, gbuf, opt_f, grad_scale=0.25, impl="ref"
    )

    # the psum the RS body issues, emulated: sum of the per-shard
    # squared-norm contributions (identical partial sums, same order)
    if spec.grad_clip:
        shard_sq = []
        for s in range(N_SHARDS):
            g_s = _shard_state(layout, gbuf, s)
            sq = sum(
                jnp.sum(jnp.square(g * 0.25)) for g in g_s
            )
            shard_sq.append(sq)
        global_sq = jnp.sum(jnp.stack(shard_sq))
        norm_psum = lambda _t: global_sq
    else:
        norm_psum = None

    got_p, got_m, got_v = [], [], []
    for s in range(N_SHARDS):
        o_s = {"step": opt_f["step"],
               "m": _shard_state(layout, opt_f["m"], s)}
        if adam:
            o_s["v"] = _shard_state(layout, opt_f["v"], s)
        sp, so, _ = apply_bucket_updates(
            spec, seg,
            _shard_state(layout, pbuf, s),
            _shard_state(layout, gbuf, s),
            o_s, grad_scale=0.25, impl="ref",
            shard_id=jnp.int32(s), norm_psum=norm_psum,
        )
        got_p.append(sp)
        got_m.append(so["m"])
        if adam:
            got_v.append(so["v"])
        assert int(so["step"]) == 1

    exact = spec.grad_clip == 0.0

    def check(re, full, what):
        if exact:
            assert bool(jnp.array_equal(re, full)), what
        else:
            np.testing.assert_allclose(np.asarray(re), np.asarray(full),
                                       atol=1e-6, rtol=1e-6, err_msg=what)

    for b in range(layout.n_buckets):
        re_p = jnp.concatenate([got_p[s][b] for s in range(N_SHARDS)])
        check(re_p, full_p[b], f"params bucket {b}")
        re_m = jnp.concatenate([got_m[s][b] for s in range(N_SHARDS)])
        check(re_m, full_o["m"][b], f"m bucket {b}")
        if adam:
            re_v = jnp.concatenate([got_v[s][b] for s in range(N_SHARDS)])
            check(re_v, full_o["v"][b], f"v bucket {b}")
        # tails stay exactly zero without the kernels' static mask
        assert not np.any(np.asarray(re_p[layout.sizes[b]:]))


def test_sharded_apply_masks_hostile_gradient_tail():
    """NaN riding the padded tail of the LAST shard's gradient span must
    not leak: the pre-mask zeroes it before both the clip norm and the
    kernel (the sharded twin of test_tail_garbage_is_masked)."""
    spec = adamw(1e-2)                                  # clip on
    params = _tree()
    layout = _sharded_layout(params)
    seg = build_segments(layout, spec)
    gbuf = [g.at[layout.sizes[b]:].set(jnp.nan)
            for b, g in enumerate(flatten_buckets(
                layout, jax.tree.leaves(params)))]
    pbuf = tuple(flatten_buckets(layout, jax.tree.leaves(params)))
    opt_f = init_flat_opt_state(spec, layout.buf_sizes)
    s = N_SHARDS - 1                                    # tail shard
    o_s = {"step": opt_f["step"], "m": _shard_state(layout, opt_f["m"], s),
           "v": _shard_state(layout, opt_f["v"], s)}
    sp, _, _ = apply_bucket_updates(
        spec, seg, _shard_state(layout, pbuf, s),
        _shard_state(layout, gbuf, s), o_s,
        grad_scale=1.0, impl="ref", shard_id=jnp.int32(s),
        norm_psum=lambda t: t,
    )
    for b in range(layout.n_buckets):
        assert bool(jnp.all(jnp.isfinite(sp[b]))), f"bucket {b}"


@pytest.mark.parametrize("clip", [0.0, 1.0], ids=["noclip", "clip"])
def test_single_shard_apply_degrades_to_unsharded(clip):
    """layout.shards == 1 (1-device FSDP smoke runs): the sharded path's
    span IS the whole buffer, and passing shard_id=0 must reproduce the
    unsharded apply instead of rejecting the layout — bit-for-bit with
    clipping off; to an ulp with clipping on (the norm reduces over the
    masked whole buffer vs the valid slice: same values, different
    pairwise-sum grouping)."""
    spec = adamw(1e-2, weight_decay=0.01, grad_clip=clip)
    params = _tree()
    layout = build_bucket_layout(params, (0, 1, 1, 0), 2)   # shards == 1
    grads = jax.tree.map(lambda p: p * 3.0, params)
    seg = build_segments(layout, spec)
    pbuf = tuple(flatten_buckets(layout, jax.tree.leaves(params)))
    gbuf = tuple(flatten_buckets(layout, jax.tree.leaves(grads)))
    opt_f = init_flat_opt_state(spec, layout.buf_sizes)
    full_p, _, _ = apply_bucket_updates(spec, seg, pbuf, gbuf, opt_f,
                                        grad_scale=0.25, impl="ref")
    sh_p, _, _ = apply_bucket_updates(
        spec, seg, pbuf, gbuf, opt_f, grad_scale=0.25, impl="ref",
        shard_id=jnp.int32(0), norm_psum=lambda t: t,
    )
    for a, b in zip(sh_p, full_p):
        if clip == 0.0:
            assert bool(jnp.array_equal(a, b))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=1e-6)


def test_runtime_flat_fsdp_runs_on_single_device(single_mesh):
    """The launch path for an FSDP arch on a 1-device debug mesh:
    shard_count=1 layout + DeftRuntime(fsdp=True) must construct,
    compile and step (the degenerate sharded engine) — a regression
    here used to surface only deep inside the first phase trace.  Runs
    in bf16 to also cover the sharded mixed-precision path (spans cast
    down BEFORE the param all-gather), checked tight-tol against the
    replicated flat bf16 engine on the same mesh."""
    from repro.configs import get_config, reduce_for_smoke
    from repro.train import DeftRuntime, init_train_state
    from test_train_steps import B, S, _schedule_for

    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(0)
    probe = init_train_state(key, cfg, opt)
    bucket_of, nb, sched = _schedule_for(cfg, probe["params"], cr=0.5)
    layout = build_bucket_layout(probe["params"], bucket_of, nb,
                                 shard_count=1)
    with single_mesh:
        rt = DeftRuntime(cfg, opt, sched, layout, single_mesh, fsdp=True,
                         compute_dtype=jnp.bfloat16)
        assert rt.flat_state and rt.stats()["sharded_state"]
        state = rt.init_state(key, dtype=jnp.bfloat16)
        rt_rep = DeftRuntime(cfg, opt, sched, layout, single_mesh,
                             compute_dtype=jnp.bfloat16)
        state_rep = rt_rep.init_state(key, dtype=jnp.bfloat16)
        for step in range(sched.period + 1):
            batch = make_batch(cfg, 0, step, B, S)
            state, m = rt.step(step, state, batch)
            state_rep, _ = rt_rep.step(step, state_rep, batch)
            assert bool(jnp.isfinite(m["loss"]))
        diff = max(
            float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(rt.params_tree(state)),
                            jax.tree.leaves(rt_rep.params_tree(state_rep)))
        )
        # same bf16 forward (the pre-gather cast is elementwise), same
        # f32 master updates; only collective rounding can differ
        assert diff < 1e-5, diff


def test_sharded_apply_with_clip_requires_norm_psum():
    """A sharded update with grad_clip on and no cross-shard norm psum
    would clip every shard from 1/N of the gradient — it must fail
    loudly, not silently diverge params."""
    params = _tree()
    layout = _sharded_layout(params)
    spec = adamw(1e-2)                                  # clip on
    seg = build_segments(layout, spec)
    pbuf = tuple(flatten_buckets(layout, jax.tree.leaves(params)))
    opt_f = init_flat_opt_state(spec, layout.buf_sizes)
    p_s = _shard_state(layout, pbuf, 0)
    o_s = {"step": opt_f["step"], "m": _shard_state(layout, opt_f["m"], 0),
           "v": _shard_state(layout, opt_f["v"], 0)}
    with pytest.raises(ValueError, match="norm_psum"):
        apply_bucket_updates(spec, seg, p_s, p_s, o_s,
                             shard_id=jnp.int32(0))


# ---------------------------------------------------------------------------
# Per-shard segment maps
# ---------------------------------------------------------------------------
def test_element_hparams_shard_slices_consistently():
    params = _tree()
    layout = _sharded_layout(params)
    spec = adamw(1e-2, weight_decay=0.1, decay_mask="matrix",
                 ndim1_lr_scale=0.5)
    seg = build_segments(layout, spec)
    for b in range(layout.n_buckets):
        assert seg.uniform(b) is None                   # mixed buckets
        sc_full, wd_full = seg.element_hparams(b)
        span = layout.shard_sizes[b]
        for s in range(N_SHARDS):
            sc, wd = seg.element_hparams_shard(b, s, N_SHARDS)
            assert sc.shape == (span,)
            assert (sc == sc_full[s * span:(s + 1) * span]).all()
            assert (wd == wd_full[s * span:(s + 1) * span]).all()
    with pytest.raises(ValueError, match="does not split"):
        seg.element_hparams_shard(0, 0, N_SHARDS + 1)


# ---------------------------------------------------------------------------
# Structural O(buckets) claim on the sharded update path
# ---------------------------------------------------------------------------
def test_sharded_update_is_o_buckets_not_o_leaves():
    """Same structural claim as the replicated engine's jaxpr op-count
    test, on the RS path: the sharded fused apply (one kernel per bucket
    span + pre-mask + slice) grows with the bucket count; a ZeRO-style
    per-leaf update over the equivalent 1/N state grows with the leaf
    count.  Wall clock on CPU is load-noisy; this is deterministic."""
    from repro.optim.optimizers import apply_updates, init_opt_state
    from _jaxpr_census import count_eqns

    n_leaves, leaf_elems, n_buckets, n_shards = 64, 512, 4, 4
    key = jax.random.PRNGKey(5)
    tree = {
        f"l{i:03d}": jax.random.normal(jax.random.fold_in(key, i),
                                       (leaf_elems,))
        for i in range(n_leaves)
    }
    grads = jax.tree.map(lambda p: p * 0.01, tree)
    bo = tuple(i * n_buckets // n_leaves for i in range(n_leaves))
    layout = build_bucket_layout(tree, bo, n_buckets, shard_count=n_shards)
    spec = adamw(1e-3)
    seg = build_segments(layout, spec)
    pbuf = tuple(flatten_buckets(layout, jax.tree.leaves(tree)))
    gbuf = tuple(flatten_buckets(layout, jax.tree.leaves(grads)))
    opt_f = init_flat_opt_state(spec, layout.buf_sizes)
    p_s = _shard_state(layout, pbuf, 0)
    g_s = _shard_state(layout, gbuf, 0)
    o_s = {"step": opt_f["step"], "m": _shard_state(layout, opt_f["m"], 0),
           "v": _shard_state(layout, opt_f["v"], 0)}

    n_flat = count_eqns(jax.make_jaxpr(
        lambda p, g, o, i: apply_bucket_updates(
            spec, seg, p, g, o, grad_scale=0.1, shard_id=i,
            norm_psum=lambda t: t)[:2]
    )(p_s, g_s, o_s, jnp.int32(0)).jaxpr)

    # ZeRO per-leaf reference: every leaf sharded 1/N, still one op
    # sequence per leaf
    shard_tree = jax.tree.map(lambda x: x[: x.size // n_shards], tree)
    shard_grads = jax.tree.map(lambda x: x[: x.size // n_shards], grads)
    opt_l = init_opt_state(spec, shard_tree)
    n_leaf = count_eqns(jax.make_jaxpr(
        lambda p, g, o: apply_updates(spec, p, g, o, grad_scale=0.1)
    )(shard_tree, shard_grads, opt_l).jaxpr)

    assert n_flat < n_leaf / 4, (n_flat, n_leaf)
    assert n_leaf > n_leaves


# ---------------------------------------------------------------------------
# bf16 compute against the f32 flat master (mixed-precision satellite)
# ---------------------------------------------------------------------------
def test_flat_bf16_matches_tree_bf16_reference(single_mesh):
    """flat_state + compute_dtype=bf16: the forward/backward runs in
    bf16 (cast at the buffer views), the master copy and moments stay
    f32.  Against the tree-path bf16 runtime (params *stored* bf16) the
    trajectories agree to bf16 rounding: the first update is identical
    (both inits are the same bf16 draw, both apply in f32), after which
    the master accumulates what the tree path rounds away — the gap per
    period stays well under one bf16 ulp of the weights."""
    from repro.configs import get_config, reduce_for_smoke
    from repro.train import DeftRuntime, init_train_state
    from test_train_steps import B, S, _schedule_for

    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(0)
    probe = init_train_state(key, cfg, opt)
    bucket_of, nb, sched = _schedule_for(cfg, probe["params"], cr=1.8)
    layout = build_bucket_layout(probe["params"], bucket_of, nb)
    del probe

    with single_mesh:
        rt_f = DeftRuntime(cfg, opt, sched, layout, single_mesh,
                           compute_dtype=jnp.bfloat16)
        rt_t = DeftRuntime(cfg, opt, sched, layout, single_mesh,
                           flat_state=False)
        s_f = rt_f.init_state(key, dtype=jnp.bfloat16)
        s_t = rt_t.init_state(key, dtype=jnp.bfloat16)
        # identical starting point: the f32 master holds the exact bf16
        # init values
        for a, b in zip(jax.tree.leaves(rt_f.params_tree(s_f)),
                        jax.tree.leaves(s_t["params"])):
            assert a.dtype == jnp.float32 and b.dtype == jnp.bfloat16
            assert bool(jnp.array_equal(a, b.astype(jnp.float32)))
        for step in range(2 * sched.period):
            batch = make_batch(cfg, 0, step, B, S)
            s_f, m_f = rt_f.step(step, s_f, batch)
            s_t, m_t = rt_t.step(step, s_t, batch)
            diff = max(
                float(jnp.max(jnp.abs(a - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(rt_f.params_tree(s_f)),
                                jax.tree.leaves(rt_t.params_tree(s_t)))
            )
            assert diff < 5e-3, f"step {step}: bf16 paths diverged {diff}"
        assert rt_f.stats()["compute_dtype"] == "bfloat16"


def test_flat_bf16_requires_matching_compute_dtype(single_mesh):
    from repro.configs import get_config, reduce_for_smoke
    from repro.train import DeftRuntime, init_train_state
    from test_train_steps import _schedule_for

    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    opt = adamw(1e-3)
    probe = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    bucket_of, nb, sched = _schedule_for(cfg, probe["params"], cr=0.5)
    layout = build_bucket_layout(probe["params"], bucket_of, nb)
    with single_mesh:
        rt = DeftRuntime(cfg, opt, sched, layout, single_mesh)
        with pytest.raises(ValueError, match="compute_dtype"):
            rt.init_state(jax.random.PRNGKey(0), dtype=jnp.bfloat16)


# ---------------------------------------------------------------------------
# True multi-device end-to-end equivalence (4 forced host devices)
# ---------------------------------------------------------------------------
_SCRIPT = r"""
import dataclasses
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs import get_config, reduce_for_smoke
from repro.core.bucket import BucketTimes
from repro.core.deft import solve_schedule
from repro.core.scheduler import SchedulerConfig
from repro.core.profiler import HardwareModel
from repro.data.pipeline import make_batch
from repro.models.model import loss_fn
from repro.optim.optimizers import adamw, apply_updates, init_opt_state
from repro.train import (DeftRuntime, assign_buckets, build_bucket_layout,
                         init_train_state, leaf_bucket_times)

mesh = jax.make_mesh((2, 2, 1), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
cfg = reduce_for_smoke(get_config("qwen3-4b"))
opt = adamw(1e-3)
key = jax.random.PRNGKey(0)
probe = init_train_state(key, cfg, opt)
bucket_of, nb = assign_buckets(probe["params"], cfg, partition_elems=150_000)
B, S = 8, 32
times = leaf_bucket_times(probe["params"], cfg, bucket_of, nb,
                          HardwareModel(dp_degree=4), S, 2)
scale = 1.8 * (times.fwd_total + times.bwd_total) / times.comm_total
times = BucketTimes(times.fwd, times.bwd, tuple(c * scale for c in times.comm))
sched = solve_schedule(times, SchedulerConfig())
assert sched.updates_per_period < sched.period, "want a merging schedule"

# force one rotating sync phase onto the secondary link so the
# hierarchical chain is exercised end to end
phases, forced = [], False
for ph in sched.phases:
    if not forced and ph.rotate and any(r == "sync" for r in ph.route_new):
        sec = tuple(r == "sync" for r in ph.route_new)
        phases.append(dataclasses.replace(ph, secondary=sec))
        forced = True
    else:
        phases.append(ph)
assert forced, "schedule has no rotating sync phase to mark secondary"
sched = dataclasses.replace(sched, phases=tuple(phases))

lay_sh = build_bucket_layout(probe["params"], bucket_of, nb, shard_count=2)
lay_rep = build_bucket_layout(probe["params"], bucket_of, nb)

# python-level gradient-accumulation reference (global gradients)
ref_params = probe["params"]
ref_opt = init_opt_state(opt, ref_params)
zeros = lambda: jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             ref_params)
ref_cur, ref_fut = zeros(), zeros()
gfn = jax.jit(jax.grad(lambda p, b: loss_fn(p, cfg, b)[0]))

with mesh:
    rt = DeftRuntime(cfg, opt, sched, lay_sh, mesh, fsdp=True)
    assert rt.flat_state and rt.stats()["sharded_state"]
    state = rt.init_state(key)
    # 1/N residency: every param/moment buffer is split over 'data' and
    # each device holds exactly one span
    for part in (state["pbuf"], state["opt"]["m"], state["opt"]["v"]):
        for b, a in enumerate(part):
            assert a.sharding.spec == P("data"), a.sharding
            shard_elems = {s.data.size for s in a.addressable_shards}
            assert shard_elems == {lay_sh.shard_sizes[b]}
    rt.compile(state, make_batch(cfg, 0, 0, B, S))

    # replicated flat engine over the same (pod, data) axes: the
    # semantics twin with full-size resident buffers
    rt_rep = DeftRuntime(cfg, opt, sched, lay_rep, mesh, multi_pod=True)
    state_rep = rt_rep.init_state(key)

    for step in range(2 * sched.period):
        batch = make_batch(cfg, 0, step, B, S)
        ph = sched.phases[step % sched.period]
        prev = state
        state, m = rt.step(step, state, batch)
        assert all(x.is_deleted() for x in jax.tree.leaves(prev)), \
            "donation must hold on the sharded engine"
        state_rep, m_rep = rt_rep.step(step, state_rep, batch)

        g = gfn(ref_params, batch)
        if ph.rotate:
            gen = jax.tree.map(lambda a, b: a.astype(jnp.float32) + b, g,
                               ref_fut)
            ref_fut = jax.tree.map(jnp.zeros_like, ref_fut)
        else:
            ref_fut = jax.tree.map(lambda f, a: f + a.astype(jnp.float32),
                                   ref_fut, g)
            gen = None
        if ph.do_update:
            src = ref_cur if ph.update_source == "cur" else gen
            ref_params, ref_opt = apply_updates(
                opt, ref_params, src, ref_opt, grad_scale=1.0 / ph.update_k)
            ref_cur = gen if ph.update_source == "cur" else \
                jax.tree.map(jnp.zeros_like, ref_cur)
        elif ph.rotate:
            ref_cur = gen
        got = jax.tree.leaves(rt.params_tree(state))
        diff_ref = max(float(jnp.max(jnp.abs(a - b)))
                       for a, b in zip(got, jax.tree.leaves(ref_params)))
        assert diff_ref < 1e-4, f"step {step}: vs reference {diff_ref}"
        diff_rep = max(
            float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(got,
                            jax.tree.leaves(rt_rep.params_tree(state_rep))))
        # same update math; only collective summation order differs
        assert diff_rep < 2e-6, f"step {step}: vs replicated {diff_rep}"

    # checkpoint boundary roundtrips exactly through the sharded form
    tree_state = rt.state_to_tree(state)
    back = rt.tree_to_state(tree_state)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(state)):
        assert bool(jnp.array_equal(a, b)), "sharded roundtrip not exact"

# tree-state RS reference (flat_state=False, XLA-auto FSDP)
with mesh:
    rt_tree = DeftRuntime(cfg, opt, sched, lay_rep, mesh, fsdp=True,
                          flat_state=False)
    state_t = rt_tree.init_state(key)
    state_s = rt.init_state(key)
    for step in range(sched.period + 1):
        batch = make_batch(cfg, 0, step, B, S)
        state_t, _ = rt_tree.step(step, state_t, batch)
        state_s, _ = rt.step(step, state_s, batch)
    diff = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(rt.params_tree(state_s)),
                        jax.tree.leaves(rt_tree.params_tree(state_t))))
    assert diff < 1e-5, f"sharded vs tree-RS reference: {diff}"
    print(f"TREE_RS_COMPARED diff={diff:.2e}")
print("FLAT_FSDP_OK")
"""


@pytest.mark.slow
@pytest.mark.multidevice
def test_flat_fsdp_engine_on_4_devices(tmp_path):
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    script = tmp_path / "run.py"
    script.write_text(_SCRIPT)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, str(script), src],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "FLAT_FSDP_OK" in out.stdout
