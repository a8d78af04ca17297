"""Layout-agnostic runtime state + cycle-boundary re-pack (DESIGN.md §9).

* `LayoutTransition` span math: repack == re-flatten, bitwise, for any
  pair of layouts of the same tree (property test incl. shard counts);
  A->B->A is the identity.
* The real fused runtime hot-swaps onto a DIFFERENT bucket partition at
  a cycle boundary — no restart — and the post-swap trajectory BIT-MATCHES
  a reference run compiled directly under the new layout.  Covered for
  the replicated flat engine (driven end-to-end by the adaptive
  controller on a BandwidthDrop whose calibrated profile favors another
  partition) and for the sharded RS engine (degenerate 1-shard tier-1
  case; the true 4->2 shard-count change runs in the multidevice test
  at the bottom).
* ZeRO gather skip: phases not preceded by an update reuse the stored
  param gather — bitwise-identical trajectories with N fewer all-gathers
  per skipping phase.
* Checkpoints written under one layout restore under another by routing
  the flat accumulators through the transition.
"""
import dataclasses
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from _jaxpr_census import count_primitives
from repro.adapt import (
    AdaptConfig,
    AdaptiveController,
    BandwidthDrop,
    RepartitionConfig,
    Repartitioner,
    SyntheticTelemetrySource,
)
from repro.configs import get_config
from repro.core.bucket import BucketTimes
from repro.core.deft import feedback_solve
from repro.core.preserver import WalkParams
from repro.core.profiler import HardwareModel
from repro.data.pipeline import make_batch
from repro.models.model import init_params
from repro.optim.optimizers import adamw
from repro.train import (
    DeftRuntime,
    assign_buckets,
    build_bucket_layout,
    build_layout_transition,
    build_leaf_time_model,
    flatten_buckets,
    leaf_bucket_times,
    repack_buffers,
    unflatten_buckets,
)

WALK = WalkParams(s0=4.0, eta=0.01, mu=1.0, sigma=40.0, batch=256)
B, S = 4, 32


# ---------------------------------------------------------------------------
# LayoutTransition span math
# ---------------------------------------------------------------------------
def _tree():
    key = jax.random.PRNGKey(0)
    return {
        "w": jax.random.normal(key, (37, 9)),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (13,)),
        "h": jax.random.normal(jax.random.fold_in(key, 2), (200,)),
        "u": jax.random.normal(jax.random.fold_in(key, 3), (5, 7, 3)),
    }


def test_transition_spans_cover_dst_exactly():
    tree = _tree()
    src = build_bucket_layout(tree, (0, 1, 1, 0), 2)
    dst = build_bucket_layout(tree, (0, 1, 2, 2), 3, shard_count=2)
    tr = build_layout_transition(src, dst)
    for b in range(dst.n_buckets):
        covered = sorted((c.dst_off, c.dst_off + c.length)
                         for c in tr.copies[b])
        cursor = 0
        for lo, hi in covered:
            assert lo == cursor     # dense, in order
            cursor = hi
        assert cursor == dst.sizes[b]
    # reverse is the inverse mapping
    back = tr.reverse()
    assert back.src == dst and back.dst == src


def test_shard_count_only_transition_is_one_span_per_bucket():
    """Same partition, different shard count: every bucket's valid data
    is one contiguous run, so the transition merges it to ONE SpanCopy
    (padding alone changes)."""
    tree = _tree()
    a = build_bucket_layout(tree, (0, 1, 1, 0), 2, shard_count=4)
    b = build_bucket_layout(tree, (0, 1, 1, 0), 2, shard_count=2)
    tr = build_layout_transition(a, b)
    for spans in tr.copies:
        assert len(spans) == 1
        assert spans[0].src_off == 0 and spans[0].dst_off == 0


def test_identity_transition_marks_all_identical():
    tree = _tree()
    lay = build_bucket_layout(tree, (0, 1, 1, 0), 2)
    tr = build_layout_transition(lay, lay)
    assert all(tr.identical)
    assert tr.moved_elems == 0
    bufs = flatten_buckets(lay, jax.tree.leaves(tree))
    out = repack_buffers(tr, bufs)
    for a, b in zip(out, bufs):
        assert a is b               # pass-through enables donation alias


def test_transition_rejects_different_trees():
    t1, t2 = _tree(), {"x": jnp.zeros((3, 3))}
    l1 = build_bucket_layout(t1, (0, 1, 1, 0), 2)
    l2 = build_bucket_layout(t2, (0,), 1)
    with pytest.raises(ValueError, match="same parameter tree"):
        build_layout_transition(l1, l2)


@given(
    st.integers(min_value=0, max_value=10_000),
    st.lists(st.integers(min_value=0, max_value=3), min_size=4, max_size=4),
    st.lists(st.integers(min_value=0, max_value=3), min_size=4, max_size=4),
    st.integers(min_value=0, max_value=2),
    st.integers(min_value=0, max_value=2),
)
@settings(max_examples=25, deadline=None)
def test_repack_is_reflatten_and_roundtrips(seed, bo_a, bo_b, sh_a, sh_b):
    """Property: for ANY two layouts of the same tree (random bucket
    assignments, random shard counts), repacking A's buffers through the
    transition equals flattening directly under B, bitwise; and A->B->A
    is the identity."""
    tree = _tree()
    # normalize assignments so bucket ids are dense 0..n-1
    def dense(bo):
        ids = {b: i for i, b in enumerate(dict.fromkeys(bo))}
        return tuple(ids[b] for b in bo), len(ids)

    bo_a, nb_a = dense(bo_a)
    bo_b, nb_b = dense(bo_b)
    lay_a = build_bucket_layout(tree, bo_a, nb_a, shard_count=2 ** sh_a)
    lay_b = build_bucket_layout(tree, bo_b, nb_b, shard_count=2 ** sh_b)
    rng = np.random.default_rng(seed)
    leaves = [jnp.asarray(rng.normal(size=l.shape).astype(np.float32))
              for l in jax.tree.leaves(tree)]
    bufs_a = flatten_buckets(lay_a, leaves)
    bufs_b = flatten_buckets(lay_b, leaves)
    tr = build_layout_transition(lay_a, lay_b)
    got_b = repack_buffers(tr, bufs_a)
    for g, w in zip(got_b, bufs_b):
        assert g.shape == w.shape
        assert bool(jnp.array_equal(g, w))
    back = repack_buffers(tr.reverse(), got_b)
    for g, w in zip(back, bufs_a):
        assert bool(jnp.array_equal(g, w))


def test_repack_preserves_dtype():
    """Pad/gap fills match the src dtype — an f32 zero concatenated into
    a bf16 buffer would silently promote the whole dst buffer."""
    tree = _tree()
    a = build_bucket_layout(tree, (0, 1, 1, 0), 2)
    b = build_bucket_layout(tree, (0, 0, 1, 1), 2, shard_count=2)
    bufs = [x.astype(jnp.bfloat16)
            for x in flatten_buckets(a, jax.tree.leaves(tree))]
    out = repack_buffers(build_layout_transition(a, b), bufs)
    assert all(x.dtype == jnp.bfloat16 for x in out)


def test_repack_rows_accumulator_axis():
    """cur/fut carry a leading device axis: the repack remaps the LAST
    axis only, rows independently."""
    tree = _tree()
    a = build_bucket_layout(tree, (0, 1, 1, 0), 2)
    b = build_bucket_layout(tree, (0, 0, 1, 1), 2, shard_count=2)
    leaves = jax.tree.leaves(tree)
    rows_a = [jnp.stack([f, -2.0 * f])
              for f in flatten_buckets(a, leaves)]
    tr = build_layout_transition(a, b)
    got = repack_buffers(tr, rows_a)
    want = [jnp.stack([f, -2.0 * f]) for f in flatten_buckets(b, leaves)]
    for g, w in zip(got, want):
        assert bool(jnp.array_equal(g, w))


# ---------------------------------------------------------------------------
# Runtime hot-swap onto a different partition (cycle-boundary re-pack)
# ---------------------------------------------------------------------------
def _tiny_cfg():
    base = get_config("qwen3-4b")
    return dataclasses.replace(
        base, name="qwen3-tiny", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512,
    )


def _plan(cfg, params, partition_elems, cr=1.8):
    bucket_of, nb = assign_buckets(params, cfg,
                                   partition_elems=partition_elems)
    t = leaf_bucket_times(params, cfg, bucket_of, nb,
                          HardwareModel(dp_degree=2), S, B)
    scale = cr * (t.fwd_total + t.bwd_total) / t.comm_total
    t = BucketTimes(t.fwd, t.bwd, tuple(c * scale for c in t.comm))
    sched, _, scfg, _ = feedback_solve(t, WALK)
    return bucket_of, nb, t, sched, scfg


def _run_reference_with_swap(cfg, opt, key, sched_a, lay_a, sched_b, lay_b,
                             mesh, swap_step, n_steps, fsdp=False):
    """Reference trajectory: layout-A runtime to the swap boundary, an
    explicit repack, then a runtime compiled DIRECTLY under layout B."""
    rt_a = DeftRuntime(cfg, opt, sched_a, lay_a, mesh, fsdp=fsdp)
    state = rt_a.init_state(key)
    rt_b = DeftRuntime(cfg, opt, sched_b, lay_b, mesh, fsdp=fsdp)
    for step in range(swap_step):
        state, _ = rt_a.step(step, state, make_batch(cfg, 0, step, B, S))
    state = rt_b.repack_state(state, build_layout_transition(lay_a, lay_b))
    for step in range(swap_step, n_steps):
        state, _ = rt_b.step(step - swap_step, state,
                             make_batch(cfg, 0, step, B, S))
    return rt_b, state


@pytest.mark.parametrize("fsdp", [False, True],
                         ids=["replicated", "sharded-rs"])
def test_partition_hot_swap_bitwise(single_mesh, fsdp):
    """prepare_swap(layout=...) re-packs the donated state at the cycle
    boundary; the resulting trajectory bit-matches the reference that
    runs layout A, repacks explicitly, and continues under a runtime
    compiled directly for layout B.  Both flat engines (the sharded one
    in its degenerate 1-shard tier-1 form; real shards run in the
    multidevice test)."""
    cfg = _tiny_cfg()
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    shards = 1
    bo_a, nb_a, _, sched_a, _ = _plan(cfg, params, 20_000)
    bo_b, nb_b, _, sched_b, _ = _plan(cfg, params, 60_000)
    assert bo_a != bo_b, "partitions must differ for this test"
    lay_a = build_bucket_layout(params, bo_a, nb_a, shard_count=shards)
    lay_b = build_bucket_layout(params, bo_b, nb_b, shard_count=shards)

    rt = DeftRuntime(cfg, opt, sched_a, lay_a, single_mesh, fsdp=fsdp)
    state = rt.init_state(key)
    n_steps = 2 * sched_a.period + 2 * sched_b.period
    with jax.set_mesh(single_mesh):
        for step in range(sched_a.period + 1):
            state, _ = rt.step(step, state, make_batch(cfg, 0, step, B, S))
        info = rt.prepare_swap(sched_b, state, make_batch(cfg, 0, 0, B, S),
                               layout=lay_b)
        assert info["layout_change"]
        assert info["n_buckets"] == (nb_a, nb_b)
        for step in range(sched_a.period + 1, n_steps):
            state, _ = rt.step(step, state, make_batch(cfg, 0, step, B, S))

        assert rt.layout_swaps == 1 and rt.layout == lay_b
        swap = rt.swap_log[0]
        assert swap["step"] % sched_a.period == 0
        assert swap["repack_s"] is not None and swap["repack_s"] > 0

        rt_ref, ref_state = _run_reference_with_swap(
            cfg, opt, key, sched_a, lay_a, sched_b, lay_b, single_mesh,
            swap["step"], n_steps, fsdp=fsdp,
        )
    for a, b in zip(jax.tree.leaves(rt.params_tree(state)),
                    jax.tree.leaves(rt_ref.params_tree(ref_state))):
        assert bool(jnp.array_equal(a, b)), \
            "partition hot-swap diverged from the direct-layout reference"


def test_adaptive_repartition_end_to_end(single_mesh):
    """The acceptance scenario: a BandwidthDrop whose calibrated profile
    favors a DIFFERENT partition drives the controller to a
    partition-changing replan; the runtime hot-swaps (repack at a cycle
    boundary, no restart) and the post-swap trajectory bit-matches the
    reference compiled directly under the new layout."""
    cfg = _tiny_cfg()
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    pe = 20_000
    bo, nb, times, schedule, scfg = _plan(cfg, params, pe)
    lay = build_bucket_layout(params, bo, nb)

    # leaf model consistent with `times` (same CR rescale helper)
    model = build_leaf_time_model(params, cfg, HardwareModel(dp_degree=2),
                                  S, B)
    model = model.with_coverage_rate(bo, nb, 1.8)
    assert model.bucket_times(bo, nb) == times
    rp = Repartitioner(model, RepartitionConfig(base_partition_elems=pe))

    drop = BandwidthDrop(step=4, comm_scale=3.0)
    src = SyntheticTelemetrySource(times, drop)
    ctrl = AdaptiveController(
        times, schedule, scfg, walk=WALK,
        cfg=AdaptConfig(warmup_steps=2, check_every=2, cooldown_steps=100,
                        min_loss_samples=10**9),   # timing trigger only
        repartitioner=rp, bucket_of=bo,
    )

    rt = DeftRuntime(cfg, opt, schedule, lay, single_mesh)
    state = rt.init_state(key)
    n_steps = 6 * schedule.period + 8
    event = None
    run_base = None
    new_lay = None
    with jax.set_mesh(single_mesh):
        for step in range(n_steps):
            batch = make_batch(cfg, 0, step, B, S)
            state, m = rt.step(step, state, batch)
            wall = src.wall_time(step, ctrl.schedule, ctrl.scheduler_cfg,
                                 rt.last_phase, solve_times=ctrl.times,
                                 run_base=run_base)
            ev = ctrl.observe(step, rt.last_phase, wall)
            if ev is not None and ev.changed:
                assert event is None, "cooldown should allow one swap"
                event = ev
                assert ev.partition_changed, \
                    "calibrated drop profile should favor repartitioning"
                run_base = rp.base_times_for(ev.partition)
                new_lay = build_bucket_layout(
                    params, ev.partition.bucket_of, ev.partition.n_buckets
                )
                rt.prepare_swap(ev.schedule, state, batch,
                                layout=new_lay, background=False)

        assert event is not None, "no replan despite 3x bandwidth drop"
        assert event.new_n_buckets != nb
        assert event.verdict.ok          # Preserver gated the partition
        st = rt.stats()
        assert st["hot_swaps"] == 1 and st["layout_swaps"] == 1
        swap = rt.swap_log[0]
        assert swap["n_buckets"] == event.new_n_buckets
        assert rt.period == event.schedule.period

        rt_ref, ref_state = _run_reference_with_swap(
            cfg, opt, key, schedule, lay, event.schedule, new_lay,
            single_mesh, swap["step"], n_steps,
        )
    for a, b in zip(jax.tree.leaves(rt.params_tree(state)),
                    jax.tree.leaves(rt_ref.params_tree(ref_state))):
        assert bool(jnp.array_equal(a, b)), \
            "adaptive repartition diverged from the direct-layout reference"


# ---------------------------------------------------------------------------
# ZeRO gather skip (sharded flat engine)
# ---------------------------------------------------------------------------
def _sharded_setup(cr=1.8, pe=40_000):
    cfg = _tiny_cfg()
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    bo, nb, _, sched, _ = _plan(cfg, params, pe, cr=cr)
    lay = build_bucket_layout(params, bo, nb, shard_count=1)
    return cfg, opt, key, sched, lay


def test_gather_reuse_masks_are_static_and_schedule_derived(single_mesh):
    cfg, opt, key, sched, lay = _sharded_setup()
    rt = DeftRuntime(cfg, opt, sched, lay, single_mesh, fsdp=True)
    masks = rt._gather_reuse_masks(sched)
    assert len(masks) == sched.period
    assert not any(masks[0]), "position 0 must always gather"
    for t in range(1, sched.period):
        expect = not sched.phases[t - 1].do_update
        assert all(m == expect for m in masks[t])
    # off switch: no masks, no pgather in the state
    rt_off = DeftRuntime(cfg, opt, sched, lay, single_mesh, fsdp=True,
                         gather_skip=False)
    assert rt_off._gather_reuse_masks(sched) == [None] * sched.period
    assert "pgather" not in rt_off.init_state(key)
    # a schedule with nothing to reuse defaults the cache OFF: an unread
    # cache cannot be donated and would ride every step for nothing
    bo1, nb1, _, sched1, _ = _plan(cfg, init_params(key, cfg), 20_000)
    if not DeftRuntime._schedule_has_reuse(sched1):
        lay1 = build_bucket_layout(init_params(key, cfg), bo1, nb1,
                                   shard_count=1)
        rt1 = DeftRuntime(cfg, opt, sched1, lay1, single_mesh, fsdp=True)
        assert not rt1.stats()["gather_skip"]
        assert "pgather" not in rt1.init_state(key)
    # explicit request on a non-RS engine fails loudly
    with pytest.raises(ValueError, match="gather_skip"):
        DeftRuntime(cfg, opt, sched, lay, single_mesh, gather_skip=True)


def test_gather_skip_bitwise_and_fewer_allgathers(single_mesh):
    """Skip ON vs OFF: bit-identical trajectories (the reused gather IS
    the bytes a fresh all-gather would produce), and each reuse-phase
    jaxpr contains exactly n_buckets fewer all_gather collectives."""
    cfg, opt, key, sched, lay = _sharded_setup()
    if not any(not ph.do_update for ph in sched.phases[:-1]) \
            or sched.period < 2:
        pytest.skip("schedule has no reusable phase at this config")
    with jax.set_mesh(single_mesh):
        rt_on = DeftRuntime(cfg, opt, sched, lay, single_mesh, fsdp=True)
        rt_off = DeftRuntime(cfg, opt, sched, lay, single_mesh, fsdp=True,
                             gather_skip=False)
        assert any(any(m) for m in rt_on._gather_reuse_masks(sched))
        s_on, s_off = rt_on.init_state(key), rt_off.init_state(key)
        for step in range(2 * sched.period + 1):
            batch = make_batch(cfg, 0, step, B, S)
            s_on, _ = rt_on.step(step, s_on, batch)
            s_off, _ = rt_off.step(step, s_off, batch)
        for a, b in zip(jax.tree.leaves(rt_on.params_tree(s_on)),
                        jax.tree.leaves(rt_off.params_tree(s_off))):
            assert bool(jnp.array_equal(a, b)), "gather skip changed math"

        # static collective count: reuse phases drop one all_gather per
        # bucket (the ZeRO param gather)
        reuse_t = next(t for t in range(1, sched.period)
                       if not sched.phases[t - 1].do_update)
        batch = make_batch(cfg, 0, 0, B, S)

        def count_allgather(rt, state, t):
            key_t = rt._schedule_keys(rt.schedule)[t]
            jaxpr = jax.make_jaxpr(
                lambda s, bb: rt._entries[key_t].jitted(s, bb)
            )(state, batch)
            return count_primitives(jaxpr.jaxpr, "all_gather")

        n_on = count_allgather(rt_on, s_on, reuse_t)
        n_off = count_allgather(rt_off, s_off, reuse_t)
        assert n_off - n_on == lay.n_buckets, (n_on, n_off)


# ---------------------------------------------------------------------------
# Cross-layout checkpoint restore
# ---------------------------------------------------------------------------
def test_checkpoint_restores_across_layouts(single_mesh, tmp_path):
    """A checkpoint written under layout A restores into a layout-B
    runtime by routing the flat accumulators through the transition —
    bitwise equal to re-flattening the same values under B."""
    from repro.checkpoint.checkpoint import restore, save

    cfg = _tiny_cfg()
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    bo_a, nb_a, _, sched_a, _ = _plan(cfg, params, 20_000)
    bo_b, nb_b, _, sched_b, _ = _plan(cfg, params, 60_000)
    lay_a = build_bucket_layout(params, bo_a, nb_a)
    lay_b = build_bucket_layout(params, bo_b, nb_b)

    rt_a = DeftRuntime(cfg, opt, sched_a, lay_a, single_mesh)
    state = rt_a.init_state(key)
    with jax.set_mesh(single_mesh):
        for step in range(sched_a.period + 1):
            state, _ = rt_a.step(step, state, make_batch(cfg, 0, step, B, S))
    save(str(tmp_path), 7, rt_a.state_to_tree(state))

    rt_b = DeftRuntime(cfg, opt, sched_b, lay_b, single_mesh)
    like = rt_b.checkpoint_struct(lay_a)
    ts = restore(str(tmp_path), 7, like)
    restored = rt_b.tree_to_state(ts, src_layout=lay_a)

    # independent reference: unflatten each accumulator row under A and
    # re-flatten under B (no LayoutTransition involved)
    def reflatten_rows(rows_a):
        out = []
        n_rows = rows_a[0].shape[0]
        for r in range(n_rows):
            leaves = unflatten_buckets(lay_a, [x[r] for x in rows_a])
            out.append(flatten_buckets(lay_b, leaves))
        return [jnp.stack([out[r][b] for r in range(n_rows)])
                for b in range(lay_b.n_buckets)]

    want_pbuf = flatten_buckets(
        lay_b, unflatten_buckets(lay_a, state["pbuf"]))
    for got, want in zip(restored["pbuf"], want_pbuf):
        assert bool(jnp.array_equal(got, want))
    for name in ("cur", "fut"):
        for got, want in zip(restored[name], reflatten_rows(state[name])):
            assert bool(jnp.array_equal(got, want))
    # and the restored state actually trains under B
    with jax.set_mesh(single_mesh):
        restored, m = rt_b.step(0, restored, make_batch(cfg, 0, 0, B, S))
    assert bool(jnp.isfinite(m["loss"]))


# ---------------------------------------------------------------------------
# Shard-count change (4 -> 2) on forced devices
# ---------------------------------------------------------------------------
_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import dataclasses
from repro.configs import get_config, reduce_for_smoke
from repro.core.bucket import BucketTimes
from repro.core.deft import solve_schedule
from repro.core.scheduler import SchedulerConfig
from repro.core.profiler import HardwareModel
from repro.data.pipeline import make_batch
from repro.optim.optimizers import adamw
from repro.train import (DeftRuntime, assign_buckets, build_bucket_layout,
                         build_layout_transition, init_train_state,
                         leaf_bucket_times)

cfg = reduce_for_smoke(get_config("qwen3-4b"))
opt = adamw(1e-3)
key = jax.random.PRNGKey(0)
B, S = 8, 32
probe = init_train_state(key, cfg, opt)
bucket_of, nb = assign_buckets(probe["params"], cfg, partition_elems=150_000)
times = leaf_bucket_times(probe["params"], cfg, bucket_of, nb,
                          HardwareModel(dp_degree=4), S, 2)
scale = 1.8 * (times.fwd_total + times.bwd_total) / times.comm_total
times = BucketTimes(times.fwd, times.bwd, tuple(c * scale for c in times.comm))
sched = solve_schedule(times, SchedulerConfig())

mesh4 = jax.make_mesh((4, 1), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)
mesh2 = jax.make_mesh((2, 2, 1), ("pod", "data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 3)
lay4 = build_bucket_layout(probe["params"], bucket_of, nb, shard_count=4)
lay2 = build_bucket_layout(probe["params"], bucket_of, nb, shard_count=2)
tr = build_layout_transition(lay4, lay2)
half = sched.period
total = 2 * sched.period

# mid-run shard-count change: 4-shard engine on mesh4, repack into the
# 2-shard engine on mesh2 (same schedule — the partition is unchanged)
rt4 = DeftRuntime(cfg, opt, sched, lay4, mesh4, fsdp=True)
with jax.set_mesh(mesh4):
    state = rt4.init_state(key)
    for b, a in enumerate(state["pbuf"]):
        assert {s.data.size for s in a.addressable_shards} \
            == {lay4.shard_sizes[b]}
    for step in range(half):
        state, _ = rt4.step(step, state, make_batch(cfg, 0, step, B, S))
rt2 = DeftRuntime(cfg, opt, sched, lay2, mesh2, fsdp=True)
with jax.set_mesh(mesh2):
    state = rt2.repack_state(state, tr)
    # residency after the repack: split over mesh2's 'data' (2 shards)
    for b, a in enumerate(state["pbuf"]):
        assert a.sharding.spec == P("data"), a.sharding
        assert {s.data.size for s in a.addressable_shards} \
            == {lay2.shard_sizes[b]}
    for step in range(half, total):
        state, _ = rt2.step(step - half, state,
                            make_batch(cfg, 0, step, B, S))

# reference: the whole run from scratch under the 2-shard engine
rt2b = DeftRuntime(cfg, opt, sched, lay2, mesh2, fsdp=True)
with jax.set_mesh(mesh2):
    ref = rt2b.init_state(key)
    for step in range(total):
        ref, _ = rt2b.step(step, ref, make_batch(cfg, 0, step, B, S))

diff = max(float(jnp.max(jnp.abs(a - b)))
           for a, b in zip(jax.tree.leaves(rt2.params_tree(state)),
                           jax.tree.leaves(rt2b.params_tree(ref))))
# same update math; only the collective summation grouping differs
# between psum(data=4) and RS(data=2)+psum(pod=2)
assert diff < 1e-5, f"shard-count change diverged: {diff}"
print(f"SHARD_REPACK_OK diff={diff:.2e}")
"""


@pytest.mark.slow
@pytest.mark.multidevice
def test_shard_count_change_4_to_2_on_forced_devices(tmp_path):
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
    assert "SHARD_REPACK_OK" in out.stdout
