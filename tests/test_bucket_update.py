"""Fused bucket-update engine (kernels/bucket_update): Pallas kernel vs
pure-JAX twin, flat path vs the per-leaf apply_updates reference, segment
maps, padded-tail masking, delayed-update staleness and donation.

Tolerance contract: the Pallas kernel and its lax twin compute the same
f32 expressions in the same order; residual differences are XLA FMA-
contraction noise (<= a few ulp), so kernel-level checks use tight
absolute tolerances and the tail (a where-select of untouched inputs)
must match bitwise.  With grad clipping off, the flat path is bitwise
against per-leaf apply_updates; with clipping on, the global-norm
reduction is grouped per bucket instead of per leaf (last-ulp clip
factor), so those checks are tight-tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _jaxpr_census import count_eqns
from repro.data.pipeline import make_batch
from repro.kernels.bucket_update import (
    apply_bucket_updates,
    bucket_update_pallas,
    bucket_update_ref,
    build_segments,
    init_flat_opt_state,
    pack_scalars,
)
from repro.optim.optimizers import (
    adamw,
    apply_updates,
    init_opt_state,
    leaf_hparams,
    sgd_momentum,
)
from repro.train.bucketing import (
    build_bucket_layout,
    flatten_buckets,
    unflatten_buckets,
)

KTOL = 1e-6          # kernel-vs-twin: FMA-contraction noise only


def _tree():
    key = jax.random.PRNGKey(0)
    return {
        "w": jax.random.normal(key, (37, 9)),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (13,)),
        "h": jax.random.normal(jax.random.fold_in(key, 2), (200,)),
        "u": jax.random.normal(jax.random.fold_in(key, 3), (5, 7, 3)),
    }


def _layout(params):
    # tree_flatten order: b(13), h(200), u(105), w(333) -> odd tails
    return build_bucket_layout(params, (0, 1, 1, 0), 2)


SPECS = [
    adamw(1e-2, weight_decay=0.01),
    sgd_momentum(3e-2, momentum=0.85, weight_decay=0.02),
    adamw(1e-2, weight_decay=0.1, decay_mask="matrix", ndim1_lr_scale=0.5),
    sgd_momentum(1e-2, grad_clip=0.0),
]
SPEC_IDS = ["adamw", "sgd", "adamw-segmented", "sgd-noclip"]


# ---------------------------------------------------------------------------
# Pallas kernel (interpret) vs the lax twin — one bucket, odd tail
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("padded,n_valid", [(640, 533), (128, 128), (256, 1)])
@pytest.mark.parametrize("spec", SPECS[:2], ids=SPEC_IDS[:2])
def test_pallas_matches_ref_twin(spec, padded, n_valid):
    key = jax.random.PRNGKey(7)
    mk = lambda i: jax.random.normal(
        jax.random.fold_in(key, i), (padded,)
    ).at[n_valid:].set(0.0)
    p, m, g = mk(0), mk(1), mk(3)
    v = jnp.abs(mk(2)) if spec.name == "adamw" else None
    scal = pack_scalars(spec, jnp.int32(3), grad_scale=0.5,
                        clip=jnp.float32(0.9))
    kw = dict(n_valid=n_valid, uniform=(1.0, spec.weight_decay),
              zero_grads=True)
    ref = bucket_update_ref(spec, p, m, v, g, scal, **kw)
    got = bucket_update_pallas(spec, p, m, v, g, scal, interpret=True, **kw)
    for name, a, b in zip("pmv", ref, got):
        if a is None:
            continue
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=KTOL, rtol=KTOL, err_msg=name)
        # the masked tail is a passthrough select: bitwise
        assert bool(jnp.array_equal(a[n_valid:], b[n_valid:]))
    assert not np.any(np.asarray(got[3]))         # fused zeroing
    # odd tail stays at the input value (zero here)
    assert not np.any(np.asarray(got[0][n_valid:]))


@pytest.mark.parametrize("spec", SPECS[:2], ids=SPEC_IDS[:2])
def test_pallas_multiblock_grid(spec):
    """Row-blocked grid with a partial final block (10 rows, blocks of
    4) matches the twin — the tiling/index-map path, not just grid=1."""
    padded, n_valid = 1280, 1200
    key = jax.random.PRNGKey(11)
    mk = lambda i: jax.random.normal(
        jax.random.fold_in(key, i), (padded,)
    ).at[n_valid:].set(0.0)
    p, m, g = mk(0), mk(1), mk(3)
    v = jnp.abs(mk(2)) if spec.name == "adamw" else None
    scal = pack_scalars(spec, jnp.int32(2), grad_scale=1.0,
                        clip=jnp.float32(1.0))
    kw = dict(n_valid=n_valid, uniform=(1.0, spec.weight_decay))
    ref = bucket_update_ref(spec, p, m, v, g, scal, **kw)
    got = bucket_update_pallas(spec, p, m, v, g, scal, block_rows=4,
                               interpret=True, **kw)
    for name, a, b in zip("pmv", ref, got):
        if a is None:
            continue
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=KTOL, rtol=KTOL, err_msg=name)


def test_tail_garbage_is_masked():
    """Garbage riding the padded gradient tail must not leak into params
    or moments — the kernels mask on the static valid length."""
    spec = adamw(1e-2, weight_decay=0.05)
    padded, n_valid = 384, 300
    key = jax.random.PRNGKey(9)
    mk = lambda i: jax.random.normal(
        jax.random.fold_in(key, i), (padded,)
    ).at[n_valid:].set(0.0)
    p, m, v = mk(0), mk(1), jnp.abs(mk(2))
    g = mk(3).at[n_valid:].set(jnp.nan)           # hostile tail
    scal = pack_scalars(spec, jnp.int32(1), grad_scale=1.0,
                        clip=jnp.float32(1.0))
    for impl_kw in ({"interpret": True},):
        p2, m2, v2, _ = bucket_update_pallas(
            spec, p, m, v, g, scal, n_valid=n_valid, uniform=(1.0, 0.05),
            **impl_kw,
        )
        for new, old in ((p2, p), (m2, m), (v2, v)):
            assert bool(jnp.array_equal(new[n_valid:], old[n_valid:]))
            assert bool(jnp.all(jnp.isfinite(new[:n_valid])))
    r = bucket_update_ref(spec, p, m, v, g, scal, n_valid=n_valid,
                          uniform=(1.0, 0.05))
    assert bool(jnp.array_equal(r[0][n_valid:], p[n_valid:]))
    assert bool(jnp.all(jnp.isfinite(r[0][:n_valid])))


def test_tail_garbage_does_not_poison_clip_norm():
    """Regression: the global-norm clip in apply_bucket_updates must sum
    the VALID spans only — a NaN riding a padded gradient tail once
    leaked through the clip scalar into every valid parameter."""
    params = _tree()
    layout = _layout(params)
    spec = adamw(1e-2)                                 # grad_clip on
    assert any(layout.buf_sizes[b] > layout.sizes[b]
               for b in range(layout.n_buckets))
    seg = build_segments(layout, spec)
    pbuf = tuple(flatten_buckets(layout, jax.tree.leaves(params)))
    gbuf = [g.at[layout.sizes[b]:].set(jnp.nan)
            for b, g in enumerate(flatten_buckets(
                layout, jax.tree.leaves(params)))]
    opt_f = init_flat_opt_state(spec, layout.buf_sizes)
    new_p, _, _ = apply_bucket_updates(spec, seg, pbuf, gbuf, opt_f,
                                       grad_scale=1.0, impl="ref")
    for b in range(layout.n_buckets):
        assert bool(jnp.all(jnp.isfinite(new_p[b][:layout.sizes[b]])))


# ---------------------------------------------------------------------------
# Flat path vs per-leaf apply_updates (the numerical reference)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_flat_matches_per_leaf_reference(spec, impl):
    params = _tree()
    layout = _layout(params)
    grads = jax.tree.map(
        lambda p: jax.random.normal(jax.random.PRNGKey(42), p.shape), params
    )
    seg = build_segments(layout, spec)

    p_ref, o_ref = params, init_opt_state(spec, params)
    pbuf = tuple(flatten_buckets(layout, jax.tree.leaves(params)))
    gbuf = tuple(flatten_buckets(layout, jax.tree.leaves(grads)))
    opt_f = init_flat_opt_state(spec, layout.buf_sizes)
    for _ in range(4):
        p_ref, o_ref = apply_updates(spec, p_ref, grads, o_ref,
                                     grad_scale=0.25)
        pbuf, opt_f, _ = apply_bucket_updates(
            spec, seg, pbuf, gbuf, opt_f, grad_scale=0.25, impl=impl
        )
    got = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params), unflatten_buckets(layout, pbuf)
    )
    exact = spec.grad_clip == 0.0 and impl == "ref"
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(p_ref)):
        if exact:
            assert bool(jnp.array_equal(a, b)), "noclip/ref must be bitwise"
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-6, rtol=5e-6)
    assert int(opt_f["step"]) == int(o_ref["step"]) == 4
    # padded tails never move
    for b_ in range(layout.n_buckets):
        tail = pbuf[b_][layout.sizes[b_]:]
        assert tail.size == 0 or not np.any(np.asarray(tail))


# ---------------------------------------------------------------------------
# Segment-id map
# ---------------------------------------------------------------------------
def test_segment_map_structure():
    params = _tree()
    layout = _layout(params)
    spec = adamw(1e-2, weight_decay=0.1, decay_mask="matrix",
                 ndim1_lr_scale=0.5)
    seg = build_segments(layout, spec)
    hps = leaf_hparams(spec, layout.shapes)
    # matrix-only decay: 1-d leaves get wd 0 and the ndim1 lr scale
    assert [hp.weight_decay for hp in hps] == [0.0, 0.0, 0.1, 0.1]
    assert [hp.lr_scale for hp in hps] == [0.5, 0.5, 1.0, 1.0]
    for b in range(layout.n_buckets):
        ids = seg.segment_ids(b)
        assert ids.shape == (layout.buf_sizes[b],)
        assert (ids[layout.sizes[b]:] == -1).all()      # tail sentinel
        for ordinal, (leaf, off) in enumerate(
            zip(layout.leaves[b], layout.offsets[b])
        ):
            n = int(np.prod(layout.shapes[leaf])) if layout.shapes[leaf] else 1
            assert (ids[off:off + n] == ordinal).all()
        sc, wd = seg.element_hparams(b)
        for ordinal, leaf in enumerate(layout.leaves[b]):
            span = ids == ordinal
            assert (sc[span] == hps[leaf].lr_scale).all()
            assert (wd[span] == np.float32(hps[leaf].weight_decay)).all()
        assert (sc[layout.sizes[b]:] == 0).all()
    # mixed-hparam buckets lose the uniform fast path
    assert seg.uniform(0) is None or seg.uniform(1) is None or all(
        hp == hps[0] for hp in hps
    )


def test_impl_dispatch_env_override(monkeypatch):
    """The REPRO_BUCKET_UPDATE env dispatch: valid overrides win over
    the backend default, unknown values raise instead of silently
    running the wrong implementation, empty falls back to the backend
    rule (ref on this CPU host)."""
    from repro.kernels.bucket_update.ops import default_bucket_update_impl

    def fresh(value):
        default_bucket_update_impl.cache_clear()
        if value is None:
            monkeypatch.delenv("REPRO_BUCKET_UPDATE", raising=False)
        else:
            monkeypatch.setenv("REPRO_BUCKET_UPDATE", value)
        try:
            return default_bucket_update_impl()
        finally:
            default_bucket_update_impl.cache_clear()

    assert fresh("interpret") == "interpret"
    assert fresh("REF") == "ref"                   # case-insensitive
    assert fresh(None) in ("pallas", "ref")
    with pytest.raises(ValueError, match="REPRO_BUCKET_UPDATE"):
        fresh("interpreted")                       # typo fails loudly


def test_uniform_fast_path_detection():
    params = _tree()
    layout = _layout(params)
    seg_u = build_segments(layout, adamw(1e-2, weight_decay=0.01))
    for b in range(layout.n_buckets):
        assert seg_u.uniform(b) == (1.0, 0.01)
    seg_n = build_segments(
        layout, adamw(1e-2, weight_decay=0.1, decay_mask="matrix")
    )
    # bucket 0 holds b(1d)+w(2d), bucket 1 holds h(1d)+u(3d): both mixed
    assert seg_n.uniform(0) is None and seg_n.uniform(1) is None


# ---------------------------------------------------------------------------
# Flat runtime: delayed-update staleness (k>1) and donation/no-growth
# ---------------------------------------------------------------------------
def _live_bytes():
    return sum(
        a.nbytes for a in jax.live_arrays() if not a.is_deleted()
    )


def test_flat_runtime_staleness_and_no_buffer_growth(single_mesh):
    """cr=1.8 gives a delayed-update schedule (k>1 merged gradients,
    updates applied phases after their batches).  The flat engine must
    (a) track the gradient-accumulation reference through the stale
    applies and (b) hold the donation contract: the live-buffer footprint
    does not grow across a full period."""
    from repro.configs import get_config, reduce_for_smoke
    from test_train_steps import B, S, _ReferenceReplay, _schedule_for
    from repro.train import DeftRuntime, init_train_state

    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(0)
    probe = init_train_state(key, cfg, opt)
    bucket_of, nb, sched = _schedule_for(cfg, probe["params"], cr=1.8)
    assert max(sched.batch_size_sequence) > 1          # real staleness
    assert sched.updates_per_period < sched.period
    layout = build_bucket_layout(probe["params"], bucket_of, nb)
    ref = _ReferenceReplay(cfg, opt, probe["params"])
    del probe

    with single_mesh:
        rt = DeftRuntime(cfg, opt, sched, layout, single_mesh)
        assert rt.flat_state
        state = rt.init_state(key)
        rt.compile(state, make_batch(cfg, 0, 0, B, S))
        baseline = None
        for step in range(2 * sched.period):
            batch = make_batch(cfg, 0, step, B, S)
            prev = state
            state, m = rt.step(step, state, batch)
            assert all(x.is_deleted() for x in jax.tree.leaves(prev)), (
                f"step {step}: donation did not hold"
            )
            ref.step(sched.phases[step % sched.period], batch)
            diff = ref.max_param_diff(rt.params_tree(state))
            assert diff < 5e-5, f"step {step}: diverged by {diff}"
            jax.block_until_ready(m["loss"])
            if step == sched.period - 1:
                baseline = _live_bytes()
        assert baseline is not None
        # steady state: repeating the cycle allocates nothing persistent
        assert _live_bytes() <= baseline, (
            f"live buffers grew across a period: "
            f"{baseline} -> {_live_bytes()}"
        )


def test_flat_update_removes_per_leaf_op_sequence():
    """THE structural claim of the flat engine, asserted the same way
    the runtime asserts its collectives guarantee — by jaxpr
    inspection, which is deterministic where CPU wall time is not: the
    fused apply's op count scales with the bucket count, the per-leaf
    apply's with the leaf count."""
    n_leaves, leaf_elems, n_buckets = 64, 512, 4
    key = jax.random.PRNGKey(5)
    tree = {
        f"l{i:03d}": jax.random.normal(jax.random.fold_in(key, i),
                                       (leaf_elems,))
        for i in range(n_leaves)
    }
    grads = jax.tree.map(lambda p: p * 0.01, tree)
    bo = tuple(i * n_buckets // n_leaves for i in range(n_leaves))
    layout = build_bucket_layout(tree, bo, n_buckets)
    spec = adamw(1e-3)
    seg = build_segments(layout, spec)
    pbuf = tuple(flatten_buckets(layout, jax.tree.leaves(tree)))
    gbuf = tuple(flatten_buckets(layout, jax.tree.leaves(grads)))
    opt_f = init_flat_opt_state(spec, layout.buf_sizes)
    opt_l = init_opt_state(spec, tree)

    n_flat = count_eqns(jax.make_jaxpr(
        lambda p, g, o: apply_bucket_updates(spec, seg, p, g, o,
                                             grad_scale=0.1)[:2]
    )(pbuf, gbuf, opt_f).jaxpr)
    n_leaf = count_eqns(jax.make_jaxpr(
        lambda p, g, o: apply_updates(spec, p, g, o, grad_scale=0.1)
    )(tree, grads, opt_l).jaxpr)
    # per-leaf grows ~10 ops/leaf; fused grows ~10 ops/bucket
    assert n_flat < n_leaf / 4, (n_flat, n_leaf)
    assert n_leaf > n_leaves            # really is O(leaves)


def test_bench_update_path_entry():
    """The checked-in BENCH_runtime.json update-path entry exists, is
    structurally sound, and shows no gross update-path regression at
    paper-regime leaf counts.  Wall-clock on a shared CPU is load-noisy
    (observed 1.0x-8.8x across runs), so the hard perf claim lives in
    test_flat_update_removes_per_leaf_op_sequence; this floor only
    catches the engine becoming categorically slower."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_runtime.json")
    data = json.load(open(path))
    up = data["update_path"]
    assert up["paper_leafcount"]["speedup_flat_vs_per_leaf"] > 0.9, up
    assert up["paper_leafcount"]["n_leaves"] >= 100
    assert up["smoke_config"]["apply_ms_flat"] > 0
    # the sharded engine's ISOLATED floor — same contract on the RS
    # path: the whole-phase scenario numbers (even interleaved) stay CPU
    # load-noisy, so the gate reads only this signal
    us = data["fsdp_flat"]["update_path_sharded"]
    assert us["speedup_flat_vs_per_leaf"] > 0.9, us
    assert us["n_leaves"] >= 100 and us["shard_count"] > 1


def test_flat_runtime_checkpoint_roundtrip(single_mesh):
    """state_to_tree / tree_to_state are exact inverses and params_tree
    matches the legacy tree layout leaf-for-leaf."""
    from repro.configs import get_config, reduce_for_smoke
    from test_train_steps import B, S, _schedule_for
    from repro.train import DeftRuntime, init_train_state

    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(3)
    probe = init_train_state(key, cfg, opt)
    bucket_of, nb, sched = _schedule_for(cfg, probe["params"], cr=0.5)
    layout = build_bucket_layout(probe["params"], bucket_of, nb)
    with single_mesh:
        rt = DeftRuntime(cfg, opt, sched, layout, single_mesh)
        state = rt.init_state(key)
        state, _ = rt.step(0, state, make_batch(cfg, 0, 0, B, S))
        tree = rt.state_to_tree(state)
        assert set(tree) == {"params", "opt", "cur", "fut"}
        for a, b in zip(jax.tree.leaves(tree["params"]),
                        jax.tree.leaves(rt.params_tree(state))):
            assert bool(jnp.array_equal(a, b))
        back = rt.tree_to_state(tree)
        for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(state)):
            assert bool(jnp.array_equal(a, b)), "roundtrip not exact"
