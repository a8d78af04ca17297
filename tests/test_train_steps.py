"""Compiled DeFT phase steps: equivalence with an explicit gradient-
accumulation reference that replays the PhaseSpec semantics with global
gradients.  This is the convergence-consistency evidence the paper gets
from its ImageNet runs — here it is exact (to f32 reduction order).

Runs on a 1x1 mesh — the full shard_map/psum graph is built; a true
multi-device run of the same check lives in test_multidevice.py.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest

from _jaxpr_census import count_primitives, iter_eqns
from repro.configs import get_config, reduce_for_smoke
from repro.core.bucket import BucketTimes
from repro.core.deft import solve_schedule
from repro.core.scheduler import SchedulerConfig
from repro.data.pipeline import make_batch
from repro.models.model import loss_fn
from repro.optim.optimizers import adamw, apply_updates, init_opt_state
from repro.train import (
    DeftRuntime,
    assign_buckets,
    build_bucket_layout,
    init_train_state,
    leaf_bucket_times,
    make_deft_step_fns,
    phase_collectives,
)
from repro.train.runtime import deft_phase_step_fused
from repro.train.steps import ddp_train_step, deft_phase_step
from repro.core.profiler import HardwareModel

B, S = 4, 32


def _schedule_for(cfg, params, cr, heterogeneous=True):
    bucket_of, nb = assign_buckets(params, cfg, partition_elems=150_000)
    hw = HardwareModel(dp_degree=1)
    times = leaf_bucket_times(params, cfg, bucket_of, nb, hw, S, B)
    scale = cr * (times.fwd_total + times.bwd_total) / max(times.comm_total, 1e-12)
    times = BucketTimes(times.fwd, times.bwd,
                        tuple(c * scale for c in times.comm))
    return bucket_of, nb, solve_schedule(
        times, SchedulerConfig(heterogeneous=heterogeneous)
    )


class _ReferenceReplay:
    """Replays PhaseSpec semantics with global (unbucketed) gradients —
    the gradient-accumulation reference both step implementations must
    match exactly (to f32 reduction order)."""

    def __init__(self, cfg, opt, params):
        self.cfg, self.opt = cfg, opt
        self.params = params
        self.opt_state = init_opt_state(opt, params)
        zeros = lambda: jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        self.cur, self.fut = zeros(), zeros()
        self.gfn = jax.jit(jax.grad(lambda p, b: loss_fn(p, cfg, b)[0]))

    def step(self, ph, batch):
        g = self.gfn(self.params, batch)
        if ph.rotate:
            gen = jax.tree.map(
                lambda a, b: a.astype(jnp.float32) + b, g, self.fut
            )
            self.fut = jax.tree.map(jnp.zeros_like, self.fut)
        else:
            self.fut = jax.tree.map(
                lambda f, a: f + a.astype(jnp.float32), self.fut, g
            )
            gen = None
        if ph.do_update:
            src = self.cur if ph.update_source == "cur" else gen
            self.params, self.opt_state = apply_updates(
                self.opt, self.params, src, self.opt_state,
                grad_scale=1.0 / ph.update_k,
            )
            self.cur = gen if ph.update_source == "cur" else \
                jax.tree.map(jnp.zeros_like, self.cur)
        elif ph.rotate:
            self.cur = gen

    def max_param_diff(self, params) -> float:
        return max(
            float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(params),
                            jax.tree.leaves(self.params))
        )


@pytest.mark.parametrize("cr", [0.5, 1.8])
def test_deft_steps_match_accumulation_reference(single_mesh, cr):
    """Legacy per-leaf path vs the reference replay."""
    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(0)
    state = init_train_state(key, cfg, opt, deft=True, accum_devices=1)
    bucket_of, _, sched = _schedule_for(cfg, state["params"], cr)
    if cr > 1:
        assert sched.updates_per_period < sched.period

    ref = _ReferenceReplay(cfg, opt, state["params"])
    with single_mesh:
        fns = make_deft_step_fns(cfg, opt, sched, bucket_of, single_mesh)
        for step in range(2 * sched.period):
            batch = make_batch(cfg, 0, step, B, S)
            ph = sched.phases[step % sched.period]
            state, m = fns[step % sched.period](state, batch)
            ref.step(ph, batch)
            diff = ref.max_param_diff(state["params"])
            assert diff < 5e-5, f"step {step}: params diverge by {diff}"
            assert bool(m["updated"]) == ph.do_update


@pytest.mark.parametrize("flat_state", [True, False],
                         ids=["flat", "tree"])
@pytest.mark.parametrize("cr", [0.5, 1.8])
def test_fused_runtime_matches_accumulation_reference(single_mesh, cr,
                                                      flat_state):
    """DeftRuntime (bucket-fused collectives, donated buffers, AOT phase
    cache) vs the same gradient-accumulation reference — both the flat-
    resident engine (fused bucket-update path; cr=1.8 exercises delayed
    k>1 stale-gradient updates) and the PR-1 tree-state engine."""
    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(0)
    probe = init_train_state(key, cfg, opt)
    bucket_of, nb, sched = _schedule_for(cfg, probe["params"], cr)
    layout = build_bucket_layout(probe["params"], bucket_of, nb)

    with single_mesh:
        rt = DeftRuntime(cfg, opt, sched, layout, single_mesh,
                         flat_state=flat_state)
        state = rt.init_state(key)
        rt.compile(state, make_batch(cfg, 0, 0, B, S))   # AOT phase cache
        ref = _ReferenceReplay(cfg, opt, probe["params"])
        for step in range(2 * sched.period):
            batch = make_batch(cfg, 0, step, B, S)
            ph = sched.phases[step % sched.period]
            state, m = rt.step(step, state, batch)
            ref.step(ph, batch)
            diff = ref.max_param_diff(rt.params_tree(state))
            assert diff < 5e-5, f"step {step}: params diverge by {diff}"
            assert bool(m["updated"]) == ph.do_update
    st = rt.stats()
    assert st["steps_dispatched"] == 2 * sched.period
    assert st["unique_phases"] <= sched.period
    assert st["compile_s_total"] > 0.0
    assert st["flat_state"] == flat_state


# ---------------------------------------------------------------------------
# Fused-path structural guarantees
# ---------------------------------------------------------------------------
_COLLECTIVE_PRIMS = {
    "psum", "psum_scatter", "reduce_scatter", "all_gather", "all_reduce",
    "all_to_all",
}


def _count_collectives(fn, *args):
    return count_primitives(jax.make_jaxpr(fn)(*args).jaxpr,
                            _COLLECTIVE_PRIMS)


def test_fused_phase_one_collective_per_synced_bucket(single_mesh):
    """THE fusion guarantee: the fused phase body contains exactly one
    psum per synced bucket (+1 fused metrics psum), while the legacy body
    holds one per synced parameter leaf (+3 metric psums).  Asserted by
    jaxpr inspection, homogeneous link setup (no secondary chains)."""
    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(0)
    probe = init_train_state(key, cfg, opt)
    bucket_of, nb, sched = _schedule_for(
        cfg, probe["params"], cr=1.8, heterogeneous=False
    )
    layout = build_bucket_layout(probe["params"], bucket_of, nb)
    batch = make_batch(cfg, 0, 0, B, S)
    legacy_state = init_train_state(key, cfg, opt, deft=True, accum_devices=1)
    fused_state = init_train_state(
        key, cfg, opt, deft=True, accum_devices=1, layout=layout
    )

    checked = 0
    with single_mesh:
        for ph in set(sched.phases):
            synced = [
                (ph.route_new[b] == "sync" and ph.rotate) or ph.sync_cur[b]
                for b in range(nb)
            ]
            n_synced_buckets = sum(synced)
            n_synced_leaves = sum(
                len(layout.leaves[b]) for b in range(nb) if synced[b]
            )
            assert not any(ph.secondary), "homogeneous schedule expected"

            fused = _count_collectives(
                functools.partial(
                    deft_phase_step_fused, cfg=cfg, opt_spec=opt, phase=ph,
                    layout=layout, mesh=single_mesh,
                ),
                fused_state, batch,
            )
            legacy = _count_collectives(
                functools.partial(
                    deft_phase_step, cfg=cfg, opt_spec=opt, phase=ph,
                    bucket_of_leaf=bucket_of, mesh=single_mesh,
                ),
                legacy_state, batch,
            )
            expected = phase_collectives(ph)
            assert expected["primary"] == n_synced_buckets
            assert fused == n_synced_buckets + 1, (fused, n_synced_buckets)
            assert legacy == n_synced_leaves + 3, (legacy, n_synced_leaves)
            if n_synced_buckets:
                checked += 1
                assert fused < legacy  # the actual win
    assert checked > 0   # at least one phase actually syncs something


def test_fused_runtime_donation_holds(single_mesh):
    """Every phase executable donates the whole train state: after a
    dispatch the input buffers are deleted (updated in place), across a
    full multi-phase period without aliasing errors."""
    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(0)
    probe = init_train_state(key, cfg, opt)
    bucket_of, nb, sched = _schedule_for(cfg, probe["params"], cr=1.8)
    layout = build_bucket_layout(probe["params"], bucket_of, nb)
    with single_mesh:
        rt = DeftRuntime(cfg, opt, sched, layout, single_mesh)
        state = rt.init_state(key)
        batch = make_batch(cfg, 0, 0, B, S)
        rt.compile(state, batch)
        for step in range(sched.period):
            prev = state
            state, m = rt.step(step, state, batch)
            leaves = jax.tree.leaves(prev)
            assert leaves and all(x.is_deleted() for x in leaves), (
                f"step {step}: donation did not hold"
            )
        assert jnp.isfinite(m["loss"])


def test_low_cr_full_update_frequency_and_progress(single_mesh):
    """CR << 1: the schedule keeps the baseline update frequency (one
    k=1 update per iteration; only the hard-dependency bucket rides into
    the next iteration's forward — the paper's delayed update) and the
    loss actually descends on the learnable stream."""
    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(1)
    state = init_train_state(key, cfg, opt, deft=True, accum_devices=1)
    bucket_of, nb, sched = _schedule_for(cfg, state["params"], cr=0.05)
    assert sched.updates_per_period == sched.period  # one update per iter
    assert all(k == 1 for k in sched.batch_size_sequence)

    losses = []
    layout = build_bucket_layout(state["params"], bucket_of, nb)
    with single_mesh:
        rt = DeftRuntime(cfg, opt, sched, layout, single_mesh)
        state = rt.init_state(key)
        for step in range(10):
            batch = make_batch(cfg, 0, step, B, S)
            state, m = rt.step(step, state, batch)
            assert bool(m["updated"])
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def _loss_case(name, vocab=None, masked=False):
    cfg = reduce_for_smoke(get_config(name))
    if vocab:
        cfg = dataclasses.replace(cfg, vocab_size=vocab)
    from repro.models.model import init_params
    params = init_params(jax.random.PRNGKey(2), cfg)
    batch = make_batch(cfg, 0, 0, B, S)
    if masked:
        keep = jax.random.bernoulli(jax.random.PRNGKey(3), 0.7, (B, S))
        batch["mask"] = keep.astype(jnp.float32)
    return cfg, params, batch


@pytest.mark.parametrize("name,chunk,vocab,masked", [
    ("gemma2-2b", 8, None, False),     # tied embed + final softcap
    ("qwen3-4b", 8, None, False),      # tied, no softcap
    ("deepseek-7b", 8, None, False),   # untied head [d, V]
    ("qwen3-4b", 8, None, True),       # a mask with zeros
    ("qwen3-4b", 12, None, False),     # a chunk that does not divide S - 1
    ("gemma2-2b", 8, 700, True),       # V = 5 x 128 + a 60-wide last slice
], ids=["tied-softcap", "tied", "untied", "masked", "ragged-chunk",
        "ragged-vocab"])
def test_loss_chunk_matches_unchunked(single_mesh, name, chunk, vocab,
                                      masked):
    """Chunked LM-head CE == plain CE (same loss, same gradients)."""
    cfg, params, batch = _loss_case(name, vocab, masked)
    l1, _ = loss_fn(params, cfg, batch, loss_chunk=0)
    l2, _ = loss_fn(params, cfg, batch, loss_chunk=chunk)
    assert float(jnp.abs(l1 - l2)) < 1e-5
    g1 = jax.grad(lambda p: loss_fn(p, cfg, batch, loss_chunk=0)[0])(params)
    g2 = jax.grad(
        lambda p: loss_fn(p, cfg, batch, loss_chunk=chunk)[0])(params)
    assert jax.tree.structure(g1) == jax.tree.structure(g2)
    diff = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2))
    )
    assert diff < 1e-4


@pytest.mark.parametrize("rows,seq,chunk,width", [
    (151_936, 4095, 256, 9472),   # qwen3-4b, one 4,096-token sequence
    (512, 31, 8, 128),            # smoke: never under 128 rows
    (512, 31, 31, 512),           # one chunk: one slice of every row
])
def test_head_slice_width_fits_the_forward_block(rows, seq, chunk, width):
    from repro.models.model import head_slice_width
    assert head_slice_width(rows, seq, chunk) == width
    assert width % 128 == 0 or width == rows
    assert width * seq <= max(chunk * rows, 128 * seq)


@pytest.mark.parametrize("name", ["qwen3-4b", "deepseek-7b"])
def test_chunked_loss_grad_carries_no_head_sized_loop_state(name):
    """The chunked loss's backward writes the head's [V, d] gradient once:
    no loop of ``jax.grad`` carries an array of the head's full shape
    (a backward by sequence chunks would add each chunk's product into
    such a carry)."""
    cfg, params, batch = _loss_case(name)
    head = {(cfg.vocab_size, cfg.d_model), (cfg.d_model, cfg.vocab_size)}
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: loss_fn(p, cfg, batch, loss_chunk=8)[0]))(params).jaxpr
    loops = 0
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name == "scan":
            n = eqn.params["num_consts"]
            carry = eqn.params["jaxpr"].in_avals[
                n:n + eqn.params["num_carry"]]
        elif eqn.primitive.name == "while":
            carry = eqn.params["body_jaxpr"].in_avals[
                eqn.params["body_nconsts"]:]
        else:
            continue
        loops += 1
        assert not {tuple(a.shape) for a in carry} & head, eqn.primitive
    assert loops
