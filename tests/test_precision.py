"""Precision as a layout dimension (DESIGN.md §13).

Covers, tier-1:

* the quantize kernel twins: Pallas-under-interpret vs the pure-JAX ref
  BIT-MATCH (int8 values, scales, dequant; stochastic-rounded bf16),
  including hostile NaN/inf padded tails and multi-program grids;
* int8 blockwise error bound (elementwise |x - dq| <= scale/2) and
  deterministic, seed-sensitive, unbiased stochastic rounding;
* the ONE cast site: kernels/quantize.cast_compute is bitwise-identical
  to the legacy inline casts it replaced (the PR-4 asymmetry fix);
* the precision-aware Preserver gate: a noise-sensitive walk rejects an
  int8 wire that a clean walk would accept, and the gate is one-sided;
* the planner ladder: under a bandwidth-constrained profile the chosen
  mixed per-bucket policy STRICTLY increases simulated coverage over
  all-f32;
* end-to-end: a forced-int8-wire bucket trains within a tight bound of
  the f32 reference while measurably quantizing; a bf16sr resident
  master stays within the expected drift envelope; a precision-only
  hot-swap installs at the cycle boundary with zero restart.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bucket import BucketTimes
from repro.core.deft import Planner, PlanRequest
from repro.core.precision import (
    WIRE_BYTES,
    PrecisionPolicy,
    apply_wire_precision,
    check_precision_schedule,
    wire_bytes_total,
)
from repro.core.preserver import WalkParams, check_schedule
from repro.kernels.quantize import (
    cast_compute,
    dequantize_int8,
    quantize_int8,
    stochastic_round_bf16,
)
from repro.kernels.quantize.ref import quantize_int8_ref

SHAPES = (128, 512, 1280, 4096)


def _buf(n, key=0, scale=3.0):
    x = jax.random.normal(jax.random.PRNGKey(key), (n,)) * scale
    return x.astype(jnp.float32)


# ---------------------------------------------------------------------------
# kernel twins bit-match (pallas-interpret vs ref)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", SHAPES)
def test_int8_interpret_matches_ref_bitwise(n):
    x = _buf(n, key=n)
    q1, s1 = quantize_int8(x, impl="interpret")
    q2, s2 = quantize_int8(x, impl="ref")
    assert q1.dtype == jnp.int8 and s1.dtype == jnp.float32
    assert bool(jnp.array_equal(q1, q2))
    assert bool(jnp.array_equal(
        jax.lax.bitcast_convert_type(s1, jnp.uint32),
        jax.lax.bitcast_convert_type(s2, jnp.uint32),
    ))
    d1 = dequantize_int8(q1, s1, impl="interpret")
    d2 = dequantize_int8(q2, s2, impl="ref")
    assert bool(jnp.array_equal(d1, d2))


@pytest.mark.parametrize("n", SHAPES)
def test_sr_bf16_interpret_matches_ref_bitwise(n):
    x = _buf(n, key=n + 1)
    a = stochastic_round_bf16(x, 7, impl="interpret")
    b = stochastic_round_bf16(x, 7, impl="ref")
    assert a.dtype == jnp.bfloat16
    assert bool(jnp.array_equal(a, b))


def test_sr_bf16_multi_program_grid_matches_ref():
    """The in-kernel global flat index (program_id * block * 128 + iota)
    must make the hash independent of the grid geometry."""
    from repro.kernels.quantize.kernel import stochastic_round_bf16_pallas
    from repro.kernels.quantize.ref import stochastic_round_bf16_ref

    x = _buf(1280, key=3)
    for br in (1, 2, 4, 10):
        a = stochastic_round_bf16_pallas(x, 5, block_rows=br, interpret=True)
        assert bool(jnp.array_equal(a, stochastic_round_bf16_ref(x, 5)))


def test_hostile_padded_tails_zeroed():
    """NaN/inf beyond n_valid must never leak through a wire cast."""
    n, valid = 512, 300
    x = _buf(n).at[valid:].set(jnp.nan).at[valid + 3].set(jnp.inf)
    for impl in ("interpret", "ref"):
        y = stochastic_round_bf16(x, 1, valid, impl=impl)
        assert bool(jnp.all(y[valid:] == 0))
        assert bool(jnp.all(jnp.isfinite(y.astype(jnp.float32))))
        q, s = quantize_int8(x, valid, impl=impl)
        d = dequantize_int8(q, s, valid, impl=impl)
        assert bool(jnp.all(d[valid:] == 0))
        assert bool(jnp.all(jnp.isfinite(d)))


# ---------------------------------------------------------------------------
# numeric properties
# ---------------------------------------------------------------------------
def test_int8_roundtrip_error_bound():
    """Blockwise quantization error is elementwise <= scale/2."""
    for key in range(3):
        x = _buf(2048, key=key, scale=10.0 ** (key - 1))
        q, s = quantize_int8(x, impl="ref")
        d = dequantize_int8(q, s, impl="ref")
        err = jnp.abs(d - x).reshape(-1, 128)
        bound = (s * 0.5)[:, None] + 1e-12
        assert bool(jnp.all(err <= bound))


def test_int8_zero_row_scale_is_one():
    x = jnp.zeros((256,), jnp.float32)
    q, s = quantize_int8(x, impl="ref")
    assert bool(jnp.all(q == 0)) and bool(jnp.all(s == 1.0))


def test_sr_bf16_deterministic_and_seed_sensitive():
    x = _buf(1024, key=9)
    a = stochastic_round_bf16(x, 42, impl="ref")
    b = stochastic_round_bf16(x, 42, impl="ref")
    c = stochastic_round_bf16(x, 43, impl="ref")
    assert bool(jnp.array_equal(a, b))
    assert not bool(jnp.array_equal(a, c))


def test_sr_bf16_unbiased():
    """E[round(x)] == x: a value exactly between two bf16 neighbours
    must round up about half the time across seeds."""
    hi = jnp.float32(1.0 + 2.0 ** -7)        # next bf16 after 1.0
    x = jnp.full((128,), 1.0 + 2.0 ** -8, jnp.float32)   # the midpoint
    ups = []
    for seed in range(64):
        y = stochastic_round_bf16(x, seed, impl="ref").astype(jnp.float32)
        ups.append(float(jnp.mean((y == hi).astype(jnp.float32))))
    frac = np.mean(ups)
    assert 0.4 < frac < 0.6, frac


def test_cast_compute_matches_legacy_inline_casts():
    """The unified cast site must be bit-identical to the legacy inline
    ``astype`` casts it replaced (replicated buffer views AND sharded
    pre-gather), in both directions."""
    x = _buf(777, key=2)
    assert cast_compute(x, None) is x
    assert cast_compute(x, jnp.float32) is x
    down = cast_compute(x, jnp.bfloat16)
    assert bool(jnp.array_equal(down, x.astype(jnp.bfloat16)))
    up = cast_compute(down, jnp.float32)
    assert bool(jnp.array_equal(up, down.astype(jnp.float32)))


# ---------------------------------------------------------------------------
# policy / pricing / gate
# ---------------------------------------------------------------------------
def test_policy_validation_and_bytes():
    p = PrecisionPolicy(wire=("f32", "bf16", "int8"))
    assert [p.wire_bytes_per_elem(b) for b in range(3)] == [4, 2, 1]
    assert p.mixed and not p.all_f32
    assert "bf16" in p.describe()
    with pytest.raises(ValueError):
        PrecisionPolicy(wire=("fp8",))
    with pytest.raises(ValueError):
        PrecisionPolicy(wire=("f32",), master="f16")
    assert wire_bytes_total((100, 100, 100), p) \
        == 100 * (WIRE_BYTES["f32"] + WIRE_BYTES["bf16"] + WIRE_BYTES["int8"])


def test_apply_wire_precision_prices_bandwidth_term_only():
    times = BucketTimes(fwd=(1e-3,) * 2, bwd=(1e-3,) * 2,
                        comm=(10e-3, 20e-3))
    p = PrecisionPolicy(wire=("bf16", "int8"))
    out = apply_wire_precision(times, p)
    lat = 20e-6
    assert out.comm[0] == pytest.approx(lat + (10e-3 - lat) * 0.5)
    assert out.comm[1] == pytest.approx(lat + (20e-3 - lat) * 0.25)
    assert out.fwd == times.fwd and out.bwd == times.bwd


def test_precision_gate_one_sided_and_noise_sensitive():
    """Near the noise floor (s0 ~ s_star) the sigma-inflated O_D walk
    must reject int8 while the clean gate accepts the same schedule."""
    walk = WalkParams(s0=1.02, s_star=1.0, eta=0.05, mu=0.9,
                      sigma=2.0, batch=32)
    ks = (1, 1, 1, 1)
    clean = check_schedule(ks, 4, walk, eps=0.02)
    assert clean.ok
    f32 = check_precision_schedule(
        ks, 4, walk, PrecisionPolicy.uniform(2, "f32"), eps=0.02
    )
    assert f32.ok and f32.ratio == pytest.approx(clean.ratio)
    int8 = check_precision_schedule(
        ks, 4, walk, PrecisionPolicy.uniform(2, "int8"), eps=0.02
    )
    assert not int8.ok
    # one-sided: narrowing the wire inflates only O_D's noise, so the
    # ratio e_B/e_D can only fall — quantization never rescues a
    # failing k-sequence
    bf16 = check_precision_schedule(
        ks, 4, walk, PrecisionPolicy.uniform(2, "bf16"), eps=0.02
    )
    assert f32.ratio >= bf16.ratio >= int8.ratio


def _constrained_times(n=8):
    rng = np.random.default_rng(0)
    comm = tuple(float(c) for c in rng.uniform(0.04, 0.09, n))
    return BucketTimes(fwd=(0.004,) * n, bwd=(0.008,) * n, comm=comm)


def test_planner_mixed_precision_increases_coverage():
    """Acceptance criterion: under a bandwidth-constrained profile the
    auto ladder picks a MIXED per-bucket policy whose simulated coverage
    strictly beats all-f32."""
    req = PlanRequest(times=_constrained_times(), wire_precision="auto",
                      sim_iterations=3)
    res = Planner().plan(req)
    assert res.precision is not None
    base = next(
        c for c in res.precision_candidates if c.policy.all_f32
    )
    best = next(
        c for c in res.precision_candidates if c.policy == res.precision
    )
    assert best.coverage > base.coverage
    assert best.iteration_time < base.iteration_time
    assert best.wire_bytes_scale < 1.0
    assert res.priced_times is not None
    assert sum(res.priced_times.comm) < sum(res.times.comm)


def test_planner_forced_uniform_and_explicit_policy():
    times = _constrained_times(4)
    res = Planner().plan(PlanRequest(times=times, wire_precision="bf16",
                                     sim_iterations=4))
    assert res.precision is not None
    assert set(res.precision.wire) <= {"f32", "bf16"}
    pol = PrecisionPolicy(wire=("int8", "f32", "f32", "f32"))
    res2 = Planner().plan(PlanRequest(times=times, precision=pol,
                                      sim_iterations=4))
    assert res2.precision in (pol, PrecisionPolicy.uniform(4, "f32"))


# ---------------------------------------------------------------------------
# adaptive controller: bandwidth collapse unlocks the precision ladder
# ---------------------------------------------------------------------------
def test_controller_bandwidth_collapse_downgrades_wire():
    """A calibrated comm_scale past ``precision_comm_scale`` escalates
    the replan to wire_precision='auto': the controller downgrades the
    wire instead of surrendering coverage to the starved link, and the
    ReplanEvent carries the adopted policy + bytes delta."""
    from repro.adapt import (
        AdaptConfig,
        AdaptiveController,
        BandwidthDrop,
        SyntheticTelemetrySource,
        run_control_loop,
    )
    from repro.core.preserver import WalkParams as WP

    walk = WP(s0=4.0, eta=0.01, mu=1.0, sigma=40.0, batch=256)
    times = _constrained_times(8)
    res0 = Planner().plan(PlanRequest(times=times, walk=walk))
    # escalation bar at the drift threshold: the first replan fires
    # while the EMA is still settling toward the injected 3x, so its
    # fitted comm_scale undershoots the asymptote
    cfg = AdaptConfig(wire_precision="auto", precision_comm_scale=1.25)
    ctrl = AdaptiveController(
        times, res0.schedule, res0.scheduler_cfg, walk=walk, cfg=cfg
    )
    assert ctrl.precision is None
    drop = BandwidthDrop(step=24, comm_scale=3.0)
    events = run_control_loop(
        ctrl, SyntheticTelemetrySource(times, drop), 96
    )
    assert events, "no replan despite a 3x bandwidth collapse"
    e = events[0]
    assert e.profile.comm_scale >= cfg.precision_comm_scale
    assert e.new_precision is not None
    assert not e.new_precision.all_f32, "wire stayed f32 under collapse"
    assert e.precision_changed and e.changed
    assert e.wire_bytes_scale < 1.0
    assert "PRECISION" in e.describe()
    # controller state tracks the latest adopted policy (the synthetic
    # source never actually quantizes its reported wall times, so later
    # replans may legitimately revise the first event's choice)
    assert ctrl.precision == events[-1].new_precision
    assert ctrl.stats()["precision_changes"] >= 1
    assert ctrl.stats()["wire_precision"] == (
        ctrl.precision.describe() if ctrl.precision else "f32"
    )


# ---------------------------------------------------------------------------
# end-to-end (runtime execution of a policy)
# ---------------------------------------------------------------------------
def _smoke_runtime(layout_precision=None, master_dtype=None, seed=0):
    from repro.configs import get_config, reduce_for_smoke
    from repro.optim.optimizers import adamw
    from repro.train import DeftRuntime, init_train_state
    from repro.train.bucketing import build_bucket_layout
    from test_train_steps import _schedule_for

    cfg = reduce_for_smoke(get_config("qwen3-4b"))
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(seed)
    probe = init_train_state(key, cfg, opt)
    bucket_of, nb, sched = _schedule_for(cfg, probe["params"], cr=0.5)
    layout = build_bucket_layout(probe["params"], bucket_of, nb)
    if layout_precision is not None:
        layout = layout.with_precision(layout_precision)
    from repro.train.runtime import RuntimeConfig
    rt_cfg = RuntimeConfig(master_dtype=master_dtype)
    return cfg, opt, sched, layout, key, rt_cfg


def _run_steps(rt, state, cfg, sched, n):
    from repro.data.pipeline import make_batch
    from test_train_steps import B, S

    losses = []
    for step in range(n):
        batch = make_batch(cfg, 0, step, B, S)
        state, m = rt.step(step, state, batch)
        losses.append(float(m["loss"]))
    return state, losses


def test_runtime_int8_wire_bucket_close_to_f32(single_mesh):
    """A forced-int8 wire bucket executes through the quantize edge and
    stays within a tight bound of the f32 reference trajectory."""
    from repro.train import DeftRuntime

    cfg, opt, sched, layout, key, _ = _smoke_runtime()
    nb = layout.n_buckets
    pol = PrecisionPolicy(wire=("int8",) + ("f32",) * (nb - 1))
    lay_q = layout.with_precision(pol)
    with single_mesh:
        rt_f = DeftRuntime(cfg, opt, sched, layout, single_mesh)
        rt_q = DeftRuntime(cfg, opt, sched, lay_q, single_mesh)
        assert rt_q.stats()["wire_precision"] == pol.describe()
        s_f = rt_f.init_state(key)
        s_q = rt_q.init_state(key)
        n = sched.period * 2
        s_f, l_f = _run_steps(rt_f, s_f, cfg, sched, n)
        s_q, l_q = _run_steps(rt_q, s_q, cfg, sched, n)
    assert np.all(np.isfinite(l_q))
    diff = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                              b.astype(jnp.float32))))
        for a, b in zip(s_f["pbuf"], s_q["pbuf"])
    )
    # quantization must actually bite (the edge is live) ...
    assert diff > 0.0
    # ... but the trajectory stays within a tight envelope of f32
    assert diff < 5e-3, diff
    assert abs(l_f[-1] - l_q[-1]) < 0.05


def test_runtime_bf16sr_master_bounded_drift(single_mesh):
    """The bf16sr resident master: params live at bf16, updates write
    back through seeded stochastic rounding, and the trajectory stays
    within the expected rounding envelope of the f32 master run."""
    from repro.train import DeftRuntime

    cfg, opt, sched, layout, key, rt_cfg = _smoke_runtime(
        master_dtype="bf16sr"
    )
    with single_mesh:
        rt_f = DeftRuntime(cfg, opt, sched, layout, single_mesh)
        rt_b = DeftRuntime(cfg, opt, sched, layout, single_mesh,
                           config=rt_cfg)
        assert rt_b.stats()["master_dtype"] == "bf16sr"
        s_f = rt_f.init_state(key)
        s_b = rt_b.init_state(key)
        for p in s_b["pbuf"]:
            assert p.dtype == jnp.bfloat16
        n = sched.period * 2
        s_f, l_f = _run_steps(rt_f, s_f, cfg, sched, n)
        s_b, l_b = _run_steps(rt_b, s_b, cfg, sched, n)
        # determinism: the seeded rounding reproduces exactly
        s_b2 = rt_b.init_state(key)
        s_b2, _ = _run_steps(rt_b, s_b2, cfg, sched, n)
    assert np.all(np.isfinite(l_b))
    for a, b in zip(s_b["pbuf"], s_b2["pbuf"]):
        assert bool(jnp.array_equal(a, b))
    rel = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
              / (jnp.max(jnp.abs(b)) + 1e-9))
        for a, b in zip(s_b["pbuf"], s_f["pbuf"])
    )
    assert rel < 0.05, rel


def test_precision_hot_swap_at_cycle_boundary(single_mesh):
    """A mid-run wire-precision change is a cycle-boundary layout swap:
    no restart, the repack is pure aliasing (zero moved elements), and
    the new policy is live from the boundary on."""
    from repro.train import DeftRuntime

    cfg, opt, sched, layout, key, _ = _smoke_runtime()
    nb = layout.n_buckets
    lay_q = layout.with_precision(PrecisionPolicy.uniform(nb, "bf16"))
    assert lay_q != layout
    with single_mesh:
        rt = DeftRuntime(cfg, opt, sched, layout, single_mesh)
        state = rt.init_state(key)
        state, _ = _run_steps(rt, state, cfg, sched, sched.period)
        from repro.data.pipeline import make_batch
        from test_train_steps import B, S

        batch = make_batch(cfg, 0, 0, B, S)
        info = rt.prepare_swap(sched, state, batch, layout=lay_q)
        assert info["layout_change"] and info["moved_elems"] == 0
        assert rt.swap_ready()
        for step in range(sched.period, 2 * sched.period):
            batch = make_batch(cfg, 0, step, B, S)
            state, m = rt.step(step, state, batch)
            assert bool(jnp.isfinite(m["loss"]))
        assert rt.hot_swaps == 1 and rt.layout_swaps == 1
        assert rt.layout is lay_q
        assert rt.stats()["wire_precision"] == "bf16x" + str(nb)


def test_runtime_wire_bytes_match_plan(single_mesh):
    """The bytes the executed collectives ship (phase span attrs) must
    equal what the knapsack priced — the §13 acceptance
    loop: policy -> pricing -> execution -> measured attribution."""
    from repro.obs import Tracer, wire_bytes_report
    from repro.train import DeftRuntime

    cfg, opt, sched, layout, key, _ = _smoke_runtime()
    nb = layout.n_buckets
    pol = PrecisionPolicy(
        wire=("int8", "bf16") + ("f32",) * (nb - 2)
    )
    lay_q = layout.with_precision(pol)
    tracer = Tracer(capacity=1 << 14)
    with single_mesh:
        rt = DeftRuntime(cfg, opt, sched, lay_q, single_mesh,
                         tracer=tracer)
        state = rt.init_state(key)
        state, _ = _run_steps(rt, state, cfg, sched, sched.period * 2)
    planned = rt.wire_bytes_per_phase
    assert len(planned) == sched.period
    rep = wire_bytes_report(tracer, planned)
    assert rep.planned_per_cycle == sum(planned)
    assert rep.ok, (rep.planned_per_phase, rep.measured_per_phase)
    observed = [p for p in rep.precisions if p is not None]
    assert observed and all(p == pol.describe() for p in observed)
    assert rt.stats()["planned_wire_bytes_per_cycle"] == sum(planned)


def test_runtime_rejects_master_dtype_changing_swap(single_mesh):
    """Hot-swaps may change wire precision but never the resident
    master dtype — that would need a state-wide cast, not a repack."""
    from repro.train import DeftRuntime

    cfg, opt, sched, layout, key, _ = _smoke_runtime()
    nb = layout.n_buckets
    bad = layout.with_precision(
        PrecisionPolicy.uniform(nb, "f32", master="bf16sr")
    )
    with single_mesh:
        rt = DeftRuntime(cfg, opt, sched, layout, single_mesh)
        state = rt.init_state(key)
        from repro.data.pipeline import make_batch
        from test_train_steps import B, S

        batch = make_batch(cfg, 0, 0, B, S)
        with pytest.raises(ValueError, match="master"):
            rt.prepare_swap(sched, state, batch, layout=bad)


# ---------------------------------------------------------------------------
# checkpoint sidecar: the policy is part of the layout a resume rebuilds
# ---------------------------------------------------------------------------
def test_layout_descriptor_roundtrips_precision(tmp_path):
    """save_layout_descriptor records the §13 wire/master policy and
    load_layout_descriptor rebuilds the SAME quantized layout — a
    resume under a bf16sr master must not silently come back f32."""
    from repro.checkpoint.checkpoint import (
        load_layout_descriptor,
        save_layout_descriptor,
    )
    from repro.train.bucketing import build_bucket_layout

    params = {f"l{i}": jnp.zeros((64,), jnp.float32) for i in range(4)}
    bucket_of, nb = (0, 0, 1, 2), 3
    pol = PrecisionPolicy(wire=("int8", "bf16", "f32"), master="bf16sr")
    lay = build_bucket_layout(params, bucket_of, nb, precision=pol)
    save_layout_descriptor(str(tmp_path), 7, lay, next_phase=1,
                           digest="d")
    got, phase, digest = load_layout_descriptor(str(tmp_path), 7, params)
    assert (phase, digest) == (1, "d")
    assert got.precision == pol
    assert got.bucket_of_leaf == lay.bucket_of_leaf

    # a policy-free layout stays policy-free on reload
    lay0 = build_bucket_layout(params, bucket_of, nb)
    save_layout_descriptor(str(tmp_path), 8, lay0)
    got0, _, _ = load_layout_descriptor(str(tmp_path), 8, params)
    assert got0.precision is None
