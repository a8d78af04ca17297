"""Unified trace/metrics layer (DESIGN.md §11): ring-buffer tracer with
injectable clock, Chrome-trace export determinism, SimResult->spans->
attribution closure against the simulator's own numbers, the live
divergence signal leading the EMA drift trigger, the runtime's swap_log
compat shim, the first-dispatch cold tag, and the <2% tracing-overhead
bound on fused smoke dispatch."""
import dataclasses
import json
import math
import random
import statistics

import jax
import jax.numpy as jnp
import pytest

from repro.adapt import (
    AdaptConfig,
    AdaptiveController,
    BandwidthDrop,
    SyntheticTelemetrySource,
    Telemetry,
    TelemetryConfig,
    run_control_loop,
    scale_times,
)
from repro.adapt.calibrate import planned_phase_durations
from repro.configs import get_config
from repro.core.bucket import BucketTimes
from repro.core.deft import feedback_solve
from repro.core.preserver import WalkParams
from repro.core.profiler import HardwareModel
from repro.core.scheduler import DeftScheduler
from repro.core.simulator import simulate_deft
from repro.data.pipeline import make_batch
from repro.elastic import HealthConfig, HealthMonitor
from repro.models.model import init_params
from repro.obs import (
    Attribution,
    ManualClock,
    Metrics,
    METRICS_SCHEMA_VERSION,
    SPAN_KINDS,
    Span,
    Tracer,
    attribute,
    attribute_trace,
    format_event,
    latest_phase_durations,
    measured_phase_durations_from_trace,
    phase_divergence,
    sim_metrics_from_spans,
    spans_from_sim,
    timeline_bubbles,
    validate_summary,
)
from repro.optim.optimizers import adamw
from repro.train import (
    DeftRuntime,
    assign_buckets,
    build_bucket_layout,
    leaf_bucket_times,
)

WALK = WalkParams(s0=4.0, eta=0.01, mu=1.0, sigma=40.0, batch=256)


def _toy_times(n=8, cr=1.8, seed=0):
    rng = random.Random(seed)
    fwd = tuple(rng.uniform(0.002, 0.02) for _ in range(n))
    bwd = tuple(2 * f for f in fwd)
    comm = tuple(rng.uniform(0.005, 0.08) for _ in range(n))
    t = BucketTimes(fwd, bwd, comm)
    scale = cr * (t.fwd_total + t.bwd_total) / t.comm_total
    return BucketTimes(fwd, bwd, tuple(c * scale for c in comm))


# ---------------------------------------------------------------------------
# Tracer: ring bound, injectable clock, deterministic export
# ---------------------------------------------------------------------------
def test_tracer_ring_bound_and_stats():
    tr = Tracer(capacity=4, clock=ManualClock())
    for i in range(10):
        tr.instant("replan", f"e{i}", step=i)
    assert len(tr) == 4
    st = tr.stats()
    assert st["recorded"] == 10 and st["retained"] == 4
    assert st["dropped"] == 6
    assert st["by_kind"] == {"replan": 4}
    # the ring keeps the NEWEST spans
    assert [s.name for s in tr.spans()] == ["e6", "e7", "e8", "e9"]


def test_tracer_rejects_unknown_kind():
    tr = Tracer(capacity=8)
    with pytest.raises(ValueError):
        tr.add("not-a-kind", "x", 0.0, 1.0)
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_tracer_span_contextmanager_uses_clock_and_survives_raise():
    clk = ManualClock()
    tr = Tracer(capacity=8, clock=clk)
    with tr.span("repack", "ok", step=3):
        clk.advance(0.5)
    with pytest.raises(RuntimeError):
        with tr.span("repack", "boom"):
            clk.advance(0.25)
            raise RuntimeError("x")
    spans = tr.spans("repack")
    assert [s.name for s in spans] == ["ok", "boom"]
    assert spans[0].duration == pytest.approx(0.5)
    assert spans[1].duration == pytest.approx(0.25)


def test_tracer_spans_filter_accepts_str_or_iterable():
    tr = Tracer(capacity=8, clock=ManualClock())
    tr.instant("replan", "a")
    tr.instant("repack", "b")
    tr.instant("elastic", "c")
    assert [s.name for s in tr.spans("repack")] == ["b"]
    assert [s.name for s in tr.spans(("replan", "elastic"))] == ["a", "c"]


def _replayed_trace():
    """One deterministic synthetic run under an injected clock."""
    clk = ManualClock()
    tr = Tracer(capacity=64, clock=clk)
    for step in range(5):
        t0 = clk()
        clk.advance(0.010 + step * 0.001)
        tr.add("phase", f"phase{step % 2}", t0, clk(),
               step=step, phase=step % 2, first=(step == 0))
        tr.add("step", f"step{step}", t0, clk(), step=step)
    tr.instant("swap-install", "swap-install", step=4, period=2,
               updates_per_period=1, n_buckets=3, shards=1, repack_s=None)
    return tr


def test_trace_replay_bit_match(tmp_path):
    """Identical injected-clock replays export byte-identical traces."""
    p1, p2 = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    pay1 = _replayed_trace().export_chrome_trace(p1)
    pay2 = _replayed_trace().export_chrome_trace(p2)
    assert pay1 == pay2
    assert open(p1, "rb").read() == open(p2, "rb").read()


def test_chrome_export_is_perfetto_shaped(tmp_path):
    path = str(tmp_path / "t.json")
    tr = _replayed_trace()
    tr.export_chrome_trace(path, extra={"note": "test"})
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert {m["args"]["name"] for m in metas} >= {"steps", "phases"}
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs and all("dur" in e and "ts" in e and "cat" in e for e in xs)
    inst = [e for e in evs if e["ph"] == "i"]
    assert inst and all(e["s"] == "t" for e in inst)
    # seconds -> microseconds
    ph0 = next(e for e in xs if e["cat"] == "phase")
    assert ph0["dur"] == pytest.approx(0.010 * 1e6)
    assert doc["otherData"]["dropped_spans"] == 0
    assert doc["otherData"]["note"] == "test"


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------
def test_metrics_counters_gauges_and_jsonl(tmp_path):
    m = Metrics()
    m.inc("replans")
    m.inc("replans")
    m.inc("spans", by=5)
    m.set("coverage_rate", 1.8)
    m.set("coverage_rate", 2.0)       # gauge holds the latest
    assert m.counter("replans") == 2
    assert m.counter("missing") == 0
    assert m.gauge("coverage_rate") == 2.0
    assert m.gauge("missing") is None

    s = m.summary()
    validate_summary(s)
    assert s["schema"] == METRICS_SCHEMA_VERSION
    assert s["counters"] == {"replans": 2, "spans": 5}
    assert s["gauges"] == {"coverage_rate": 2.0}

    path = str(tmp_path / "m.jsonl")
    m.export_jsonl(path)
    m.inc("replans")
    m.export_jsonl(path, extra={"step": 7})
    lines = open(path).read().splitlines()
    assert len(lines) == 2
    last = json.loads(lines[-1])
    validate_summary(last)
    assert last["counters"]["replans"] == 3 and last["extra"]["step"] == 7


def test_validate_summary_rejects_bad_payloads():
    with pytest.raises(ValueError):
        validate_summary({"schema": METRICS_SCHEMA_VERSION})
    with pytest.raises(ValueError):
        validate_summary({"schema": 999, "counters": {}, "gauges": {}})
    with pytest.raises(ValueError):
        validate_summary(
            {"schema": METRICS_SCHEMA_VERSION, "counters": [], "gauges": {}}
        )


# ---------------------------------------------------------------------------
# Closure: SimResult -> spans -> the simulator's own numbers
# ---------------------------------------------------------------------------
def _deft_sim(times, n_iters=24):
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    sched = DeftScheduler(times, scfg)
    plans = sched.run(n_iters)
    sim = simulate_deft(times, plans, mu=scfg.mu,
                        heterogeneous=scfg.heterogeneous,
                        keep_timeline=True)
    return sim, scfg, schedule


def test_sim_span_closure_reproduces_simulator_numbers():
    times = _toy_times()
    sim, scfg, _ = _deft_sim(times)
    spans = spans_from_sim(sim)
    m = sim_metrics_from_spans(spans, mu=scfg.mu)
    # iteration time is bit-exact (same subtraction the simulator does)
    assert m.iteration_time == sim.iteration_time
    assert m.bubble_fraction == pytest.approx(sim.bubble_fraction,
                                              rel=1e-9, abs=1e-12)
    # compute reconstructed from F/B spans == the profile totals
    assert m.compute_time == pytest.approx(
        times.fwd_total + times.bwd_total, rel=1e-9
    )
    # per-bucket nominal comm matches the profile (merging never grows a
    # tensor, so any occurrence carries the bucket's nominal cost)
    for b, c in m.per_bucket_comm.items():
        assert c == pytest.approx(times.comm[b], rel=1e-9)
    assert m.coverage_rate == pytest.approx(times.coverage_rate, rel=1e-9)
    assert 0.0 <= m.bubble_fraction < 1.0


def test_spans_from_sim_requires_timeline():
    times = _toy_times(n=4)
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    plans = DeftScheduler(times, scfg).run(8)
    sim = simulate_deft(times, plans, mu=scfg.mu,
                        heterogeneous=scfg.heterogeneous)
    with pytest.raises(ValueError):
        spans_from_sim(sim)


def test_timeline_bubbles_attributes_idle_to_collectives():
    # compute busy [0,1] and [2,3]; a bucket-7 collective covers the
    # idle gap [1,2]; a bucket-1 collective overlaps busy time only
    spans = [
        Span("compute", "F0@0", 0.0, 1.0),
        Span("compute", "B0@0", 2.0, 3.0),
        Span("collective", "C7", 0.8, 2.0, attrs=(("bucket", 7), ("link", 0))),
        Span("collective", "C1", 0.2, 0.9, attrs=(("bucket", 1), ("link", 1))),
    ]
    idle, exposed, busy = timeline_bubbles(spans, 0.0, 3.0)
    assert idle == pytest.approx(1.0)
    assert exposed == {7: pytest.approx(1.0)}
    assert busy[0] == pytest.approx(1.2) and busy[1] == pytest.approx(0.7)


# ---------------------------------------------------------------------------
# Live attribution: measured vs plan
# ---------------------------------------------------------------------------
def test_attribution_undisturbed_run_matches_plan():
    times = _toy_times()
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    planned = planned_phase_durations(times, scfg, schedule.period)
    att = attribute(planned, times, scfg, schedule)
    assert isinstance(att, Attribution)
    # measuring exactly the plan: identity scales, ~zero divergence
    assert att.comp_scale == pytest.approx(1.0, abs=0.02)
    assert att.comm_scale == pytest.approx(1.0, abs=0.02)
    assert att.max_divergence < 1e-9
    assert att.cr_error < 0.05
    assert att.measured_cr == pytest.approx(times.coverage_rate, rel=0.05)
    assert att.iteration_time > 0 and 0 <= att.bubble_fraction < 1
    # the knapsack never over-fills its capacity windows by much more
    # than the simulator's overflow spill
    assert att.capacity_utilization["link0"] > 0
    for v in att.capacity_utilization.values():
        assert v < 2.0


def test_attribution_degraded_run_flags_comp_scale():
    # a compute slowdown lengthens every phase monotonically, so the
    # fit is well identified (a comm slowdown is not: missed collective
    # windows turn into gather-skips and phases SHORTEN — see §11)
    times = _toy_times()
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    degraded = scale_times(times, 1.6, 1.0)
    measured = planned_phase_durations(degraded, scfg, schedule.period)
    att = attribute(measured, times, scfg, schedule)
    assert att.comp_scale > 1.3          # the compute axis took the hit
    assert att.comm_scale == pytest.approx(1.0, abs=0.35)
    assert att.measured_cr < times.coverage_rate
    assert att.max_divergence > 0.1
    # every bucket syncs inside some slipped phase, so all diverge
    assert att.per_bucket_divergence
    assert max(att.per_bucket_divergence.values()) > 0.05


def test_phase_divergence_and_latest_samples():
    planned = [1.0, 2.0]
    assert phase_divergence(planned, [1.1, None]) == (
        pytest.approx(0.1), None,
    )
    tel = Telemetry(2, TelemetryConfig(warmup_steps=0))
    tel.record(0, 0, 1.0)
    tel.record(1, 1, 2.0)
    tel.record(2, 0, 3.0)              # newest sample wins
    assert latest_phase_durations(tel.samples(), 2) == [3.0, 2.0]


def test_attribute_trace_excludes_first_dispatch_spans():
    times = _toy_times()
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    period = schedule.period
    planned = planned_phase_durations(times, scfg, period)
    clk = ManualClock()
    tr = Tracer(capacity=256, clock=clk)
    for step in range(3 * period):
        p = step % period
        # first cycle is compile-polluted: 50x the planned duration
        dur = planned[p] * (50.0 if step < period else 1.0)
        t0 = clk()
        # the runtime's enqueue span is no measure of the phase: only the
        # training loop's blocked step span is read
        tr.add("phase", "deft.phase", t0, t0 + 1e-6, step=step, phase=p,
               first=(step < period))
        clk.advance(dur)
        tr.add("step", f"step{step}", t0, clk(), step=step, phase=p,
               first=(step < period))
    measured = measured_phase_durations_from_trace(tr, period)
    for p in range(period):
        assert measured[p] == pytest.approx(planned[p], rel=1e-9)
    att = attribute_trace(tr, times, scfg, schedule)
    assert att.max_divergence < 1e-6   # pollution fully excluded


# ---------------------------------------------------------------------------
# Divergence leads the EMA drift trigger
# ---------------------------------------------------------------------------
_DROP_STEP = 24
_DROP_SCALE = 1.9      # phase slip in (threshold, EMA-instant) band


def _drop_controller(drift_source):
    times = _toy_times()
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    src = SyntheticTelemetrySource(
        times, BandwidthDrop(step=_DROP_STEP, comm_scale=_DROP_SCALE)
    )
    ctrl = AdaptiveController(
        times, schedule, scfg, walk=WALK,
        cfg=AdaptConfig(warmup_steps=4, check_every=1, cooldown_steps=8,
                        min_loss_samples=10**9, drift_source=drift_source),
    )
    return ctrl, src, times, schedule, scfg


def test_divergence_flags_drop_before_ema_trigger():
    """The attribution divergence crosses the drift threshold strictly
    before the legacy EMA screen replans — the acceptance property."""
    ctrl, src, times, schedule, scfg = _drop_controller("ema")
    planned = planned_phase_durations(times, scfg, schedule.period)
    flagged = None
    phase = 0
    ema_step = None
    for step in range(3 * _DROP_STEP):
        wall = src.wall_time(step, ctrl.schedule, ctrl.scheduler_cfg,
                             phase, solve_times=ctrl.times)
        ev = ctrl.observe(step, phase, wall)
        phase = (phase + 1) % schedule.period
        if flagged is None:
            div = phase_divergence(
                planned,
                latest_phase_durations(ctrl.telemetry.samples(),
                                       schedule.period),
            )
            if max((abs(d) for d in div if d is not None), default=0.0) \
                    > ctrl.cfg.drift_threshold:
                flagged = step
        if ev is not None:
            ema_step = step
            break
    assert ema_step is not None, "EMA screen never triggered"
    assert flagged is not None and _DROP_STEP <= flagged < ema_step
    # and the full attribution report at the flag step names the drop
    att = attribute(
        latest_phase_durations(ctrl.telemetry.samples(), schedule.period),
        times, scfg, ctrl.schedule,
    )
    assert att.comm_scale > 1.1
    assert att.max_divergence > ctrl.cfg.drift_threshold


def test_divergence_drift_source_replans_no_later_than_ema():
    ctrl_e, src_e, *_ = _drop_controller("ema")
    ctrl_d, src_d, *_ = _drop_controller("divergence")
    run_control_loop(ctrl_e, src_e, 3 * _DROP_STEP)
    run_control_loop(ctrl_d, src_d, 3 * _DROP_STEP)
    assert ctrl_e.events and ctrl_d.events
    assert ctrl_d.events[0].step < ctrl_e.events[0].step
    # both tripped after the drop, on the timing path
    for ev in (ctrl_d.events[0], ctrl_e.events[0]):
        assert ev.step >= _DROP_STEP and ev.trigger == "timing-drift"


def test_controller_emits_replan_spans():
    ctrl, src, *_ = _drop_controller("divergence")
    tracer = Tracer(capacity=64, clock=ManualClock())
    ctrl.tracer = tracer
    run_control_loop(ctrl, src, 3 * _DROP_STEP)
    spans = tracer.spans("replan")
    assert len(spans) == len(ctrl.events)
    sp = spans[0]
    assert sp.name == "timing-drift" and sp.step == ctrl.events[0].step
    args = sp.args
    assert args["old_period"] == ctrl.events[0].old_period
    assert args["changed"] == ctrl.events[0].changed
    assert args["comm_scale"] > 1.0


# ---------------------------------------------------------------------------
# Telemetry cold tag (first-dispatch pollution fix)
# ---------------------------------------------------------------------------
def test_telemetry_cold_tag_replaces_fixed_warmup():
    tel = Telemetry(1, TelemetryConfig(warmup_steps=5))
    tel.record(0, 0, 100.0, cold=True)     # first dispatch: never enters
    assert tel.phase_time(0) is None
    tel.record(1, 0, 1.0, cold=False)      # tagged warm: enters at once
    assert tel.phase_time(0) == pytest.approx(1.0)
    # legacy behaviour (no tag) still honors the fixed count
    tel2 = Telemetry(1, TelemetryConfig(warmup_steps=5))
    tel2.record(0, 0, 1.0)
    assert tel2.phase_time(0) is None


def test_telemetry_cold_tag_respects_rebase_window():
    tel = Telemetry(1, TelemetryConfig(warmup_steps=0))
    tel.rebase(1, extra_warmup=2)
    # the old schedule's tail steps land inside the re-armed window even
    # when tagged warm — they ran under the OLD phase keys
    tel.record(0, 0, 9.0, cold=False)
    tel.record(1, 0, 9.0, cold=False)
    assert tel.phase_time(0) is None
    tel.record(2, 0, 1.0, cold=False)
    assert tel.phase_time(0) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# One formatter for every event surface
# ---------------------------------------------------------------------------
def test_format_event_all_surfaces():
    # swap install / failure dicts (the runtime's swap_log shapes)
    line = format_event({"step": 10, "period": 4, "updates_per_period": 1,
                         "n_buckets": 5, "shards": 2, "repack_s": 0.025})
    assert line.startswith("swap") and "period=4" in line
    assert "repack 25 ms" in line
    line = format_event({"step": None, "event": "swap-compile-failed",
                         "attempt": 1, "retrying": True, "error": "boom"})
    assert "compile-failed" in line and "retrying" in line
    line = format_event({"step": None, "event": "swap-abandoned",
                         "attempts": 3, "elapsed_s": 1.5,
                         "superseded": True, "error": "boom"})
    assert "ABANDONED" in line and "superseded" in line
    # elastic migration / halt dicts
    line = format_event({"step": 12, "action": "scale-down", "trigger":
                         "dead", "detected_step": 9, "old_shards": 4,
                         "new_shards": 3, "old_period": 2, "new_period": 3,
                         "migrate_s": 0.5, "repack_s": 0.1})
    assert line.startswith("elastic") and "4->3 shards" in line
    line = format_event({"step": 12, "action": "checkpoint-halt",
                         "trigger": "dead", "detected_step": 9,
                         "checkpoint": "/tmp/x"})
    assert "checkpoint-halt" in line
    # spans
    line = format_event(Span("repack", "repack-state", 0.0, 0.004, step=3,
                             attrs=(("moved_elems", 42),)))
    assert line.startswith("repack") and "4.00 ms" in line
    assert "moved_elems=42" in line
    # replan + fault events route through their describe()
    times = _toy_times(n=4)
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    src = SyntheticTelemetrySource(
        times, BandwidthDrop(step=8, comm_scale=3.0))
    ctrl = AdaptiveController(
        times, schedule, scfg, walk=WALK,
        cfg=AdaptConfig(warmup_steps=2, check_every=1, cooldown_steps=4,
                        min_loss_samples=10**9))
    run_control_loop(ctrl, src, 40)
    assert ctrl.events
    assert format_event(ctrl.events[0]).startswith("adapt")
    mon = HealthMonitor(2, HealthConfig(warmup_steps=0))
    ev = mon.notice_preemption(5, 1)
    assert format_event(ev).startswith("elastic")
    assert "event" in format_event(object())


def test_health_monitor_mirrors_detections_into_trace():
    tracer = Tracer(capacity=32, clock=ManualClock())
    mon = HealthMonitor(
        4, HealthConfig(warmup_steps=1, straggler_patience=2), tracer=tracer
    )
    mon.notice_preemption(4, 3)
    for i in range(8):
        walls = [0.1, 0.1 * (3.0 if i >= 2 else 1.0), 0.1, None]
        mon.observe(i, walls)
    names = [s.name for s in tracer.spans("elastic")]
    assert "detect-preemption" in names
    assert "detect-straggler" in names
    sp = next(s for s in tracer.spans("elastic")
              if s.name == "detect-straggler")
    assert sp.args["shard"] == 1 and sp.args["monitor_clock"] > 0


# ---------------------------------------------------------------------------
# The engine's named scopes in a compiled phase
# ---------------------------------------------------------------------------
def test_scope_names_parse():
    from repro.obs.hlo_scopes import innermost, parse_sync

    assert innermost("jit(f)/shard_map/deft_model/transpose(jvp())/dot"
                     ) == "deft_model"
    # XLA joins the names of merged instructions with ';'
    assert innermost("jit(f)/shard_map/broadcast_in_dim;deft_route/"
                     "broadcast_in_dim") == "deft_route"
    assert innermost("jit(f)/shard_map/deft_update/deft_sync.b2.primary."
                     "new/psum") == "deft_sync.b2.primary.new"
    assert innermost("jit(f)/out") is None and innermost("") is None
    assert parse_sync("deft_sync.b3.secondary.new") == (
        "sync", 3, "secondary", "new")
    assert parse_sync("deft_gather.b0.primary") == (
        "gather", 0, "primary", None)
    assert parse_sync("deft_route") is None


_REWRITTEN = """HloModule m

%region_rs (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="deft_sync.b0.secondary.cur/reduce_scatter"}
  %b = f32[] parameter(1), metadata={op_name="deft_sync.b0.secondary.cur/reduce_scatter"}
  ROOT %add.1 = f32[] add(%a, %b), metadata={op_name="deft_sync.b0.secondary.cur/add"}
}

%region_sum (c: f32[], d: f32[]) -> f32[] {
  %c = f32[] parameter(0)
  %d = f32[] parameter(1)
  ROOT %add.2 = f32[] add(%c, %d), metadata={op_name="deft_sync.b1.primary.new/add"}
}

%region_plain (e: f32[], f: f32[]) -> f32[] {
  %e = f32[] parameter(0)
  %f = f32[] parameter(1)
  ROOT %add.3 = f32[] add(%e, %f)
}

%async_body (p: f32[8]) -> f32[32] {
  %p = f32[8] parameter(0)
  ROOT %all-gather.1 = f32[32] all-gather(%p), dimensions={0}, metadata={op_name="jit(f)/shard_map/deft_sync.b2.secondary.new/all_gather"}
}

ENTRY %main (x: f32[32], y: f32[32], u: f32[32], w: f32[3], z: f32[8], i: s32[]) -> f32[32] {
  %x = f32[32] parameter(0)
  %y = f32[32] parameter(1)
  %u = f32[32] parameter(2)
  %w = f32[3] parameter(3)
  %z = f32[8] parameter(4)
  %i = s32[] parameter(5)
  %all-reduce.1 = f32[32] all-reduce(%x), to_apply=%region_rs
  %dynamic-slice.1 = f32[8] dynamic-slice(%all-reduce.1, %i), dynamic_slice_sizes={8}
  %all-gather.2 = f32[32] all-gather(%dynamic-slice.1), dimensions={0}, metadata={op_name="jit(f)/shard_map/deft_sync.b0.secondary.cur/all_gather"}
  %start = (f32[8], f32[32]) fusion(%z), kind=kCustom, calls=%async_body
  %all-reduce.2 = (f32[32], f32[32], f32[3]) all-reduce(%y, %u, %w), to_apply=%region_sum, metadata={op_name="jit(f)/shard_map/deft_sync.b1.primary.new/psum"}
  %gte.0 = f32[32] get-tuple-element(%all-reduce.2), index=0, metadata={op_name="jit(f)/shard_map/deft_sync.b1.primary.new/psum"}
  %gte.1 = f32[32] get-tuple-element(%all-reduce.2), index=1
  %dynamic-slice.2 = f32[8] dynamic-slice(%gte.1, %i), dynamic_slice_sizes={8}
  %all-gather.3 = f32[32] all-gather(%dynamic-slice.2), dimensions={0}, metadata={op_name="jit(f)/shard_map/deft_sync.b3.secondary.new/all_gather"}
  %gte.2 = f32[3] get-tuple-element(%all-reduce.2), index=2, metadata={op_name="jit(f)/shard_map/deft_metrics/psum"}
  %all-reduce.3 = f32[32] all-reduce(%x), to_apply=%region_plain
  ROOT %multiply.1 = f32[32] multiply(%all-reduce.3, %gte.0), metadata={op_name="jit(f)/shard_map/deft_update/mul"}
}
"""


def test_collective_scopes_follow_xla_rewrites():
    """A collective XLA made without metadata is named by its reduction
    computation; an async wrapper by the collective it calls; a combined
    all-reduce by each tuple element's own scope or the sync its element
    reaches; a collective whose result reaches no sync stays unnamed."""
    from repro.obs import collective_scopes

    got = collective_scopes([_REWRITTEN])
    b0 = frozenset({"deft_sync.b0.secondary.cur"})
    assert got["all-reduce.1"] == b0 and got["all-gather.2"] == b0
    b2 = frozenset({"deft_sync.b2.secondary.new"})
    assert got["start"] == b2 and got["all-gather.1"] == b2
    assert got["all-reduce.2"] == {"deft_sync.b1.primary.new",
                                   "deft_sync.b3.secondary.new",
                                   "deft_metrics"}
    assert got["all-gather.3"] == {"deft_sync.b3.secondary.new"}
    assert got["all-reduce.3"] == frozenset()
    assert set(got) == {"all-reduce.1", "all-gather.2", "start",
                        "all-gather.1", "all-reduce.2", "all-gather.3",
                        "all-reduce.3"}


def test_collective_scopes_on_a_compiled_v5e_phase():
    """The four-chip cell's phase as the TPU compiler writes it (compiled
    for a described v5e:2x2; the entry computation and the computations
    its collectives call, layouts and backend configs cut): its
    reduce-scatters run as all-reduces without metadata, and four
    buckets' syncs and the metrics psum share one combined all-reduce.
    Every collective is named, on the links the plan gave its buckets."""
    import pathlib

    from repro.obs import collective_scopes
    from repro.obs.hlo_scopes import parse_sync

    text = (pathlib.Path(__file__).parent / "data"
            / "v5e_2x2_deft_phase.hlo.txt").read_text()
    got = collective_scopes([text])
    sec = lambda b, gen="new": f"deft_sync.b{b}.secondary.{gen}"
    assert got["all-reduce.13"] == {sec(0, "cur")}      # 1.56 GB, no metadata
    assert got["all-reduce.9"] == {sec(4)}
    assert got["psum.21"] == {"deft_sync.b1.primary.new"}
    assert got["psum.22"] == {"deft_sync.b5.primary.new"}
    assert got["async-collective-start.1"] == {sec(3)}
    assert got["all-reduce.14"] == {sec(2), sec(7), sec(8), "deft_metrics"}
    assert all(got.values())
    # the plan: buckets 1 and 5 on the primary link, the rest secondary
    buckets = set()
    for found in got.values():
        for scope in found - {"deft_metrics"}:
            _, b, link, gen = parse_sync(scope)
            assert link == ("primary" if b in (1, 5) else "secondary")
            assert gen == ("cur" if b == 0 else "new")
            buckets.add(b)
    assert buckets == set(range(9))


# ---------------------------------------------------------------------------
# Runtime integration: spans, swap_log shim, overhead bound
# ---------------------------------------------------------------------------
B, S = 4, 32


def _tiny_cfg():
    base = get_config("qwen3-4b")
    return dataclasses.replace(
        base, name="qwen3-tiny", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512,
    )


def _tiny_schedule(cfg, params):
    bucket_of, nb = assign_buckets(params, cfg, partition_elems=20_000)
    hw = HardwareModel(dp_degree=2)
    times = leaf_bucket_times(params, cfg, bucket_of, nb, hw, S, B)
    scale = 1.8 * (times.fwd_total + times.bwd_total) / times.comm_total
    times = BucketTimes(times.fwd, times.bwd,
                        tuple(c * scale for c in times.comm))
    schedule, _, scfg, _ = feedback_solve(times, WALK)
    layout = build_bucket_layout(params, bucket_of, nb)
    return times, schedule, scfg, layout


def test_runtime_trace_and_swap_log_shim(single_mesh):
    cfg = _tiny_cfg()
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    times, schedule, scfg, layout = _tiny_schedule(cfg, params)
    tracer = Tracer(capacity=4096)
    runtime = DeftRuntime(cfg, adamw(1e-3), schedule, layout, single_mesh,
                          tracer=tracer)
    assert runtime.trace_steps and runtime.tracer is tracer
    state = runtime.init_state(key)

    # a second schedule to hot-swap to (comm 2x slower)
    new_schedule, _, _, _ = feedback_solve(
        scale_times(times, 1.0, 2.0), WALK
    )
    assert new_schedule.phases != schedule.phases
    n_steps = 2 * schedule.period + new_schedule.period
    with jax.set_mesh(single_mesh):
        for step in range(n_steps):
            t_s = tracer.now()
            state, m = runtime.step(step, state, make_batch(cfg, 0, step, B, S))
            # the training loop's blocked step span, as launch/train.py records it
            jax.block_until_ready(m["loss"])
            tracer.add("step", f"step{step}", t_s, tracer.now(), step=step,
                       phase=runtime.last_phase,
                       first=runtime.last_dispatch_first)
            if step == 0:
                assert runtime.last_dispatch_first        # cold tag
            if step == 2 * schedule.period - 1:
                # attribution over the undisturbed window: every phase
                # of the installed plan has an untagged (warm) sample
                att = attribute_trace(tracer, times, scfg, schedule)
                assert att.period == schedule.period
                assert all(mv is not None for mv in att.measured_phase_s)
                runtime.prepare_swap(new_schedule, state,
                                     make_batch(cfg, 0, 0, B, S),
                                     background=False)
        jax.block_until_ready(m["loss"])

    # per-step spans: one phase ⊃ launch per dispatch carrying the
    # planned wire bytes (place only for AOT-compiled phases: the jitted
    # fallback takes the batch as it comes), first-dispatch tagging on
    # exactly the unique executables
    phases = tracer.spans("phase")
    assert len(phases) == n_steps
    assert all(sp.name == "deft.phase" for sp in phases)
    assert all(sp.phase is not None and sp.duration > 0 for sp in phases)
    assert all("wire_bytes" in sp.args for sp in phases)
    firsts = [sp for sp in phases if sp.args.get("first")]
    assert firsts and firsts[0].step == 0
    phase_of = {sp.step: sp for sp in phases}
    launches = tracer.spans("launch")
    assert [sp.step for sp in launches] == [sp.step for sp in phases]
    for sp in launches + tracer.spans("place"):
        ph = phase_of[sp.step]
        assert ph.t0 <= sp.t0 <= sp.t1 <= ph.t1

    # control-plane spans + the swap_log compat shim
    assert len(tracer.spans("swap-compile")) == 1
    installs = tracer.spans("swap-install")
    assert len(installs) == 1
    log = runtime.swap_log
    assert len(log) == 1
    entry = log[0]
    assert entry["step"] % schedule.period == 0
    assert entry["period"] == new_schedule.period
    assert entry["updates_per_period"] == new_schedule.updates_per_period
    assert entry["n_buckets"] == layout.n_buckets
    assert entry["shards"] == layout.shards
    assert entry["repack_s"] is None          # same layout: no repack
    assert runtime.stats()["trace"]["recorded"] == tracer.n_recorded

    # spawn() propagates the tracer when tracing is on
    assert runtime.spawn(schedule=schedule).tracer is tracer


def test_untraced_runtime_records_control_plane_only(single_mesh):
    cfg = _tiny_cfg()
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    _, schedule, _, layout = _tiny_schedule(cfg, params)
    runtime = DeftRuntime(cfg, adamw(1e-3), schedule, layout, single_mesh)
    assert not runtime.trace_steps            # no per-step span cost
    assert runtime.swap_log == []             # shim on the internal tracer
    state = runtime.init_state(key)
    with jax.set_mesh(single_mesh):
        for step in range(2):
            state, m = runtime.step(step, state,
                                    make_batch(cfg, 0, step, B, S))
    assert runtime.tracer.spans("phase") == []


def _host_spans(path, prefix="deft."):
    """(name, start ns, end ns) of the host-plane events under
    ``prefix`` in the profiler session written to ``path``."""
    import glob

    xplane = glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True)
    assert len(xplane) == 1, xplane
    pd = jax.profiler.ProfileData.from_file(xplane[0])
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(prefix)]
    return sorted(out, key=lambda e: e[1])


@pytest.mark.parametrize("traced", [True, False], ids=["tracer", "no-tracer"])
def test_runtime_spans_reach_profiler_host_plane(single_mesh, tmp_path,
                                                  traced):
    """Under a profiler session every step shows on the host plane as
    ``deft.phase`` holding ``deft.place`` and ``deft.launch``, in the
    ring's order when a tracer is attached; without one the ring keeps
    no per-step span, and the annotations still reach the profiler."""
    cfg = _tiny_cfg()
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    _, schedule, _, layout = _tiny_schedule(cfg, params)
    tracer = Tracer(capacity=256) if traced else None
    runtime = DeftRuntime(cfg, adamw(1e-3), schedule, layout, single_mesh,
                          tracer=tracer)
    n_steps = schedule.period + 1
    with jax.set_mesh(single_mesh):
        state = runtime.init_state(key)
        runtime.compile(state, make_batch(cfg, 0, 0, B, S))
        with jax.profiler.trace(str(tmp_path)):
            for step in range(n_steps):
                state, m = runtime.step(step, state,
                                        make_batch(cfg, 0, step, B, S))
                jax.block_until_ready(m["loss"])
    host = _host_spans(tmp_path)
    phases = [e for e in host if e[0] == "deft.phase"]
    assert len(phases) == n_steps
    for name in ("deft.place", "deft.launch"):
        inner = [e for e in host if e[0] == name]
        assert len(inner) == n_steps
        assert all(p[1] <= e[1] <= e[2] <= p[2]
                   for p, e in zip(phases, inner))
    if traced:
        ring = tracer.spans("phase")
        assert [sp.step for sp in ring] == list(range(n_steps))
        # the ring's spans and the profiler's events time the same calls
        ring_s = [sp.duration for sp in ring]
        host_s = [(e - b) * 1e-9 for _, b, e in phases]
        assert all(h <= r + 1e-3 and r <= h + 1e-3
                   for h, r in zip(host_s, ring_s))
    else:
        assert runtime.tracer.spans(("phase", "place", "launch")) == []


@pytest.mark.slow
def test_tracing_overhead_under_2_percent(single_mesh):
    """Dispatching with per-step tracing attached stays within 2% of the
    untraced fused dispatch rate (median of paired interleaved chunk
    ratios, best of 3 attempts)."""
    import time

    cfg = _tiny_cfg()
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    _, schedule, _, layout = _tiny_schedule(cfg, params)
    opt = adamw(1e-3)
    rt_plain = DeftRuntime(cfg, opt, schedule, layout, single_mesh)
    rt_traced = DeftRuntime(cfg, opt, schedule, layout, single_mesh,
                            tracer=Tracer(capacity=1 << 16))
    batch = make_batch(cfg, 0, 0, B, S)
    with jax.set_mesh(single_mesh):
        s_plain = rt_plain.init_state(key)
        s_traced = rt_traced.init_state(key)
        rt_plain.compile(s_plain, batch)
        rt_traced.compile(s_traced, batch)

        def timed(rt, state, n=40):
            t0 = time.perf_counter()
            for i in range(n):
                state, m = rt.step(i, state, batch)
            jax.block_until_ready(m["loss"])
            return time.perf_counter() - t0, state

        # warm both, then time paired rounds and take the MEDIAN of the
        # per-round traced/plain ratios: pairing shares each round's
        # ambient load between the two engines, alternating which goes
        # first cancels any systematic second-position penalty, and the
        # median kills rounds where a load spike hit only one side
        # (min-of-chunks is one-sided — a single anomalously fast plain
        # chunk sets a floor the traced side can never match).  On a
        # loaded single-core host even that flakes, so the measurement
        # retries up to 3 times: a genuine overhead regression shifts
        # every round of every attempt and still fails.
        _, s_plain = timed(rt_plain, s_plain, n=10)
        _, s_traced = timed(rt_traced, s_traced, n=10)
        overhead = math.inf
        for _attempt in range(3):
            ratios = []
            for r in range(9):
                if r % 2 == 0:
                    dp, s_plain = timed(rt_plain, s_plain)
                    dt, s_traced = timed(rt_traced, s_traced)
                else:
                    dt, s_traced = timed(rt_traced, s_traced)
                    dp, s_plain = timed(rt_plain, s_plain)
                ratios.append(dt / dp)
            overhead = min(overhead, statistics.median(ratios) - 1.0)
            if overhead < 0.02:
                break
    assert overhead < 0.02, (
        f"tracing overhead {overhead * 100:.2f}% >= 2% "
        f"(median of paired traced/plain chunk ratios, best of 3 attempts)"
    )
