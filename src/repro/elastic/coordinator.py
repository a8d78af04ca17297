"""Elastic execution: drive a DeftRuntime across mesh changes (DESIGN.md §10).

The :class:`ElasticCoordinator` wraps a flat-state :class:`DeftRuntime`
and owns the fault-to-recovery pipeline:

    observe (per-shard walls) -> HealthMonitor -> FaultEvent
        -> ElasticController.propose (Preserver-gated plan)
        -> armed until the next cycle boundary
        -> migrate: fold accumulator rows -> device_put onto the
           survivor mesh -> ``repack_state`` -> ``reset_cycle`` -> new
           runtime dispatches — ZERO restart.

Shard identity: observations are indexed by **origin shard id** — the
data-parallel rows of the mesh the coordinator was constructed with.
After a 4->2 scale-down the surviving origin rows keep their ids, so a
:class:`~repro.elastic.faults.FaultScenario` scripted against the
original mesh replays unchanged across migrations; the coordinator
translates to current-mesh positions internally.

Accumulator folding: ``cur``/``fut`` rows carry per-device gradient
sums whose consumer divides by ``n_dp * k`` after a psum.  A mesh change
preserves the GLOBAL batch (per-device batch resizes), so rows fold as

    scale-down (n -> n'):  row'_j = (n'/n) * sum_{i : i mod n' == j} row_i
    scale-up   (n -> n'):  row'_j = (n'/n) * row_j   (j < n, else 0)

which keeps ``psum(rows') / n'`` identical to ``psum(rows) / n`` — the
in-flight delayed gradients survive the migration bit-for-bit in their
update semantics.  The repack itself only remaps the trailing (element)
axis; the fold is the one device-axis operation, done eagerly before the
transfer.

What a real deployment adds: this in-process harness migrates live
buffers — the "dead" devices still answer reads.  On real hardware a
dead shard's ZeRO spans are gone; production pairs this control flow
with the emergency-checkpoint path (or redundant sharding) to re-source
lost spans.  The control-plane logic — detection, pricing, gating,
cycle-boundary repack — is exactly what this module tests.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpoint import (
    save as save_ckpt,
    save_layout_descriptor,
    schedule_digest,
)
from repro.elastic.controller import ElasticController, ElasticPlan
from repro.elastic.health import FaultEvent, HealthMonitor
from repro.launch.mesh import make_elastic_mesh
from repro.obs.trace import Tracer
from repro.train.bucketing import (
    build_bucket_layout,
    build_layout_transition,
)

P = jax.sharding.PartitionSpec


class ElasticHalt(RuntimeError):
    """Raised by :meth:`ElasticCoordinator.step` when the degradation
    ladder bottoms out (no survivors / preempted out): the emergency
    checkpoint is on disk and the driver should exit cleanly; a later
    ``--resume`` continues from it."""

    def __init__(self, step: int, checkpoint_path: str):
        self.step = step
        self.checkpoint_path = checkpoint_path
        super().__init__(
            f"elastic halt at step {step}"
            + (f" (checkpoint: {checkpoint_path})"
               if checkpoint_path else " (no checkpoint dir configured)")
        )


def fold_accum_rows(rows: jax.Array, n_new: int) -> jax.Array:
    """Fold a ``(n_old, size)`` accumulator stack to ``n_new`` device
    rows, preserving ``psum(rows)/n`` (the global-mean gradient the
    delayed update consumes) under a constant global batch."""
    n_old = int(rows.shape[0])
    if n_new == n_old:
        return rows
    scale = n_new / n_old
    if n_new < n_old:
        seg = jnp.arange(n_old) % n_new
        out = jax.ops.segment_sum(rows, seg, num_segments=n_new)
    else:
        pad = jnp.zeros((n_new - n_old,) + rows.shape[1:], rows.dtype)
        out = jnp.concatenate([rows, pad], axis=0)
    return out * scale


def migrate_state(old_rt, new_rt, state) -> Any:
    """Move a flat train state from ``old_rt``'s mesh/layout onto
    ``new_rt``'s: fold the accumulator device rows, materialize onto the
    new device set (the one unavoidable full-state transfer of a
    device-set change), then ``repack_state`` into the new layout with
    its committed shardings.  Consumes ``state``."""
    from jax.sharding import NamedSharding

    state = dict(state)
    # the gather cache is layout- and mesh-bound and derived; drop it —
    # the post-migration cycle starts at position 0, which re-gathers
    state.pop("pgather", None)
    n_old, n_new = old_rt.accum_devices, new_rt.accum_devices
    if n_old != n_new:
        # the state still lives on the old mesh, whatever mesh the caller
        # has set around this call
        with jax.set_mesh(old_rt.mesh):
            state["cur"] = tuple(fold_accum_rows(b, n_new)
                                 for b in state["cur"])
            state["fut"] = tuple(fold_accum_rows(b, n_new)
                                 for b in state["fut"])
    state = jax.device_put(state, NamedSharding(new_rt.mesh, P()))
    tr = build_layout_transition(old_rt.layout, new_rt.layout)
    with jax.set_mesh(new_rt.mesh):
        return new_rt.repack_state(state, tr)


class ElasticCoordinator:
    """Fault-tolerant wrapper around a flat-state :class:`DeftRuntime`.

    The driver loop calls :meth:`step` in place of ``runtime.step`` and
    :meth:`observe` with per-origin-shard walls each step; everything
    else — detection, planning, cycle-boundary migration, the
    degradation ladder — happens inside.  ``self.runtime`` is always the
    currently dispatching runtime.
    """

    def __init__(
        self,
        runtime,
        controller: ElasticController,
        monitor: HealthMonitor,
        *,
        params_abs,
        batch_spec=None,
        checkpoint_dir: str = "",
        mesh_for: Optional[Callable] = None,
        compile_on_migrate: bool = True,
        tracer: Optional[Tracer] = None,
    ):
        if not runtime.flat_state:
            raise ValueError(
                "elastic execution needs a flat-state runtime — the "
                "migration path repacks flat buffers (DESIGN.md §10)"
            )
        mesh = runtime.mesh
        if "pod" in mesh.axis_names:
            raise ValueError(
                "elastic execution supports (data, model) meshes; fold "
                "the pod axis into data before wrapping"
            )
        self.runtime = runtime
        self.controller = controller
        self.monitor = monitor
        self.params_abs = params_abs
        self.batch_spec = batch_spec
        self.checkpoint_dir = checkpoint_dir
        self._mesh_for = mesh_for or make_elastic_mesh
        self.compile_on_migrate = compile_on_migrate
        # origin shard id -> that data row's devices (model columns)
        devs = mesh.devices
        self._rows: Tuple[Tuple[Any, ...], ...] = tuple(
            tuple(devs[i, :]) for i in range(devs.shape[0])
        )
        self.n_origin = len(self._rows)
        # origin ids currently IN the mesh, mesh order, and the spare
        # pool capacity returns draw from.  An armed (not yet executed)
        # plan's membership is always `(members | returning) - spares`:
        # faulted members sit in BOTH `members` and `spares` until the
        # plan executes; capacity returnees sit in `returning` until
        # they land in `members`.  Every mutation re-arms the plan from
        # that one invariant, so cascading faults, straggler recoveries
        # and capacity returns compose instead of clobbering each other.
        self.members: List[int] = list(range(self.n_origin))
        self.spares: List[int] = []
        self._returning: List[int] = []
        # origin id -> the fault kind that planned it out (cleared when
        # the shard is restored or drawn back from the spare pool)
        self._out_reason: Dict[int, str] = {}
        self._pending: Optional[ElasticPlan] = None
        self._pending_members: List[int] = []
        self._halt: Optional[ElasticPlan] = None
        self.log: List[Dict[str, Any]] = []
        self.fault_events: List[FaultEvent] = []
        # detection -> arm -> migrate lifecycle mirrors into one trace
        # (DESIGN.md §11): default to the runtime's tracer so elastic
        # events land next to the step/phase spans they interrupt.
        # Compare against None, never truthiness — an empty Tracer has
        # __len__ == 0 and would be silently replaced by a private one
        if tracer is None:
            tracer = getattr(runtime, "tracer", None)
        self.tracer = tracer if tracer is not None else Tracer(capacity=1024)
        if self.monitor.tracer is None:
            self.monitor.tracer = self.tracer
        if monitor.n_shards != len(self.members):
            monitor.reset(len(self.members))

    # ---- observations ---------------------------------------------------
    def observe(
        self,
        step: int,
        walls: Sequence[Optional[float]],
        collectives: Optional[Sequence[Optional[float]]] = None,
        now: Optional[float] = None,
    ) -> List[FaultEvent]:
        """Feed one step's per-ORIGIN-shard observations (length
        ``n_origin``; entries for shards not currently in the mesh are
        ignored).  Returns the fault events raised, after any replanning
        they triggered."""
        if len(walls) != self.n_origin:
            raise ValueError(
                f"expected {self.n_origin} origin-shard observations, "
                f"got {len(walls)}"
            )
        cur_walls = [walls[o] for o in self.members]
        cur_colls = (
            [collectives[o] for o in self.members]
            if collectives is not None else None
        )
        events = self.monitor.observe(step, cur_walls, cur_colls, now=now)
        self._handle(step, events)
        return events

    def notice_preemption(
        self, step: int, shards: Sequence[int]
    ) -> List[FaultEvent]:
        """Explicit preemption notice for origin ``shards`` — no timeout
        wait; the scale-down (or halt) is planned immediately."""
        events = []
        for o in shards:
            if o not in self.members:
                continue
            ev = self.monitor.notice_preemption(
                step, self.members.index(o)
            )
            if ev is not None:
                events.append(ev)
        self._handle(step, events)
        return events

    def notice_capacity(self, step: int, shards: Sequence[int]) -> None:
        """Origin ``shards`` became available again.  A shard whose
        removal is still armed (in ``spares`` AND ``members``) is simply
        restored — its removal cancels; a shard already migrated out
        joins ``returning`` and the symmetric scale-up arms.  Either way
        the plan is re-armed from the membership invariant, MERGING with
        (never clobbering) any armed fault plan."""
        fresh = [o for o in shards if o in self.spares]
        if not fresh:
            return
        trigger = "scale-up"
        for o in fresh:
            self.spares.remove(o)
            self._out_reason.pop(o, None)
            if o not in self.members:
                self._returning.append(o)
        if not any(o in self._returning for o in fresh):
            # pure cancellation of armed removals: if removals for OTHER
            # shards remain armed, keep their fault trigger on the plan
            trigger = self._remaining_trigger() or trigger
        self._rearm(step, trigger)

    # ---- fault handling -------------------------------------------------
    def _handle(self, step: int, events: List[FaultEvent]) -> None:
        self.fault_events.extend(events)
        lost: List[Tuple[int, str]] = []
        restored = False
        for ev in events:
            if ev.kind in ("dead", "preemption", "straggler"):
                o = self.members[ev.shard]
                # a shard already planned out (armed earlier this cycle
                # window) must not be re-lost: it is in `spares`, and
                # counting it again would double-book the removal
                if o not in self.spares and all(o != p for p, _ in lost):
                    lost.append((o, ev.kind))
            # 'bandwidth' is informational here: uniform drift is the
            # adaptive replanner's job
            elif ev.kind == "recovered":
                o = self.members[ev.shard]
                # a straggler that recovers before its armed removal
                # executes is restored: out of the spare pool, removal
                # cancelled (dead/preempted shards never emit 'recovered')
                if o in self.spares and self._out_reason.get(o) == "straggler":
                    self.spares.remove(o)
                    self._out_reason.pop(o, None)
                    restored = True
        if not lost and not restored:
            return
        # shards planned out of the mesh move to the spare pool the
        # moment the plan arms — capacity returns can bring them back
        for o, kind in lost:
            self.spares.append(o)
            self._out_reason[o] = kind
        trigger = lost[-1][1] if lost else (self._remaining_trigger()
                                            or "scale-up")
        self._rearm(step, trigger)

    def _remaining_trigger(self) -> Optional[str]:
        """Fault kind of the latest still-armed removal, if any."""
        out = [o for o in self.members if o in self.spares]
        return self._out_reason.get(out[-1]) if out else None

    def _rearm(self, step: int, trigger: str) -> None:
        """Recompute the armed plan from the membership invariant
        ``(members | returning) - spares``; a target identical to the
        current membership disarms (nothing left to migrate)."""
        target = sorted(
            (set(self.members) | set(self._returning)) - set(self.spares)
        )
        if target == sorted(self.members):
            if self._pending is not None:
                self.tracer.instant(
                    "elastic", "disarm", step=step, trigger=trigger,
                )
            self._pending = None
            self._pending_members = []
            return
        plan = self.controller.propose(step, len(target), trigger)
        if plan.action == "checkpoint-halt":
            self._halt = plan
            self._pending = None
            self._pending_members = []
            self.tracer.instant(
                "elastic", "arm-checkpoint-halt", step=step,
                trigger=trigger, detected_step=plan.step,
            )
            return
        self._pending = plan
        self._pending_members = target
        self.tracer.instant(
            "elastic", f"arm-{plan.action}", step=step, trigger=trigger,
            detected_step=plan.step, new_shards=plan.n_shards,
            new_period=plan.schedule.period if plan.schedule else None,
        )

    # ---- migration ------------------------------------------------------
    def maybe_migrate(self, i: int, state):
        """Execute an armed plan if ``i`` is a cycle boundary (or halt
        immediately).  Returns the (possibly migrated) state; afterwards
        ``self.runtime`` dispatches it."""
        if self._halt is not None:
            self._do_halt(i, state)
        if self._pending is None:
            return state
        if self.runtime.phase_in_cycle(i) != 0:
            return state
        plan, self._pending = self._pending, None
        return self._execute(i, state, plan)

    def step(self, i: int, state, batch):
        """Drop-in for ``DeftRuntime.step`` with elastic handling."""
        state = self.maybe_migrate(i, state)
        return self.runtime.step(i, state, batch)

    def _do_halt(self, i: int, state) -> None:
        plan, self._halt = self._halt, None
        path = ""
        if self.checkpoint_dir:
            path = self.emergency_checkpoint(i, state)
        self.log.append({
            "step": i, "action": "checkpoint-halt",
            "detected_step": plan.step, "trigger": plan.trigger,
            "checkpoint": path,
        })
        self.tracer.instant(
            "elastic", "checkpoint-halt", step=i, trigger=plan.trigger,
            detected_step=plan.step, checkpoint=path,
        )
        raise ElasticHalt(i, path)

    def emergency_checkpoint(self, step: int, state) -> str:
        """Checkpoint NOW (tree form + layout/schedule sidecar), atomic
        — the clean-resume half of the unsurvivable-fault path."""
        rt = self.runtime
        path = save_ckpt(self.checkpoint_dir, step, rt.state_to_tree(state))
        save_layout_descriptor(
            self.checkpoint_dir, step, rt.layout,
            next_phase=rt.phase_in_cycle(step),
            digest=schedule_digest(rt.schedule),
        )
        return path

    def _execute(self, i: int, state, plan: ElasticPlan):
        t_mig = time.perf_counter()
        tr0 = self.tracer.now()
        old_rt = self.runtime
        members = sorted(self._pending_members)
        assert len(members) == plan.n_shards, (members, plan)
        assert len(set(members)) == len(members), members
        # a plan must never re-seat a shard still in the spare pool — a
        # cascading fault or capacity return that mutated the pool after
        # this plan armed would have re-armed it (see _rearm)
        assert set(members).isdisjoint(self.spares), (members, self.spares)
        rows = [self._rows[o] for o in members]
        new_mesh = self._mesh_for(rows)
        new_layout = build_bucket_layout(
            self.params_abs, plan.bucket_of, plan.n_buckets,
            shard_count=plan.n_shards if plan.sharded else 1,
        )
        old_pol = getattr(old_rt.layout, "precision", None)
        if old_pol is not None:
            # §13: the wire/master policy migrates with the state.  A
            # changed bucket count invalidates per-bucket wire choices,
            # so those reset to f32 (uniform policies survive); the
            # resident master dtype always carries — the migration must
            # not change the memory envelope mid-flight.
            from repro.core.precision import PrecisionPolicy

            if plan.n_buckets == old_rt.layout.n_buckets:
                new_layout = new_layout.with_precision(old_pol)
            else:
                wires = set(old_pol.wire)
                uni = wires.pop() if len(wires) == 1 else "f32"
                new_layout = new_layout.with_precision(
                    PrecisionPolicy.uniform(plan.n_buckets, uni,
                                            old_pol.master)
                )
        new_rt = old_rt.spawn(
            mesh=new_mesh, schedule=plan.schedule, layout=new_layout,
            fsdp=plan.sharded,
        )
        t0 = time.perf_counter()
        state = migrate_state(old_rt, new_rt, state)
        jax.block_until_ready(jax.tree_util.tree_leaves(state))
        repack_s = time.perf_counter() - t0
        compile_s = None
        if self.compile_on_migrate and self.batch_spec is not None:
            t0 = time.perf_counter()
            new_rt.compile(state, self.batch_spec)
            compile_s = time.perf_counter() - t0
        new_rt.reset_cycle(i)
        self.log.append({
            "step": i, "action": plan.action, "trigger": plan.trigger,
            "detected_step": plan.step,
            "old_shards": len(self.members), "new_shards": plan.n_shards,
            "old_period": old_rt.period, "new_period": new_rt.period,
            "sharded": plan.sharded,
            "preserver_ok": bool(plan.verdict and plan.verdict.ok),
            "preserver_ratio": plan.verdict.ratio if plan.verdict else None,
            "n_buckets": (old_rt.layout.n_buckets, new_layout.n_buckets),
            "repack_s": repack_s, "compile_s": compile_s,
            "migrate_s": time.perf_counter() - t_mig,
            "members": tuple(members),
        })
        self.tracer.add(
            "elastic", f"migrate-{plan.action}", tr0, self.tracer.now(),
            step=i, trigger=plan.trigger, detected_step=plan.step,
            old_shards=len(self.members), new_shards=plan.n_shards,
            old_period=old_rt.period, new_period=new_rt.period,
            repack_s=repack_s, compile_s=compile_s,
        )
        self.members = members
        self._returning = [o for o in self._returning if o not in members]
        self._pending_members = []
        self.runtime = new_rt
        self.monitor.reset(len(members))
        self.controller.adopt(plan)
        return state

    # ---- reporting ------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        return {
            "n_origin": self.n_origin,
            "members": tuple(self.members),
            "spares": tuple(self.spares),
            "returning": tuple(self._returning),
            "migrations": list(self.log),
            "fault_events": [
                dataclasses.asdict(e) for e in self.fault_events
            ],
            "pending": self._pending is not None,
        }
