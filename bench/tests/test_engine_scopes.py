"""The engine's named work in a trace (bench/engine_scopes.py): the
device time of its own ops, the exposed sync split by link, one row per
bucket, the host spans, and bench/engine_report.py end to end on the
CPU at test widths."""
import pytest

from bench import engine_scopes as es
from bench import trace_reduce as tr
from bench.metrics import collective_exposed_ms, engine_device_ms

ROUTE = "jit(f)/shard_map/deft_route/add"
GRADS = "jit(f)/shard_map/deft_grads/concatenate"
UPDATE = "jit(f)/shard_map/deft_update/reduce_sum"
MODEL = "jit(f)/shard_map/deft_model/transpose(jvp())/dot_general"


def _op(name, opcode, s, e):
    return tr.Op(name, opcode, float(s), float(e))


def test_engine_device_ms_reads_the_engines_own_ops():
    """Route and grads ops overlap (a union is taken); the update's norm
    counts, its kernel and its collective do not; the model does not."""
    ops = {
        0: [_op("fusion.1", "fusion", 0, 10), _op("fusion.2", "fusion", 5, 12),
            _op("bucket_update.3", "custom-call", 20, 40),
            _op("multiply_reduce_fusion", "fusion", 40, 44),
            _op("psum.1", "all-reduce", 50, 55),
            _op("fusion.9", "fusion", 60, 90)],
        1: [_op("fusion.1", "fusion", 0, 8),
            _op("multiply_reduce_fusion", "fusion", 30, 34)],
    }
    scopes = {"fusion.1": ROUTE, "fusion.2": GRADS, "bucket_update.3": UPDATE,
              "multiply_reduce_fusion": UPDATE, "psum.1": UPDATE,
              "fusion.9": MODEL}
    ctx = {"trace": tr.Trace(ops, {}, []), "lo": 0, "hi": 100,
           "devices": [0, 1], "steps": 2, "scopes": scopes}
    # chip 0: 0-12 and 40-44; chip 1: 0-8 and 30-34
    assert engine_device_ms.read(ctx) == pytest.approx((16 + 12) / 2 / 2
                                                       * 1e-6)
    # an engine that names no scope reads nothing
    ctx["scopes"] = {n: "jit(f)/add" for n in scopes}
    assert engine_device_ms.read(ctx) is None


@pytest.fixture
def sync_window():
    """One chip, one step, 0-100 ns: an async pair named on its start
    only (in flight 10-60, compute 20-50 beside it), one named on its
    done only (62-68, alone), a primary psum (70-80), a combined
    all-reduce of a secondary bucket and the metrics (80-85), an
    all-reduce nothing names (85-88) and the metrics psum alone
    (90-92)."""
    names = {
        "async-collective-start": ("fusion", 10, 12),
        "fusion.5": ("fusion", 20, 50),
        "async-collective-done": ("fusion", 55, 60),
        "async-collective-start.1": ("fusion", 62, 63),
        "async-collective-done.1": ("fusion", 66, 68),
        "psum.1": ("all-reduce", 70, 80),
        "all-reduce.14": ("all-reduce", 80, 85),
        "all-reduce.7": ("all-reduce", 85, 88),
        "psum.9": ("all-reduce", 90, 92),
    }
    ops = {0: [_op(n, c, s, e) for n, (c, s, e) in names.items()]}
    comm = frozenset(n for n in names if n != "fusion.5")
    hlo = tr.HloOps({n: c for n, (c, _, _) in names.items()}, comm,
                    frozenset(),
                    {"async-collective-done": "async-collective-start",
                     "async-collective-done.1": "async-collective-start.1"})
    ctx = {"trace": tr.Trace(ops, {}, []), "lo": 0, "hi": 100,
           "devices": [0], "steps": 1, "scopes": {}, "hlo": hlo}
    program = {
        "async-collective-start": frozenset({"deft_sync.b3.secondary.new"}),
        "async-collective-done.1": frozenset({"deft_sync.b0.secondary.cur"}),
        "psum.1": frozenset({"deft_sync.b1.primary.new"}),
        "all-reduce.14": frozenset({"deft_sync.b2.secondary.new",
                                    "deft_metrics"}),
        "all-reduce.7": frozenset(),
        "psum.9": frozenset({"deft_metrics"}),
    }
    return ctx, program


def test_exposed_sync_split_by_link(sync_window):
    ctx, program = sync_window
    ops = es.comm_scopes(ctx["hlo"], program)
    # a start or done the program leaves unnamed takes its pair's scope
    assert ops["async-collective-done"] == {"deft_sync.b3.secondary.new"}
    assert ops["async-collective-start.1"] == {"deft_sync.b0.secondary.cur"}
    assert es.group(ops["all-reduce.14"]) == "secondary"
    assert es.group(ops["all-reduce.7"]) == "unscoped"
    assert es.group(frozenset({"deft_sync.b1.primary.new",
                               "deft_sync.b2.secondary.new"})) == "shared"
    parts = es.exposed_by_group(ctx, program)
    # secondary: 10-20 and 50-60 of the first pair, 62-68, 80-85
    assert parts == pytest.approx({"secondary": 31e-6, "primary": 10e-6,
                                   "metrics": 2e-6, "unscoped": 3e-6})
    # the parts add up to what collective_exposed_ms reads
    assert sum(parts.values()) == pytest.approx(
        collective_exposed_ms.read(ctx))


def test_one_row_per_bucket_or_combined_set(sync_window):
    ctx, program = sync_window
    rows = es.by_scopes(ctx, program)
    assert [sorted(k) for k, _, _ in rows] == [
        ["deft_sync.b0.secondary.cur"], ["deft_sync.b1.primary.new"],
        ["deft_metrics", "deft_sync.b2.secondary.new"],
        ["deft_sync.b3.secondary.new"], [], ["deft_metrics"]]
    b3 = rows[3]
    assert b3[1:] == pytest.approx((50e-6, 20e-6))     # in flight, exposed


def test_host_spans_in_the_window():
    t = tr.Trace({}, {}, [("deft.phase", 0, 2e6), ("deft.place", 1e5, 5e5),
                          ("deft.phase", 3e6, 4.5e6),
                          ("deft.phase", 9e6, 9.5e6)])
    assert es.host_ms(t, "deft.phase", 0, 5e6) == pytest.approx([2.0, 1.5])
    assert es.host_ms(t, "deft.place", 0, 5e6) == pytest.approx([0.4])


def test_engine_report_on_the_cpu(monkeypatch):
    """The report's host side end to end at test widths: the engine's
    spans on the profiler's host plane, the step parts and the idle gaps
    named by them (the CPU trace has no TPU planes, so no device side)."""
    import jax

    from conftest import tiny_cell

    from bench import engine_report

    monkeypatch.setenv("REPRO_BUCKET_UPDATE", "interpret")
    cell = tiny_cell("qwen3-4b-l1.1chip.s4096")
    out = engine_report.report(cell, 2147483711, 1.0, jax.devices())
    assert out["untraced"]["steps"] > 0
    un = out["untraced"]
    assert un["dispatch_ms"]["median"] < un["wall_ms"]["median"]
    traced = out["traced"]
    assert traced["steps"] == cell.traffic["trace_steps"]
    host = traced["host_span_ms"]
    assert set(host) == {"deft.phase", "deft.place", "deft.launch"}
    assert all(v > 0 for v in host.values())
    assert traced["engine_host_ms"] == host["deft.phase"]
    assert any("deft.launch" in label for label, _ in out["idle_gaps"])
