"""The reduction from trace events to busy time, idle share, exposed
collective time and scope matches, on a synthetic trace."""
import pytest

from bench import trace_reduce as tr
from bench.metrics import (collective_exposed_ms, collective_ms,
                           device_idle_share, attn_bwd_ms)

# event names as a TPU trace writes them (from a v5e trace)
FUSION = ("%fusion.354 = bf16[8,4,4096,512]{2,3,1,0:T(8,128)(2,1)} "
          "fusion(f32[1,8,4,4096,512]{3,4,2,1,0:T(8,128)} %fusion.353), "
          "kind=kOutput, calls=%fused_computation.34.clone.clone")
WHILE = ("%while.20 = (s32[]{:T(128)}, bf16[151936,2560]{1,0:T(8,128)(2,1)}) "
         "while((s32[]{:T(128)}, bf16[151936,2560]{1,0:T(8,128)(2,1)}) "
         "%tuple.149), condition=%region_18, body=%region_14")
KERNEL = ("%flash_attention_fwd.6 = (bf16[1,32,4096,128]{3,2,1,0:T(8,128)"
          "(2,1)S(1)}, f32[1,32,1,4096]{3,2,1,0:T(1,128)}) custom-call("
          "bf16[1,32,4096,128]{3,2,1,0:T(8,128)(2,1)} %x), "
          "custom_call_target=\"tpu_custom_call\"")


def test_event_names_parse():
    assert tr.parse_event_name(FUSION) == ("fusion.354", "fusion")
    assert tr.parse_event_name(WHILE) == ("while.20", "while")
    assert tr.parse_event_name(KERNEL) == ("flash_attention_fwd.6",
                                           "custom-call")
    ar = "%all-reduce-start.3 = f32[1024]{0} all-reduce-start(f32[1024]{0} %p)"
    assert tr.parse_event_name(ar) == ("all-reduce-start.3",
                                       "all-reduce-start")
    assert tr.is_collective("all-reduce-start")
    assert tr.is_collective("collective-permute-done")
    assert not tr.is_collective("fusion")


def test_interval_arithmetic():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert tr.total(u) == 6
    assert tr.subtract([(0, 10)], [(2, 3), (5, 6)]) == [(0, 2), (3, 5),
                                                        (6, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.gaps([(2, 3), (5, 6)], 0, 8) == [(0, 2), (3, 5), (6, 8)]
    assert tr.clip([(0, 5), (6, 9)], 2, 7) == [(2, 5), (6, 7)]


def _op(name, opcode, s, e):
    return tr.Op(name, opcode, float(s), float(e))


@pytest.fixture
def two_chips():
    """Two chips over a 100 ns window of two steps.

    chip 0: a while (10-60) holding fusions 10-30 and 35-60; an
    all-reduce in flight 50-80, a fusion 70-75: exposed 60-70, 75-80.
    chip 1: compute 0-40; a collective-permute 40-90 with nothing else:
    all exposed.
    """
    ops = {
        0: [_op("while.1", "while", 10, 60), _op("fusion.1", "fusion", 10, 30),
            _op("fusion.2", "fusion", 35, 60), _op("fusion.3", "fusion", 70, 75),
            _op("all-reduce-done.1", "all-reduce-done", 78, 80)],
        1: [_op("fusion.1", "fusion", 0, 40),
            _op("collective-permute.1", "collective-permute", 40, 90)],
    }
    asyncs = {0: [_op("all-reduce-start.1", "all-reduce-start", 50, 80)]}
    host = [("bench.step", 0, 50), ("bench.dispatch", 0, 2),
            ("bench.step", 50, 100), ("bench.block", 52, 100)]
    return tr.Trace(ops, asyncs, host)


def test_busy_idle_and_exposed(two_chips):
    t = two_chips
    assert tr.window(t) == (0, 100)
    # the while holds the chip from 10 to 60, its loop control included
    assert tr.total(tr.busy(t, 0, 0, 100)) == 50 + 5 + 2
    assert tr.total(tr.busy(t, 1, 0, 100)) == 90
    ctx = {"trace": t, "lo": 0, "hi": 100, "devices": [0, 1], "steps": 2,
           "scopes": {"fusion.2": "jit(f)/flash_attention_bwd_blocked/dot"}}
    busy0 = 50 + 5 + 2            # 10-60, 70-75, 78-80
    idle = 1 - (busy0 + 90) / 2 / 100
    assert device_idle_share.read(ctx) == pytest.approx(100 * idle)
    # chip 0: 50-80 in flight; chip 1: 40-90
    assert collective_ms.read(ctx) == pytest.approx((30 + 50) / 2 / 2 * 1e-6)
    # chip 0: 60-70 and 75-80 exposed; chip 1: 40-90
    assert collective_exposed_ms.read(ctx) == pytest.approx(
        (15 + 50) / 2 / 2 * 1e-6)
    # one chip has ops under the scope: 25 ns over 2 steps
    assert attn_bwd_ms.read(ctx) == pytest.approx(25 / 2 * 1e-6)


def test_idle_gaps_named_by_host_span(two_chips):
    gaps = tr.idle_gaps_by_host(two_chips, 0, 100)
    # no chip busy in 90-100 only; the innermost covering span is block
    assert gaps[0][0] == "bench.block"
    assert gaps[0][1] == pytest.approx(10e-9)
    assert len(gaps) == 1


def test_no_collectives_reads_nothing(two_chips):
    t = tr.Trace({0: two_chips.ops[0][:4]}, {}, two_chips.host)
    ctx = {"trace": t, "lo": 0, "hi": 100, "devices": [0], "steps": 2,
           "scopes": {}}
    assert collective_ms.read(ctx) is None
    assert collective_exposed_ms.read(ctx) is None
    assert attn_bwd_ms.read(ctx) is None


def test_scopes_from_hlo():
    text = '''
  %fusion.354 = bf16[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%c, metadata={op_name="jit(step)/transpose(jvp(f))/flash_attention_bwd_blocked/mul" source_file="x.py" source_line=3}
  ROOT %tuple.1 = (f32[8]{0}) tuple(%fusion.354), metadata={op_name="jit(step)/out"}
  %copy.2 = f32[8]{0} copy(f32[8]{0} %p)
'''
    s = tr.scopes_from_hlo([text])
    assert s["fusion.354"].endswith("flash_attention_bwd_blocked/mul")
    assert s["tuple.1"] == "jit(step)/out"
    assert "copy.2" not in s


def test_kernel_rooflines_from_calls_and_shapes():
    from bench import flops, spec
    from bench.metrics import attn_fwd_roofline, bucket_update_roofline

    cfg, traffic = spec.config("qwen3-4b-l1"), spec.traffic("1chip.s4096")
    peak = spec.peaks()["TPU v5 lite"]
    cost = flops.attn_fwd_cost(cfg, 4096, 1)
    least = flops.least_time_s(cost["flops"], cost["bytes"], peak)
    upd = (flops.bucket_update_bytes(flops.total_params(cfg), False)
           / peak["hbm_bytes_per_s"])
    ns = 1e9
    ops = {0: [_op("flash_attention_fwd.6", "custom-call", 0, 4 * least * ns),
               _op("flash_attention_fwd.7", "custom-call", 5 * least * ns,
                   9 * least * ns),
               _op("bucket_update.9", "custom-call", 10 * least * ns,
                   10 * least * ns + 2 * upd * ns),
               # starts outside the window: not counted
               _op("flash_attention_fwd.6", "custom-call", 1e12, 1e12 + 1)]}
    t = tr.Trace(ops, {}, [])
    ctx = {"trace": t, "lo": 0, "hi": 1e11, "devices": [0], "steps": 1,
           "config": cfg, "traffic": traffic, "peak": peak,
           "update_steps": 1, "zeroing_update_steps": 0}
    # two calls, each four times its least time
    assert attn_fwd_roofline.read(ctx) == pytest.approx(25.0)
    assert bucket_update_roofline.read(ctx) == pytest.approx(50.0)


def test_hlo_ops_on_a_compiled_v5e_program():
    """An excerpt of the four-chip cell's phase as the TPU compiler
    writes it (compiled for a v5e:2x2): an all-gather in an async
    collective fusion, launched by ``async-collective-start``, carried
    by the compute fusion ``fusion.460`` and waited for by
    ``async-collective-done``; ``all-reduce.13`` runs alone."""
    from conftest import DATA

    h = tr.hlo_ops([(DATA / "v5e_2x2_async_collective.hlo.txt").read_text()])
    for name in ("async-collective-start", "async-collective-done",
                 "all-reduce.13"):
        assert name in h.comm, name
        assert h.opcode[name] in ("fusion", "all-reduce")
    assert h.carrier == {"fusion.460"}
    assert "convolution_add_fusion.4" not in h.comm | h.carrier
    assert h.start_of == {"async-collective-done": "async-collective-start"}

    # events as the trace names them, by the instruction's text: the async
    # all-gather in flight from 10 to 60 ns; compute in it runs 20-50 (its
    # carrier) and 52-55; the done waits 55-60; the all-reduce runs alone
    # 70-90 and a compute fusion 90-100
    lines = {tr.parse_event_name(ln.strip())[0]: ln.strip()
             for ln in (DATA / "v5e_2x2_async_collective.hlo.txt")
             .read_text().splitlines() if " = " in ln}
    # a compute fusion that the excerpt does not hold: read by its own text
    lines["convolution_add_fusion.4"] = FUSION.replace("fusion.354",
                                                       "convolution_add_fusion.4")
    dev = "/device:TPU:0"
    t = tr.from_events(
        [(dev, "XLA Ops", lines[n], s, e - s) for n, s, e in (
            ("convolution_add_fusion.4", 90, 100),
            ("async-collective-start", 10, 12), ("fusion.460", 20, 50),
            ("convolution_add_fusion.4", 52, 55),
            ("async-collective-done", 55, 60), ("all-reduce.13", 70, 90))]
        + [("/host:CPU", "python", "bench.step", 0, 100)])
    assert [o.name for o in t.ops[0]][:2] == ["async-collective-start",
                                              "fusion.460"]
    ctx = {"trace": t, "lo": 0, "hi": 100, "devices": [0], "steps": 1,
           "hlo": h}
    assert tr.collectives(t, 0, 0, 100, h) == [(10, 60), (70, 90)]
    assert collective_ms.read(ctx) == pytest.approx(70e-6)
    # exposed: 10-20 and 50-52 waiting for the start, the done 55-60,
    # the all-reduce
    assert collective_exposed_ms.read(ctx) == pytest.approx(37e-6)
    # by event names alone the fusions would read as compute
    assert collective_exposed_ms.read({**ctx, "hlo": None}) == \
        pytest.approx(20e-6)
