"""``lm_head_ms`` on a synthetic trace: the union of the ops whose
``op_name`` carries the ``lm_head`` scope, per step, averaged over the
chips; None where no op carries it."""
import pytest

from bench import trace_reduce as tr
from bench.metrics import lm_head_ms

# op_names as the compiled phase gives them (a v5e compile of the
# one-chip cell): the forward scan, the backward's vocabulary loop and
# the assembly of the head's gradient
SCOPES = {
    "while.107": "jit(f)/deft_model/jvp(lm_head)/while",
    "fusion.440": "jit(f)/deft_model/jvp(lm_head)/while/body/closed_call/"
                  "dot_general",
    "while.111": "jit(f)/deft_model/transpose(deft_model)/jvp(lm_head)/while",
    "fusion.475": "jit(f)/deft_model/transpose(deft_model)/jvp(lm_head)/"
                  "while/body/closed_call/dot_general",
    "pad_maximum_fusion": "jit(f)/deft_model/transpose(deft_model)/"
                          "jvp(lm_head)/concatenate",
    "fusion.2": "jit(f)/deft_model/transpose(jvp())/scatter-add",
    "fusion.9": "jit(f)/deft_model/transpose(jvp())/"
                "flash_attention_bwd_blocked/dot_general",
}


def _op(name, s, e):
    return tr.Op(name, name.split(".")[0], float(s), float(e))


def _ctx(ops, scopes):
    return {"trace": tr.Trace(ops, {}, []), "lo": 0, "hi": 100,
            "devices": sorted(ops), "steps": 2, "scopes": scopes}


def test_reads_the_scope_over_chips_and_steps():
    ops = {
        # forward while 0-20 holding its dot; backward while 30-60 with
        # its dot inside; the assembly 60-65; unscoped work beside them
        0: [_op("while.107", 0, 20), _op("fusion.440", 2, 18),
            _op("while.111", 30, 60), _op("fusion.475", 31, 40),
            _op("pad_maximum_fusion", 60, 65), _op("fusion.2", 65, 70),
            _op("fusion.9", 70, 90)],
        # the other chip: the backward loop runs past the window's end
        1: [_op("while.111", 80, 110), _op("fusion.2", 0, 40)],
    }
    chip0 = 20 + 30 + 5
    chip1 = 20
    assert lm_head_ms.read(_ctx(ops, SCOPES)) == pytest.approx(
        (chip0 + chip1) / 2 / 2 * 1e-6)


def test_none_without_the_scope():
    ops = {0: [_op("fusion.2", 0, 40), _op("fusion.9", 40, 60),
               _op("while.107", 60, 80)]}
    # the parent's names: no op carries lm_head
    parent = {k: v.replace("jvp(lm_head)", "jvp()")
              for k, v in SCOPES.items()}
    assert lm_head_ms.read(_ctx(ops, parent)) is None
    assert lm_head_ms.read(_ctx(ops, {})) is None
