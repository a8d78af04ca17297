"""The control, the reference computed in float8, fails the cells'
limits at test widths, as it does at the cells' own sizes on the chip
(``bench/control.py``)."""
import jax
import pytest

from conftest import tiny_cell, tiny_layernorm_cell

from bench import control


@pytest.mark.parametrize("make", [
    lambda: tiny_cell("qwen3-4b-l1.1chip.s4096"), tiny_layernorm_cell],
    ids=["qwen3-4b-l1.1chip.s4096", "layernorm_gelu_window"])
def test_fp8_control_is_not_correct(make):
    cell = make()
    recs = control.readings(cell, [2147483659, 3000000019, 7], ["fp8"],
                            jax.devices())
    assert len(recs) == 3
    for r in recs:
        over = [k for k in ("loss_gap", "grad_gap", "grad_diff", "update_gap")
                if r[k] > cell.limits[k]]
        assert over and r["correct"] is False, r
