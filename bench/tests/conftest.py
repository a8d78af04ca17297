import dataclasses
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = ROOT / "bench" / "tests" / "data"
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# widths a CPU test can hold; everything else is the cell's own file
TINY = dict(num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, intermediate_size=256,
            vocab_size=512)


def tiny_cell(workload: str, *, batch_per_chip: int = 1, seq: int = 64):
    """The cell ``workload`` at test widths, with its own limits."""
    from bench import run, spec

    man = spec.manifest()
    wl = spec.workload(man, workload)
    t = spec.traffic(wl["traffic"])
    t.update(seq=seq, pool=4, batch_per_chip=batch_per_chip, trace_steps=4)
    return run.Cell(wl["name"], _tiny(spec.config(wl["config"]), seq), t,
                    spec.limits(wl["name"]), wl["chips"])


def _tiny(c, seq):
    c.update(TINY)
    if c.get("sliding_window"):
        c["sliding_window"] = seq * 3 // 4
    return c


def tiny_layernorm_cell(*, batch_per_chip: int = 1, seq: int = 64):
    """The one-chip qwen3 cell with the StarCoder2 test configuration in
    its place (LayerNorm, a non-gated GELU MLP, a sliding window that
    masks keys), at test widths."""
    cell = tiny_cell("qwen3-4b-l1.1chip.s4096", batch_per_chip=batch_per_chip,
                     seq=seq)
    c = json.loads((DATA / "starcoder2-7b-l1.json").read_text())
    return dataclasses.replace(cell, name="starcoder2-7b-l1.test",
                               cfg=_tiny(c, seq))
