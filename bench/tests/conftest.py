import dataclasses
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = ROOT / "bench" / "tests" / "data"
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_cell(workload: str, *, batch_per_chip: int = 1, seq: int = 64):
    """The cell ``workload`` at test widths, with its own limits."""
    from bench import run, spec

    man = spec.manifest()
    wl = spec.workload(man, workload)
    t = spec.traffic(wl["traffic"])
    t.update(seq=seq, pool=4, batch_per_chip=batch_per_chip, trace_steps=4)
    return run.Cell(wl["name"], tiny(spec.config(wl["config"]), seq), t,
                    spec.limits(wl["name"]), wl["chips"])


def tiny(c, seq):
    """Configuration ``c`` at widths a CPU test can hold, by its
    architecture module; everything else is the file's own."""
    from bench import spec

    return spec.arch(c).tiny(c, seq)


def tiny_layernorm_cell(*, batch_per_chip: int = 1, seq: int = 64):
    """The one-chip qwen3 cell with the StarCoder2 test configuration in
    its place (LayerNorm, a non-gated GELU MLP, a sliding window that
    masks keys), at test widths."""
    cell = tiny_cell("qwen3-4b-l1.1chip.s4096", batch_per_chip=batch_per_chip,
                     seq=seq)
    c = json.loads((DATA / "starcoder2-7b-l1.json").read_text())
    return dataclasses.replace(cell, name="starcoder2-7b-l1.test",
                               cfg=tiny(c, seq))
