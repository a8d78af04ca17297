"""Compiled-artifact analysis: collective bytes, roofline terms.

``cost_analysis()`` gives HLO FLOPs and bytes; collective traffic is NOT in
cost_analysis, so we parse the compiled HLO text and sum the operand sizes
of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute instruction.

Hardware constants come from the peaks table ``core.profiler.DEVICE_PEAKS``
(the dry runs target TPU v5e).
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple

from repro.core.profiler import PLANNING_TARGET_KIND, peaks_for

_PEAKS = peaks_for(PLANNING_TARGET_KIND)
PEAK_FLOPS = _PEAKS.bf16_flops     # per chip
HBM_BW = _PEAKS.hbm_bw             # bytes/s per chip
ICI_BW = _PEAKS.ici_link_bw        # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind operand bytes summed over the module.

    Matches instruction lines of the form
      ``%x = TYPE[dims] all-reduce(TYPE[dims] %a, ...), ...``
    and sums the *operand* shapes (falling back to the result shape when
    operands are printed without types).  ``*-start`` variants (async
    collectives) are counted; their ``*-done`` halves are skipped to avoid
    double counting.
    """
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)$", s)
        if not m:
            continue
        rhs = m.group(1)
        kind = None
        for c in _COLLECTIVES:
            # result shape then opcode, e.g. "bf16[8,128]{1,0} all-reduce("
            if re.search(rf"\}}?\s{c}(-start)?\(", rhs) or re.search(
                rf"\]\s{c}(-start)?\(", rhs
            ):
                kind = c
                break
        if kind is None:
            continue
        if f"{kind}-done" in rhs:
            continue
        # operand shapes: shapes appearing inside the call parens
        paren = rhs.find("(")
        operand_text = rhs[paren:]
        shapes = _SHAPE_RE.findall(operand_text)
        total = sum(_shape_bytes(d, dims) for d, dims in shapes)
        if total == 0:
            # fall back to the result shape(s) before the opcode
            shapes = _SHAPE_RE.findall(rhs[:paren])
            total = sum(_shape_bytes(d, dims) for d, dims in shapes)
        out[kind] += total
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclasses.dataclass
class Roofline:
    """Per-step roofline terms (seconds) on the target hardware."""

    arch: str
    shape: str
    mesh: str                     # '16x16' | '2x16x16'
    mode: str                     # 'ddp' | 'deft' | 'prefill' | 'decode'
    n_chips: int
    hlo_flops: float              # whole-program FLOPs (per device program)
    hlo_bytes: float              # bytes accessed (per device program)
    coll_bytes: float             # collective operand bytes (per device)
    coll_breakdown: Dict[str, int]
    bytes_per_device: float       # peak memory from memory_analysis
    model_flops: float            # 6*N(active)*D useful training FLOPs
    links_per_chip: float = 2.0   # usable ICI links on a 2-D torus axis slice
    extra: Dict = dataclasses.field(default_factory=dict)

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / (ICI_BW * self.links_per_chip)

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        total = self.hlo_flops * self.n_chips
        return self.model_flops / total if total else 0.0

    def to_json(self) -> Dict:
        d = dataclasses.asdict(self)
        d.update(
            t_compute=self.t_compute,
            t_memory=self.t_memory,
            t_collective=self.t_collective,
            dominant=self.dominant,
            useful_flops_ratio=self.useful_flops_ratio,
        )
        return d


def analyse_compiled(
    compiled,
    *,
    arch: str,
    shape: str,
    mesh_desc: str,
    mode: str,
    n_chips: int,
    model_flops: float,
) -> Roofline:
    """Extract roofline terms from a compiled executable.

    cost_analysis flops/bytes on an SPMD executable are per-device program
    costs; collective bytes parsed from HLO are likewise per device.
    """
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    bytes_ = float(ca.get("bytes accessed", 0.0))
    try:
        ma = compiled.memory_analysis()
        bytes_dev = float(
            getattr(ma, "temp_size_in_bytes", 0)
            + getattr(ma, "argument_size_in_bytes", 0)
            + getattr(ma, "output_size_in_bytes", 0)
            - getattr(ma, "alias_size_in_bytes", 0)
        )
    except Exception:  # pragma: no cover - backend-dependent
        bytes_dev = 0.0
    coll = collective_bytes(compiled.as_text())
    return Roofline(
        arch=arch,
        shape=shape,
        mesh=mesh_desc,
        mode=mode,
        n_chips=n_chips,
        hlo_flops=flops,
        hlo_bytes=bytes_,
        coll_bytes=float(coll["total"]),
        coll_breakdown=coll,
        bytes_per_device=bytes_dev,
        model_flops=model_flops,
    )


def model_flops_for(cfg, shape) -> float:
    """Useful training FLOPs per step: 6*N_active*tokens (dense matmul
    term only — the classic MFU numerator); decode/prefill use 2*N*tokens
    (forward only)."""
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    n = cfg.active_params()
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n * tokens


def format_roofline_row(r: Roofline) -> str:
    return (
        f"| {r.arch} | {r.shape} | {r.mode} | {r.mesh} | "
        f"{r.t_compute*1e3:.2f} | {r.t_memory*1e3:.2f} | "
        f"{r.t_collective*1e3:.2f} | {r.dominant} | "
        f"{r.useful_flops_ratio:.2f} | {r.bytes_per_device/2**30:.2f} |"
    )
