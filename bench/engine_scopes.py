"""Device and host time of the DeFT engine's named work, from a trace.

``train/runtime.py`` names its work inside each phase with
``jax.named_scope`` (``deft_model``, ``deft_grads``, ``deft_route``,
``deft_update``, ``deft_metrics``, and per bucket
``deft_sync.b<b>.<primary|secondary>.<cur|new>`` and
``deft_gather.b<b>.<link>``) and on the host with the spans
``deft.phase`` ⊃ ``deft.place``, ``deft.launch``.  The innermost
``deft_`` component of an instruction's ``op_name`` names it; the
program's ``repro.obs.hlo_scopes.collective_scopes`` names each
collective, also where XLA rewrote or combined it and dropped the
metadata.  A program without the scopes gives nothing here.
"""
from __future__ import annotations

import re
from typing import Dict, FrozenSet, List, Mapping, Optional, Tuple

from bench import trace_reduce as tr

# the program's naming (repro.obs.hlo_scopes), read here without the
# program so that readers also run against an engine without scopes
_SCOPE_RE = re.compile(r"(?:^|[/;])(deft_[A-Za-z0-9_.]+)")
_SYNC_RE = re.compile(
    r"^deft_(?:sync|gather)\.b(\d+)\.(primary|secondary)(?:\.(cur|new))?$")
# the engine's own device work besides the model and the collectives
ENGINE_SCOPES = ("deft_grads", "deft_route", "deft_update")
METRICS = "deft_metrics"
GROUPS = ("primary", "secondary", "metrics", "shared", "unscoped")


def innermost(op_name: str) -> Optional[str]:
    """The innermost ``deft_`` scope of an ``op_name``, or None."""
    found = _SCOPE_RE.findall(op_name or "")
    return found[-1] if found else None


def group(scopes: FrozenSet[str]) -> str:
    """Which part of the sync a comm op named by ``scopes`` is: its one
    link ('primary' or 'secondary'), 'metrics' (the metrics psum alone;
    combined into a sync it follows the sync), 'shared' (buckets on both
    links in one combined op) or 'unscoped'."""
    links = {m.group(2) for m in map(_SYNC_RE.match, scopes) if m}
    if len(links) == 1:
        return links.pop()
    if links:
        return "shared"
    return "metrics" if METRICS in scopes else "unscoped"


def comm_scopes(hlo: tr.HloOps, scopes: Mapping[str, FrozenSet[str]]
                ) -> Dict[str, FrozenSet[str]]:
    """The scopes of each comm op of ``hlo`` (bench/trace_reduce.py's
    classification) from the program's ``collective_scopes`` of the same
    phases; a start or done the program does not name takes its pair's."""
    pair: Dict[str, str] = {}
    for done, start in hlo.start_of.items():
        pair[done], pair[start] = start, done
    out = {}
    for n in hlo.comm:
        found = scopes.get(n) or scopes.get(pair.get(n, ""), frozenset())
        out[n] = frozenset(found)
    return out


def in_flight(trace: tr.Trace, dev: int, lo: float, hi: float,
              hlo: tr.HloOps, names: FrozenSet[str]) -> List[tr.Interval]:
    """Device time in which a comm op in ``names`` runs or is in flight
    from its start to its done: ``trace_reduce.collectives`` limited to
    those ops, carriers left out (they compute)."""
    ivs: List[tr.Interval] = []
    pending: Dict[str, List[float]] = {}
    for o in trace.ops.get(dev, ()):
        if o.name not in names or tr.kind(o, hlo) != "comm":
            continue
        ivs.append((o.start, o.end))
        s = hlo.start_of.get(o.name)
        if s is not None and pending.get(s):
            ivs.append((pending[s].pop(0), o.end))
        elif o.name in hlo.starts:
            pending.setdefault(o.name, []).append(o.start)
    ivs += [(o.start, o.end) for o in trace.async_ops.get(dev, ())
            if o.name in names and tr.kind(o, hlo) == "comm"]
    return tr.clip(tr.union(ivs), lo, hi)


def per_step_ms(ctx, names: FrozenSet[str]) -> Tuple[float, float]:
    """(in flight, exposed) ms per step of the comm ops in ``names``,
    averaged over the chips of the traced window ``ctx`` (as
    bench/run.py builds it); exposed is the part with no compute op
    running on that chip, as ``collective_exposed_ms`` reads it."""
    t, lo, hi, hlo = ctx["trace"], ctx["lo"], ctx["hi"], ctx["hlo"]
    fl = ex = 0.0
    for d in ctx["devices"]:
        ivs = in_flight(t, d, lo, hi, hlo, names)
        fl += tr.total(ivs)
        ex += tr.total(tr.subtract(ivs, tr.compute(t, d, lo, hi, hlo)))
    n = len(ctx["devices"]) * ctx["steps"]
    return fl * 1e-6 / n, ex * 1e-6 / n


def exposed_by_group(ctx, scopes: Mapping[str, FrozenSet[str]]
                     ) -> Dict[str, float]:
    """Exposed comm ms per step of each :func:`group` present."""
    ops = comm_scopes(ctx["hlo"], scopes)
    out: Dict[str, float] = {}
    for g in GROUPS:
        names = frozenset(n for n, s in ops.items() if group(s) == g)
        if names:
            out[g] = per_step_ms(ctx, names)[1]
    return out


def by_scopes(ctx, scopes: Mapping[str, FrozenSet[str]]
              ) -> List[Tuple[FrozenSet[str], float, float]]:
    """(scopes, in flight ms, exposed ms) per step for each set of scopes
    that names comm ops, in bucket order: one row per bucket's sync,
    one per set of buckets XLA combined into one collective."""
    ops = comm_scopes(ctx["hlo"], scopes)

    def order(key):
        nums = [int(m.group(1)) for m in map(_SYNC_RE.match, key) if m]
        return (min(nums) if nums else 1 << 30, sorted(key))

    out = []
    for key in sorted(set(ops.values()), key=order):
        names = frozenset(n for n, s in ops.items() if s == key)
        out.append((key,) + per_step_ms(ctx, names))
    return out


def engine_ms(ctx) -> Optional[float]:
    """Device ms per step of ops whose innermost scope is one of
    :data:`ENGINE_SCOPES`, neither collectives nor the ``bucket_update``
    kernel's instructions; union over the ops, averaged over the chips.
    None when no such op is named."""
    t, lo, hi, hlo = ctx["trace"], ctx["lo"], ctx["hi"], ctx.get("hlo")
    names = {n for n, op in ctx["scopes"].items()
             if innermost(op) in ENGINE_SCOPES
             and n.split(".")[0] != "bucket_update"}
    if not names or not ctx["devices"]:
        return None
    per_dev = []
    for d in ctx["devices"]:
        ivs = [(o.start, o.end) for o in t.ops.get(d, ())
               if o.name in names
               and tr.kind(o, hlo) not in ("comm", "container")]
        per_dev.append(tr.total(tr.clip(tr.union(ivs), lo, hi)))
    return sum(per_dev) / len(per_dev) * 1e-6 / ctx["steps"]


def host_ms(trace: tr.Trace, span: str, lo: float, hi: float
            ) -> List[float]:
    """Durations in ms of the host spans named ``span`` that start in
    [lo, hi), in order."""
    return [(e - s) * 1e-6 for name, s, e in trace.host
            if name == span and lo <= s < hi]
