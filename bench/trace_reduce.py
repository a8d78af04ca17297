"""From a profiler trace (``.xplane.pb``) to device intervals.

A TPU trace has one plane per chip, ``/device:TPU:<n>``.  Its line
``XLA Ops`` holds one event per executed HLO instruction, named by the
instruction's text (``%fusion.12 = bf16[...] fusion(...), ...``); a
``while`` event spans its body's events, so times here are unions of
intervals, never plain sums over nested events.  The line ``Async XLA
Ops`` holds asynchronous work in flight.  The host plane ``/host:CPU``
holds the benchmark's ``jax.profiler.TraceAnnotation`` spans, on the
same clock.

An event's name does not say whether it moves data between chips: on a
TPU an asynchronous collective is compiled into fusions, a start and a
done that only launch and wait for it (``async-collective-start`` and
``async-collective-done``, each a fusion around the collective and a
custom call) and, between them, compute fusions that carry it while
they run.  :func:`hlo_ops` therefore classifies each instruction by
what the compiled program's text says its computations hold, and pairs
each op that ends a collective with the op that began it, so that the
collective counts as in flight from the one to the other.  The names of
the program's ``jax.named_scope`` regions are not in the trace either;
:func:`scopes_from_hlo` reads each instruction's ``op_name`` from the
same text.
"""
from __future__ import annotations

import dataclasses
import re
from typing import (Dict, FrozenSet, Iterable, List, Optional, Sequence,
                    Tuple)

Interval = Tuple[float, float]

_NAME_RE = re.compile(r"^%?([^\s=]+) = ")
_OPCODE_RE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")
_HLO_META_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([^\s=]+) = .*?metadata=\{[^}]*?op_name=\"([^\"]*)\"",
    re.M)
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = (.*)$")
_CALLS_RE = re.compile(r"\bcalls=(\{[^}]*\}|%[\w.\-]+)")
_REF_RE = re.compile(r"%([\w.\-]+)")
_DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")

COLLECTIVE_OPCODES = (
    "all-reduce", "reduce-scatter", "all-gather", "collective-permute",
    "all-to-all",
)
# ops that only contain other ops or wait for them
CONTAINER_OPCODES = ("while", "conditional", "call")
# ops that hold called computations, whose content decides their kind
WRAPPER_OPCODES = ("fusion", "call", "async-start", "async-update",
                   "async-done")
# ops inside a wrapper that neither compute nor communicate
PLUMBING_OPCODES = ("parameter", "constant", "custom-call", "tuple",
                    "get-tuple-element", "bitcast", "copy", "reshape")
# ops a collective's in-flight buffers pass through from start to done
PASS_OPCODES = ("get-tuple-element",)
# launches and waits of asynchronous copies and slices: no compute
WAIT_SUFFIXES = ("-start", "-update", "-done")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str          # instruction name, e.g. "fusion.12"
    opcode: str        # e.g. "fusion", "custom-call", "all-reduce-start"
    start: float       # ns
    end: float         # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Op]]           # device id -> XLA Ops
    async_ops: Dict[int, List[Op]]     # device id -> Async XLA Ops
    host: List[Tuple[str, float, float]]   # (name, start, end), bench.*


def parse_event_name(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an ``XLA Ops`` event name."""
    m = _NAME_RE.match(text)
    name = m.group(1) if m else text.split(" ")[0].lstrip("%")
    rest = text[m.end():] if m else text
    # the opcode is the first word directly followed by "(" after the
    # result shape; layout tags such as T(8,128) follow a ':' instead
    o = _OPCODE_RE.search(" " + rest)
    return name, (o.group(1) if o else "")


def is_collective(opcode: str) -> bool:
    return any(opcode == c or opcode.startswith(c + "-")
               for c in COLLECTIVE_OPCODES)


@dataclasses.dataclass(frozen=True)
class HloOps:
    """What the compiled programs say of each instruction, by name.

    ``comm``: ops that only move data between chips or launch or wait
    for such a move (a collective, or a wrapper whose computations hold
    a collective and no compute).  ``carrier``: wrappers that compute
    while a collective they hold is in flight.  ``start_of``: each comm
    op that ends a collective, mapped to the comm op that began it (the
    first comm op its operands reach through tuple elements and
    carriers).  Names are unique within one program; over several
    programs (phases) the last one read wins.
    """
    opcode: Dict[str, str]
    comm: FrozenSet[str]
    carrier: FrozenSet[str]
    start_of: Dict[str, str]

    @property
    def starts(self) -> FrozenSet[str]:
        return frozenset(self.start_of.values())


def _operands(rest: str, m: "re.Match") -> List[str]:
    """Names inside the parentheses that open at the end of ``m``."""
    i = m.end() - 1          # ``m`` matched " " + rest
    depth, j = 0, i
    while j < len(rest):
        if rest[j] == "(":
            depth += 1
        elif rest[j] == ")":
            depth -= 1
            if depth == 0:
                break
        j += 1
    return _REF_RE.findall(rest[i:j])


def hlo_ops(texts: Iterable[str]) -> HloOps:
    """Classify every instruction of the compiled HLO ``texts``."""
    opcode: Dict[str, str] = {}
    calls: Dict[str, List[str]] = {}
    operands: Dict[str, List[str]] = {}
    body: Dict[str, List[str]] = {}
    for text in texts:
        comp: Optional[str] = None
        for line in text.splitlines():
            if line and not line[0].isspace() and line.rstrip().endswith("{") \
                    and not line.startswith("HloModule"):
                words = line.split()
                comp = (words[1] if words[0] == "ENTRY" else words[0]) \
                    .lstrip("%")
                body[comp] = []
                continue
            m = _INSTR_RE.match(line)
            if not m or comp is None:
                continue
            name, rest = m.group(1), " " + m.group(2)
            o = _OPCODE_RE.search(rest)
            if not o:
                continue
            opcode[name] = o.group(1)
            operands[name] = _operands(rest, o)
            c = _CALLS_RE.search(rest)
            calls[name] = _REF_RE.findall(c.group(1)) if c else []
            body[comp].append(name)

    content: Dict[str, Tuple[bool, bool]] = {}

    def holds(comp: str) -> Tuple[bool, bool]:
        """(a collective, compute) somewhere inside computation ``comp``."""
        if comp not in content:
            content[comp] = (False, False)      # guards against cycles
            coll = comp_ = False
            for n in body.get(comp, ()):
                op = opcode[n]
                if is_collective(op):
                    coll = True
                elif op in WRAPPER_OPCODES and calls[n]:
                    for c in calls[n]:
                        a, b = holds(c)
                        coll, comp_ = coll or a, comp_ or b
                elif op not in PLUMBING_OPCODES:
                    comp_ = True
            content[comp] = (coll, comp_)
        return content[comp]

    comm, carrier = set(), set()
    for n, op in opcode.items():
        if is_collective(op):
            comm.add(n)
        elif op in WRAPPER_OPCODES and calls[n]:
            coll = comp_ = False
            for c in calls[n]:
                a, b = holds(c)
                coll, comp_ = coll or a, comp_ or b
            if coll:
                (carrier if comp_ else comm).add(n)
    start_of: Dict[str, str] = {}
    for n in comm:
        seen, todo = {n}, list(operands[n])
        while todo:
            x = todo.pop(0)
            if x in seen or x not in opcode:
                continue
            seen.add(x)
            if x in comm:
                start_of[n] = x
                break
            if opcode[x] in PASS_OPCODES or x in carrier:
                todo.extend(operands[x])
    return HloOps(opcode, frozenset(comm), frozenset(carrier), start_of)


def kind(op: "Op", hlo: Optional[HloOps] = None) -> str:
    """'comm', 'carrier', 'container', 'wait' or 'compute'; by the
    compiled program where it names the op, else by the event's own
    opcode."""
    code = op.opcode
    if hlo is not None and op.name in hlo.opcode:
        if op.name in hlo.comm:
            return "comm"
        if op.name in hlo.carrier:
            return "carrier"
        code = hlo.opcode[op.name]
    elif is_collective(code):
        return "comm"
    if code in CONTAINER_OPCODES:
        return "container"
    if code.endswith(WAIT_SUFFIXES) or code.startswith("async-"):
        return "wait"
    return "compute"


def events(path: str, host_prefix: str = "bench."
           ) -> Iterable[Tuple[str, str, str, float, float]]:
    """(plane, line, event name, start ns, duration ns) of the events
    this reduction reads from the ``.xplane.pb`` at ``path``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        if _DEVICE_RE.match(plane.name):
            for line in plane.lines:
                if line.name in ("XLA Ops", "Async XLA Ops"):
                    for e in line.events:
                        yield (plane.name, line.name, e.name, e.start_ns,
                               e.duration_ns)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(host_prefix):
                        yield (plane.name, line.name, e.name, e.start_ns,
                               e.duration_ns)


def from_events(evs: Iterable[Sequence]) -> Trace:
    """A :class:`Trace` from :func:`events` records."""
    ops: Dict[int, List[Op]] = {}
    async_ops: Dict[int, List[Op]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane, line, text, start, dur in evs:
        m = _DEVICE_RE.match(plane)
        if m:
            out = ops if line == "XLA Ops" else async_ops
            name, opcode = parse_event_name(text)
            out.setdefault(int(m.group(1)), []).append(
                Op(name, opcode, start, start + dur))
        else:
            host.append((text, start, start + dur))
    for d in ops:
        ops[d].sort(key=lambda o: o.start)
    host.sort(key=lambda h: h[1])
    return Trace(ops, async_ops, host)


def load(path: str, host_prefix: str = "bench.") -> Trace:
    return from_events(events(path, host_prefix))


def scopes_from_hlo(texts: Iterable[str]) -> Dict[str, str]:
    """instruction name -> metadata op_name, over compiled HLO texts."""
    out: Dict[str, str] = {}
    for t in texts:
        for m in _HLO_META_RE.finditer(t):
            out[m.group(1)] = m.group(2)
    return out


# ---- interval arithmetic -------------------------------------------------
def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(merged: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merged)


def clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            if cur >= e:
                break
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of ``merged`` inside [lo, hi)."""
    return subtract([(lo, hi)], clip(merged, lo, hi))


# ---- reductions ------------------------------------------------------------
def window(trace: Trace, span: str = "bench.step") -> Optional[Interval]:
    """From the first to the last host span named ``span``."""
    steps = [h for h in trace.host if h[0] == span]
    if not steps:
        return None
    return steps[0][1], steps[-1][2]


def busy(trace: Trace, dev: int, lo: float, hi: float) -> List[Interval]:
    return clip(union((o.start, o.end) for o in trace.ops.get(dev, ())),
                lo, hi)


def ops_matching(trace: Trace, dev: int, pred) -> List[Op]:
    return [o for o in trace.ops.get(dev, ()) if pred(o)]


def kernel_calls(trace: Trace, dev: int, kernel: str, lo: float, hi: float
                 ) -> Tuple[int, float]:
    """Calls of the Pallas kernel ``kernel`` (its instructions are named
    ``<kernel>.<n>``) that start in [lo, hi), and their device seconds."""
    ops = [o for o in trace.ops.get(dev, ())
           if o.name.split(".")[0] == kernel and lo <= o.start < hi]
    return len(ops), total(union((o.start, o.end) for o in ops)) * 1e-9


def collectives(trace: Trace, dev: int, lo: float, hi: float,
                hlo: Optional[HloOps] = None) -> List[Interval]:
    """Device time in which a collective runs or is in flight: comm ops,
    carriers, the span from each start to its done, and collectives on
    the async line."""
    ivs: List[Interval] = []
    pending: Dict[str, List[float]] = {}
    starts = hlo.starts if hlo is not None else frozenset()
    for o in trace.ops.get(dev, ()):
        if kind(o, hlo) not in ("comm", "carrier"):
            continue
        ivs.append((o.start, o.end))
        s = hlo.start_of.get(o.name) if hlo is not None else None
        if s is not None and pending.get(s):
            ivs.append((pending[s].pop(0), o.end))
        elif o.name in starts:
            pending.setdefault(o.name, []).append(o.start)
    ivs += [(o.start, o.end) for o in trace.async_ops.get(dev, ())
            if kind(o, hlo) in ("comm", "carrier")]
    return clip(union(ivs), lo, hi)


def compute(trace: Trace, dev: int, lo: float, hi: float,
            hlo: Optional[HloOps] = None) -> List[Interval]:
    """Device time of ops that compute: neither comm ops nor containers
    of other ops nor launches and waits; carriers compute."""
    ivs = [(o.start, o.end) for o in trace.ops.get(dev, ())
           if kind(o, hlo) in ("compute", "carrier")]
    return clip(union(ivs), lo, hi)


def self_times(trace: Trace, dev: int, lo: float, hi: float
               ) -> Dict[str, float]:
    """ns per instruction name, containers left out, in [lo, hi)."""
    out: Dict[str, float] = {}
    for o in trace.ops.get(dev, ()):
        if o.opcode in CONTAINER_OPCODES:
            continue
        d = min(o.end, hi) - max(o.start, lo)
        if d > 0:
            out[o.name] = out.get(o.name, 0.0) + d
    return out


def idle_gaps_by_host(trace: Trace, lo: float, hi: float,
                      outer: str = "bench.step") -> List[Tuple[str, float]]:
    """Every gap in which no device runs an op, longest first, named by
    the host spans inside ``outer`` that overlap it, in order and joined
    by '+' (``outer`` itself where none does, else 'outside')."""
    devs = sorted(trace.ops)
    busy_all = union(iv for d in devs for iv in busy(trace, d, lo, hi))
    out = []
    for s, e in gaps(busy_all, lo, hi):
        names: List[str] = []
        in_outer = False
        for name, hs, he in trace.host:
            if min(e, he) <= max(s, hs):
                continue
            if name == outer:
                in_outer = True
            elif name not in names:
                names.append(name)
        label = "+".join(names) or (outer if in_outer else "outside")
        out.append((label, (e - s) * 1e-9))
    out.sort(key=lambda x: -x[1])
    return out
