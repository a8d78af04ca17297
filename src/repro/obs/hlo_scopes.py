"""Where the engine's named scopes land in a compiled phase (DESIGN.md §11).

``train/runtime.py`` names its work inside each phase with
``jax.named_scope``: ``deft_model`` (forward and backward), ``deft_grads``,
``deft_route`` (generation bookkeeping), ``deft_update``, ``deft_metrics``
and, per bucket, ``deft_sync.b<b>.<primary|secondary>.<cur|new>`` and
``deft_gather.b<b>.<primary|secondary>``.  A scope reaches every
instruction's ``op_name`` in the compiled HLO; the innermost ``deft_``
component of it names the instruction (:func:`innermost`).

XLA's collective passes drop that metadata on the ops they make: on a
TPU v5e a reduce-scatter becomes an all-reduce plus a slice, an
all-gather an all-reduce of a zero-padded buffer, and independent
all-reduces are combined into one over a tuple.  :func:`collective_scopes`
therefore names a collective by, in order: its own ``op_name``; for a
wrapper (an async start or done fusion), the collectives it calls; the
reduction computation it applies, which keeps the scope of the
collective it was made from; and the sync scopes its result reaches
first along its users.  A combined collective is named by all of its
tuple elements, each followed on its own.
"""
from __future__ import annotations

import re
from typing import Dict, FrozenSet, Iterable, List, Optional, Set, Tuple

COLLECTIVE_OPCODES = (
    "all-reduce", "reduce-scatter", "all-gather", "collective-permute",
    "all-to-all",
)
METRICS_SCOPE = "deft_metrics"
_SCOPE_RE = re.compile(r"(?:^|[/;])(deft_[A-Za-z0-9_.]+)")
_SYNC_RE = re.compile(
    r"^deft_(sync|gather)\.b(\d+)\.(primary|secondary)(?:\.(cur|new))?$")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = (.*)$")
_OPCODE_RE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")
_CALLS_RE = re.compile(r"\bcalls=(\{[^}]*\}|%[\w.\-]+)")
_APPLY_RE = re.compile(r"\bto_apply=%([\w.\-]+)")
_META_RE = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_INDEX_RE = re.compile(r"\bindex=(\d+)")
_REF_RE = re.compile(r"%([\w.\-]+)")


def innermost(op_name: str) -> Optional[str]:
    """The innermost ``deft_`` scope in an ``op_name`` (XLA joins the
    names of merged instructions with ';'), or None."""
    found = _SCOPE_RE.findall(op_name or "")
    return found[-1] if found else None


def parse_sync(scope: str) -> Optional[Tuple[str, int, str, Optional[str]]]:
    """(``'sync'`` or ``'gather'``, bucket, link, generation or None) of
    a per-bucket scope; None for any other scope."""
    m = _SYNC_RE.match(scope)
    if not m:
        return None
    return m.group(1), int(m.group(2)), m.group(3), m.group(4)


def is_collective(opcode: str) -> bool:
    return any(opcode == c or opcode.startswith(c + "-")
               for c in COLLECTIVE_OPCODES)


def _named(scope: Optional[str]) -> bool:
    return scope is not None and (
        scope == METRICS_SCOPE or parse_sync(scope) is not None)


class _Program:
    """Each instruction's opcode, operands, users, called and applied
    computations, ``op_name`` and tuple index, over HLO texts."""

    def __init__(self, texts: Iterable[str]):
        self.opcode: Dict[str, str] = {}
        self.operands: Dict[str, List[str]] = {}
        self.calls: Dict[str, List[str]] = {}
        self.apply: Dict[str, str] = {}
        self.op_name: Dict[str, str] = {}
        self.index: Dict[str, int] = {}
        self.body: Dict[str, List[str]] = {}
        self.users: Dict[str, List[str]] = {}
        for text in texts:
            comp: Optional[str] = None
            for line in text.splitlines():
                if line and not line[0].isspace() \
                        and line.rstrip().endswith("{") \
                        and not line.startswith("HloModule"):
                    words = line.split()
                    comp = (words[1] if words[0] == "ENTRY"
                            else words[0]).lstrip("%")
                    self.body[comp] = []
                    continue
                m = _INSTR_RE.match(line)
                if not m or comp is None:
                    continue
                name, rest = m.group(1), " " + m.group(2)
                o = _OPCODE_RE.search(rest)
                if not o:
                    continue
                self.opcode[name] = o.group(1)
                self.operands[name] = _operands(rest, o.end() - 1)
                c = _CALLS_RE.search(rest)
                self.calls[name] = _REF_RE.findall(c.group(1)) if c else []
                a = _APPLY_RE.search(rest)
                if a:
                    self.apply[name] = a.group(1)
                meta = _META_RE.search(rest)
                self.op_name[name] = meta.group(1) if meta else ""
                i = _INDEX_RE.search(rest)
                if o.group(1) == "get-tuple-element" and i:
                    self.index[name] = int(i.group(1))
                self.body[comp].append(name)
        for n, ops in self.operands.items():
            for x in ops:
                self.users.setdefault(x, []).append(n)

    def own(self, name: str) -> Optional[str]:
        return innermost(self.op_name.get(name, ""))

    def combined(self, name: str) -> bool:
        return is_collective(self.opcode.get(name, "")) \
            and len(self.operands[name]) > 1

    def called_collectives(self, name: str) -> List[str]:
        """The collectives inside ``name``'s called computations."""
        out: List[str] = []
        todo, seen = list(self.calls.get(name, ())), set()
        while todo:
            comp = todo.pop()
            if comp in seen:
                continue
            seen.add(comp)
            for x in self.body.get(comp, ()):
                if is_collective(self.opcode[x]):
                    out.append(x)
                todo.extend(self.calls.get(x, ()))
        return out

    def region(self, name: str) -> Set[str]:
        """Scopes inside ``name``'s reduction computation."""
        return {s for s in map(self.own, self.body.get(
            self.apply.get(name, ""), ())) if s}


def _operands(rest: str, i: int) -> List[str]:
    """Names inside the parentheses that open at ``rest[i]``."""
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


def collective_scopes(texts: Iterable[str]) -> Dict[str, FrozenSet[str]]:
    """Sync, gather and metrics scopes of every instruction that is a
    collective or calls one, over compiled HLO ``texts``; an empty set
    where nothing names it (see the module docstring for the order)."""
    p = _Program(texts)
    memo: Dict[str, FrozenSet[str]] = {}

    def direct(n: str) -> Set[str]:
        own = p.own(n)
        if _named(own):
            return {own}
        if not is_collective(p.opcode[n]):
            return set().union(*(direct(x)
                                 for x in p.called_collectives(n)))
        if p.combined(n):
            # its reduction names the first element only
            return set()
        return {s for s in p.region(n) if _named(s)}

    def reached(n: str) -> Set[str]:
        # the first named scopes along the users of n's result; another
        # engine scope ends a path unnamed
        out: Set[str] = set()
        todo: List[Tuple[str, str]] = [(u, n) for u in p.users.get(n, ())]
        seen: Set[str] = set()
        while todo:
            u, frm = todo.pop()
            if u in seen:
                continue
            seen.add(u)
            found = direct(u) if (is_collective(p.opcode[u])
                                  or p.calls.get(u)) else set()
            own = p.own(u)
            if _named(own):
                found.add(own)
            if found and not p.combined(u):
                out |= found
                continue
            if own is not None and not p.combined(u):
                continue
            users = p.users.get(u, ())
            if p.combined(u):
                k = p.operands[u].index(frm)
                users = [g for g in users if p.index.get(g) == k]
            todo.extend((g, u) for g in users)
        return out

    for n, op in p.opcode.items():
        if not (is_collective(op) or p.called_collectives(n)):
            continue
        found = direct(n)
        if not found or p.combined(n):
            found |= reached(n)
        memo[n] = frozenset(found)
    return memo
