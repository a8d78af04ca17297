"""Jaxpr census shared by the structural tests: walk every equation of a
jaxpr, nested sub-jaxprs (shard_map, pjit, scan, cond bodies) included,
and count equations or primitives."""
from __future__ import annotations

from typing import Iterator

from jax.extend.core import ClosedJaxpr, Jaxpr


def subjaxprs(param) -> Iterator[Jaxpr]:
    """Sub-jaxprs held by one equation parameter."""
    if isinstance(param, ClosedJaxpr):
        yield param.jaxpr
    elif isinstance(param, Jaxpr):
        yield param
    elif isinstance(param, (list, tuple)):
        for x in param:
            yield from subjaxprs(x)


def iter_eqns(jaxpr: Jaxpr):
    """Every equation of ``jaxpr``, depth first through sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in subjaxprs(p):
                yield from iter_eqns(sub)


def count_eqns(jaxpr: Jaxpr) -> int:
    return sum(1 for _ in iter_eqns(jaxpr))


def count_primitives(jaxpr: Jaxpr, names) -> int:
    """Equations whose primitive name is in ``names``."""
    names = {names} if isinstance(names, str) else set(names)
    return sum(1 for e in iter_eqns(jaxpr) if e.primitive.name in names)
