"""Logical-axis sharding (MaxText-style rules).

Model code annotates tensors with *logical* dimension names
(``constrain(x, ("batch", "seq", "embed"))``); a rule table maps logical
names to mesh axes.  When no rules are active (CPU unit tests) the
annotations are no-ops, so the same model code runs everywhere.

Rules differ per train-step mode:

* ``pjit`` baseline — batch over ('pod','data'), tensor dims over 'model';
  optionally FSDP: weight input-feature dims over 'data'.
* DeFT explicit-DP (shard_map manual over ('pod','data')) — batch is
  already local inside the manual region, so the 'batch' rule must be
  dropped there; tensor dims stay on the auto 'model' axis.
* DeFT-RS hierarchical (shard_map manual over 'pod') — batch over 'data',
  weights FSDP over 'data', explicit psum over 'pod'.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

AxisVal = Union[None, str, Tuple[str, ...]]

_state = threading.local()


def _current_rules() -> Optional[Dict[str, AxisVal]]:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def logical_rules(rules: Optional[Dict[str, AxisVal]]):
    """Activate a logical->mesh axis mapping for model code in scope."""
    prev = _current_rules()
    _state.rules = rules
    try:
        yield
    finally:
        _state.rules = prev


def _axis_prod(mesh_shape: Dict[str, int], axis: AxisVal) -> int:
    if axis is None:
        return 1
    names = (axis,) if isinstance(axis, str) else axis
    return 1 if not names else int(
        __import__("math").prod(mesh_shape.get(n, 1) for n in names)
    )


def spec_for(names: Sequence[Optional[str]], shape=None) -> P:
    """PartitionSpec for a tuple of logical dim names under active rules.
    Axes whose dimension does not divide the mesh axis product are dropped
    (replicated) — e.g. 36 heads over a 16-way 'model' axis.  So are axes
    an enclosing shard_map made manual: the data there is already local,
    and a constraint may name only auto axes."""
    rules = _current_rules() or {}
    mesh = jax.sharding.get_abstract_mesh()
    mesh_shape = dict(getattr(mesh, "shape", {}) or {})
    manual = {
        a for a, t in zip(getattr(mesh, "axis_names", ()),
                          getattr(mesh, "axis_types", ()))
        if t == jax.sharding.AxisType.Manual
    }
    out = []
    for i, n in enumerate(names):
        axis = rules.get(n) if n else None
        if axis is not None and manual:
            kept = tuple(a for a in ((axis,) if isinstance(axis, str)
                                     else axis) if a not in manual)
            axis = (kept[0] if len(kept) == 1 else kept) or None
        if axis is not None and shape is not None:
            if shape[i] % _axis_prod(mesh_shape, axis) != 0:
                axis = None
        out.append(axis)
    return P(*out)


def constrain(x: jax.Array, names: Sequence[Optional[str]]) -> jax.Array:
    """with_sharding_constraint iff rules are active; else identity."""
    rules = _current_rules()
    if rules is None:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return x
    assert len(names) == x.ndim, (names, x.shape)
    return jax.lax.with_sharding_constraint(x, spec_for(names, x.shape))


def shard_count(name: str, size: int) -> int:
    """Into how many pieces :func:`constrain` splits a dimension of
    ``size`` named ``name`` (1 where no rules are active)."""
    mesh = jax.sharding.get_abstract_mesh()
    if _current_rules() is None or mesh is None or not mesh.axis_names:
        return 1
    return _axis_prod(dict(mesh.shape), spec_for((name,), (size,))[0])


# ---------------------------------------------------------------------------
# Canonical rule tables
# ---------------------------------------------------------------------------
def rules_pjit(
    multi_pod: bool, fsdp: bool, layout: str = "tp"
) -> Dict[str, AxisVal]:
    """Baseline pjit train/serve step (XLA inserts every collective)."""
    if layout == "dp":
        batch = ("pod", "data", "model") if multi_pod else ("data", "model")
        return {"batch": batch, "embed": None, "heads": None, "kv": None,
                "ff": None, "vocab": None, "experts": None, "lru": None,
                "seq": None, "modal": None}
    batch = ("pod", "data") if multi_pod else ("data",)
    del fsdp  # FSDP shards *weights* (see specs.param_rules); activations
    #           keep 'embed' replicated to avoid batch/data double-mapping.
    return {
        "batch": batch,
        "embed": None,
        "heads": "model",
        "kv": "model",
        "ff": "model",
        "vocab": "model",
        "experts": "model",
        "lru": "model",
        "seq": None,
        "modal": None,
    }


def rules_deft_manual_dp() -> Dict[str, AxisVal]:
    """Inside shard_map manual over ('pod','data'): batch dims are local."""
    return {
        "batch": None,
        "embed": None,
        "heads": "model",
        "kv": "model",
        "ff": "model",
        "vocab": "model",
        "experts": "model",
        "lru": "model",
        "seq": None,
        "modal": None,
    }


def rules_deft_rs_manual_pod() -> Dict[str, AxisVal]:
    """Inside shard_map manual over ('pod',): data axis still auto (FSDP +
    batch sharding handled by XLA); pod-axis collectives are explicit."""
    return {
        "batch": ("data",),
        "embed": None,   # weight FSDP comes from specs.param_rules, not here
        "heads": "model",
        "kv": "model",
        "ff": "model",
        "vocab": "model",
        "experts": "model",
        "lru": "model",
        "seq": None,
        "modal": None,
    }
