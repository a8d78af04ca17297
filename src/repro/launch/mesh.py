"""Production mesh construction.

A function, not a module-level constant — importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def _auto_axis_types(n: int):
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single v5e pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto_axis_types(len(axes)))


def make_elastic_mesh(device_rows):
    """Mesh over an EXPLICIT device subset — the elastic control plane's
    scale-down/up builds these (DESIGN.md §10): ``device_rows[i]`` is
    the tuple of model-axis devices of data-parallel shard ``i``, so a
    4->2 scale-down passes the two surviving rows and the dead devices
    simply stop appearing in any sharding.

    ``jax.make_mesh`` always spans ``jax.devices()``; this constructs
    ``jax.sharding.Mesh`` directly from the survivor array instead."""
    import numpy as np

    arr = np.array(device_rows, dtype=object)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    return jax.sharding.Mesh(
        arr, ("data", "model"), axis_types=_auto_axis_types(2)
    )


def make_debug_mesh(data: int = 4, model: int = 2, pod: int = 0):
    """Small CPU mesh for tests/examples (needs forced host device count)."""
    if pod:
        return jax.make_mesh(
            (pod, data, model),
            ("pod", "data", "model"),
            axis_types=_auto_axis_types(3),
        )
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=_auto_axis_types(2)
    )


# ---------------------------------------------------------------------------
# Per-link communication chains (DESIGN.md §14)
# ---------------------------------------------------------------------------
def ring_chain(n: int, link: int) -> tuple:
    """Device-order chain (axis indices, DeAR-style ring reordering) for
    ``link`` over ``n`` data-parallel positions.

    Link 0 is the natural axis order — the ordering XLA's single-axis
    collectives already use, so primary traffic keeps its fabric.  Link
    ``l`` > 0 interleaves with stride ``l + 1`` (evens-then-odds for the
    first secondary link: ``[0, 2, ..., 1, 3, ...]``), which on a
    multi-NIC torus maps neighbor hops onto a *different* physical cable
    set than the natural ring — the DeAR observation that decoupled
    stages on distinct device orders stop contending for the same links.
    Falls back to a rotation when the stride pattern degenerates (it
    never does for n >= 3, but n <= 2 has only one ring)."""
    if n <= 0:
        raise ValueError(f"ring_chain needs n >= 1, got {n}")
    if link <= 0 or n <= 2:
        return tuple(range(n))
    stride = link + 1
    chain = [p for s in range(stride) for p in range(s, n, stride)]
    if len(set(chain)) != n:
        chain = [(p + link) % n for p in range(n)]
    return tuple(chain)


def link_chains(n: int, n_links: int = 2) -> dict:
    """``{link_id: chain}`` for every link — the topology input the
    runtime's chain collectives and the planner's per-link pricing
    share."""
    return {link: ring_chain(n, link) for link in range(n_links)}
