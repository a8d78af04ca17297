"""Plain reference of the cells' training steps.

It imports nothing of the program.  The loss of one sequence is the
configuration's architecture module's (``bench/archs``,
:func:`row_loss`), which reads the published sizes from the
configuration file and the parameter tree's names and shapes; it starts
from the benchmark's own seeded weights.  Here is what every
architecture shares: the matrix products, the fp8 control and the
training that follows the plan.

Training follows the plan's update list (:func:`train`): at step i the
gradient of the global batch is taken at the current weights, and where
the plan applies an update, AdamW with global-norm clipping is applied
to the mean of the generations it names (a zero gradient where it names
none, which still advances AdamW's step count).

``precision="fp8"`` is the control: every matrix product's operands and
the gradients flowing back into them are rounded to 4 exponent and 3
mantissa bits (float8 e4m3) with a per-tensor scale.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec

HIGHEST = jax.lax.Precision.HIGHEST
# largest finite value of 4 exponent and 3 mantissa bits with an
# IEEE-style top exponent (float8 e4m3 without its extra top binade)
F8_MAX = 240.0


def _round_f8(x):
    # reduce_precision, not a round trip through a float8 type: XLA may
    # drop a convert pair as excess precision
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return jax.lax.reduce_precision(x / s, exponent_bits=4,
                                    mantissa_bits=3) * s


@jax.custom_vjp
def _q8(x):
    return _round_f8(x)


def _q8_fwd(x):
    return _round_f8(x), None


def _q8_bwd(_, g):
    return (_round_f8(g),)


_q8.defvjp(_q8_fwd, _q8_bwd)


def einsum_for(precision: str):
    """einsum(subscripts, activation, operand); under fp8 the activation is
    rounded here, and so is the operand unless ``rounded`` says the
    caller rounded it once already (a weight)."""
    if precision == "fp8":
        def ein(subs, a, b, rounded=False):
            return jnp.einsum(subs, _q8(a), b if rounded else _q8(b),
                              precision=HIGHEST)
        return ein
    return lambda subs, a, b, rounded=False: jnp.einsum(
        subs, a, b, precision=HIGHEST)


def round_weights(params, names: Sequence[str]):
    """Each leaf whose last key is in ``names`` rounded to fp8 once per
    step, not at every use."""
    def one(path, x):
        last = getattr(path[-1], "key", None)
        return _q8(x) if last in names else x
    return jax.tree_util.tree_map_with_path(one, params)


def row_loss(params, tokens, c: Dict[str, Any], precision: str = "f32",
             block: int = 512, positions: Optional[int] = None):
    """The loss of one sequence ``tokens`` [S] by the configuration's
    architecture module (``bench/archs``); ``positions`` keeps only the
    first that many targets."""
    return spec.arch(c).row_loss(params, tokens, c, precision, block,
                                 positions)


def leaf_names(tree) -> List[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path) for path, _ in flat]


def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def _adamw(opt: Dict[str, Any]):
    b1, b2, eps, lr = opt["beta1"], opt["beta2"], opt["eps"], opt["lr"]
    wd, clip = opt["weight_decay"], opt["grad_clip"]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def step(params, m, v, g, t):
        if clip:
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                              for x in jax.tree.leaves(g)))
            g = jax.tree.map(
                lambda x: x * jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12)),
                g)
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t

        def upd(p, m_, v_):
            u = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
            if wd:
                u = u + wd * p
            return p - lr * u

        return jax.tree.map(upd, params, m, v), m, v, g

    return step


def train(make_params, rows: Sequence[Sequence[jax.Array]],
          updates: Sequence[Optional[Tuple[Tuple[int, ...], int]]],
          c: Dict[str, Any], opt: Dict[str, Any], *,
          precision: str = "f32", fault: str = "",
          devices: Sequence[Any] = ()) -> Dict[str, Any]:
    """Follow the plan for ``len(rows)`` steps from ``make_params()``, a
    jitted function that draws the initial weights (called again at the
    end, so that no second copy of them is held).

    ``rows[i]`` are the global batch's sequences at step i;
    ``updates[i]`` is None or (steps whose gradients the update at step
    i applies, their count k).  ``fault`` plants a fault in the
    reference: ``half_batch`` (half of the rows, or of the positions of
    a single row, left out and the mean taken over the rest) or
    ``no_exchange`` (the gradient is the first row's alone, scaled as if
    the sum over the rows had arrived).

    With several ``devices`` the rows of a step are spread over them,
    one row a device at a time, and their gradients summed on the first
    (where the weights and the optimizer state live).

    A step's gradient waits on the host until the update that applies
    it, so that no more than one gradient sits beside the weights and
    AdamW's state on the device.

    Returns the losses, the leaves of the gradient that the first
    non-empty update applied (after clipping; host arrays), and the
    per-leaf norms of the change of the weights over all the steps."""
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t, n: row_loss(p, t, c, precision, positions=n)),
        static_argnums=2)
    add = jax.jit(lambda a, b, w: jax.tree.map(lambda x, y: x + w * y, a, b),
                  donate_argnums=0)
    scale = jax.jit(lambda a, w: jax.tree.map(lambda x: w * x, a),
                    donate_argnums=0)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    step = _adamw(opt)
    params = make_params()
    params_device = jax.tree.leaves(params)[0].sharding
    m, v = zeros(params), zeros(params)
    t = 0
    grads: Dict[int, Any] = {}
    losses: List[float] = []
    first_grad = None
    for i, batch in enumerate(rows):
        use = list(batch)
        positions = None
        if fault == "half_batch":
            if len(use) > 1:
                use = use[: len(use) // 2]
            else:
                positions = (use[0].shape[0] - 1) // 2
        g = None
        loss = 0.0
        width = max(len(devices), 1)
        for j0 in range(0, len(use), width):
            # one row per device at a time, dispatched side by side; each
            # gradient is summed into the first device's as it arrives
            wave = []
            for d, r in enumerate(use[j0:j0 + width]):
                p_d = params if d == 0 else jax.device_put(params, devices[d])
                r_d = r if d == 0 else jax.device_put(r, devices[d])
                wave.append(grad_fn(p_d, r_d, positions))
                del p_d
            for d, (l, gr) in enumerate(wave):
                j = j0 + d
                loss += float(l) / len(use)
                if d:
                    gr = jax.device_put(gr, devices[0])
                wave[d] = None
                if fault == "no_exchange":
                    if j == 0:
                        g = scale(gr, 1.0 / len(batch))
                    continue
                w = 1.0 / len(use)
                if g is None:
                    g = gr if w == 1.0 else scale(gr, w)
                else:
                    g = add(g, gr, w)
                del gr
            del wave
        losses.append(loss)
        grads[i] = jax.device_get(g)
        del g
        upd = updates[i]
        if upd is not None:
            src, k = upd
            acc = None
            for s in src:
                gs = jax.device_put(grads.pop(s), params_device)
                acc = (gs if k == 1 else scale(gs, 1.0 / k)) if acc is None \
                    else add(acc, gs, 1.0 / k)
                del gs
            if acc is None:
                acc = zeros(params)
            t += 1
            params, m, v, applied = step(params, m, v, acc, jnp.float32(t))
            del acc
            if src and first_grad is None:
                first_grad = [np.asarray(x) for x in jax.tree.leaves(applied)]
            del applied
    del m, v, grads
    delta = jax.jit(lambda a: leaf_norms(
        jax.tree.map(lambda x, y: x - y, a, make_params())))(params)
    return {"losses": losses, "first_grad": first_grad, "delta": delta}
