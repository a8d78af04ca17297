"""Plain reference of the cells' training steps.

It imports nothing of the program.  It reads the published sizes from
the configuration file and the parameter tree's names and shapes (the
interface: ``embed/table``, ``final_norm``, and per layer ``pre_norm``,
``mixer/{wq,wk,wv,wo[,q_norm,k_norm]}``, ``ffn_norm``,
``ffn/{gate,up,down}`` or ``ffn/{up,down}``, stacked on a leading layer
axis), and starts from the benchmark's own seeded weights.

One sequence at a time, in float32 with matrix products at
``Precision.HIGHEST``: token embedding; per layer a pre-norm, grouped-
query causal attention (per-head RMS q/k norms where the model has
them, rotary embedding with the two halves of each head rotated
together, softmax scale 1/sqrt(head_dim), keys inside the sliding
window where one is set), a residual, a pre-norm and the MLP (gated
SiLU or tanh-GELU), a residual; a final norm, the tied LM head and the
mean next-token cross entropy.  Attention runs in blocks of queries and
the loss in blocks of positions, each recomputed in the backward pass,
so that 4,096 tokens fit.  Departures the configuration states (norm
epsilon, no biases in linear layers) are read from its file; an RMSNorm
weight is ``1 + scale``, as the program writes it.

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


def _ein(precision: str):
    """einsum(spec, activation, operand); under fp8 the activation is
    rounded here, and so is the operand unless ``rounded`` says the
    caller rounded it once already (a weight)."""
    if precision == "fp8":
        def ein(spec, a, b, rounded=False):
            return jnp.einsum(spec, _q8(a), b if rounded else _q8(b),
                              precision=HIGHEST)
        return ein
    return lambda spec, a, b, rounded=False: jnp.einsum(
        spec, a, b, precision=HIGHEST)


_MATRICES = ("wq", "wk", "wv", "wo", "gate", "up", "down", "table")


def _round_weights(params):
    """Each matrix rounded to fp8 once per step, not at every use."""
    def one(path, x):
        last = getattr(path[-1], "key", None)
        return _q8(x) if last in _MATRICES else x
    return jax.tree_util.tree_map_with_path(one, params)


def _eps(c: Dict[str, Any]) -> float:
    return c["rms_norm_eps"] if c["norm"] == "rmsnorm" else c["norm_epsilon"]


def _norm(p, x, c):
    eps = _eps(c)
    if c["norm"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * (1.0 + p["scale"])
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rms_head(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * (1.0 + scale)


def _rope(x, theta):
    """x [S, H, D]; the first and second halves of D rotate as pairs."""
    s, _, d = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, h, c, ein, block: int):
    s = h.shape[0]
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // nh
    g = nh // nkv
    window = c.get("sliding_window") or 0
    q = ein("sd,de->se", h, p["wq"], True).reshape(s, nh, hd)
    k = ein("sd,de->se", h, p["wk"], True).reshape(s, nkv, hd)
    v = ein("sd,de->se", h, p["wv"], True).reshape(s, nkv, hd)
    if c.get("qk_norm"):
        q = _rms_head(q, p["q_norm"], _eps(c))
        k = _rms_head(k, p["k_norm"], _eps(c))
    q = _rope(q, c["rope_theta"]).reshape(s, nkv, g, hd)
    k = _rope(k, c["rope_theta"])
    kpos = jnp.arange(s)

    @jax.checkpoint
    def one_block(qb, start):
        qpos = start + jnp.arange(qb.shape[0])
        sc = ein("qkgd,tkd->kgqt", qb, k) / jnp.sqrt(jnp.float32(hd))
        ok = kpos[None, :] <= qpos[:, None]
        if window:
            ok &= kpos[None, :] > qpos[:, None] - window
        sc = jnp.where(ok, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return ein("kgqt,tkd->qkgd", pr, v)

    outs = [one_block(q[i:i + block], i) for i in range(0, s, block)]
    o = jnp.concatenate(outs, 0).reshape(s, nh * hd)
    return ein("se,ed->sd", o, p["wo"], True)


def _mlp(p, h, c, ein):
    if c["hidden_act"] == "silu":
        a = jax.nn.silu(ein("sd,df->sf", h, p["gate"], True))
        a = a * ein("sd,df->sf", h, p["up"], True)
    else:
        a = jax.nn.gelu(ein("sd,df->sf", h, p["up"], True),
                        approximate=True)
    return ein("sf,fd->sd", a, p["down"], True)


def _layers(params) -> List[Dict[str, Any]]:
    if params.get("prefix") or params.get("tail"):
        raise ValueError("the reference knows only a stacked layer pattern")
    out = []
    for stacked in params["stack"]:
        n = jax.tree.leaves(stacked)[0].shape[0]
        out += [jax.tree.map(lambda x, i=i: x[i], stacked) for i in range(n)]
    return out


def row_loss(params, tokens, c: Dict[str, Any], precision: str = "f32",
             block: int = 512, positions: Optional[int] = None):
    """Mean next-token cross entropy of one sequence ``tokens`` [S];
    ``positions`` keeps only the first that many targets."""
    ein = _ein(precision)
    x = params["embed"]["table"][tokens]
    if precision == "fp8":
        params = _round_weights(params)
    table = params["embed"]["table"]
    for p in _layers(params):
        layer = jax.checkpoint(
            lambda x, p: x + _attention(p["mixer"], _norm(p["pre_norm"], x, c),
                                        c, ein, block))
        x = layer(x, p)
        x = x + _mlp(p["ffn"], _norm(p["ffn_norm"], x, c), c, ein)
    x = _norm(params["final_norm"], x, c)
    h, y = x[:-1], tokens[1:]
    n = h.shape[0] if positions is None else positions

    @jax.checkpoint
    def chunk(hc, yc):
        logits = ein("sd,vd->sv", hc, table, True)
        lz = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, yc[:, None], -1)[:, 0]
        return jnp.sum(lz - gold)

    tot = sum(chunk(h[i:min(i + block, n)], y[i:min(i + block, n)])
              for i in range(0, n, block))
    return tot / n


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
