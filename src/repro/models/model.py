"""Full model: init / forward / prefill / decode over any ArchConfig.

Layer-stack assembly
--------------------
The decoder stack is organized as

    prefix  — the first ``moe.first_k_dense`` layers (dense-FFN variants of
              the pattern), unrolled;
    stack   — floor((n - prefix - tail) / period) whole pattern periods,
              with per-position weights stacked over periods and the
              period body run under ``jax.lax.scan`` (HLO size stays
              O(period), which is what keeps 512-device dry-run compiles
              tractable at 100 layers);
    tail    — the remainder layers, unrolled.

Gradients w.r.t. stacked leaves come back stacked, so one leaf == one
DeFT gradient bucket covering all periods of that weight — matching the
paper's "less than 20 items" knapsack regime.

Encoder-decoder (seamless) carries a separate scanned encoder over the
(stub-frontend) modality embeddings; VLM cross-attention layers consume
the modality embeddings directly as memory.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, LayerSpec
from repro.models.blocks import apply_block, init_block, init_block_cache
from repro.models.common import (
    apply_norm,
    cross_entropy_loss,
    dense_init,
    embed_init,
    init_norm,
    softcap,
)
from repro.models.moe import add_stats, zero_stats
from repro.sharding import constrain, shard_count
from repro.util.flags import scan_unroll_enabled


# ---------------------------------------------------------------------------
# Stack layout
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class StackLayout:
    prefix_specs: Tuple[LayerSpec, ...]
    period: int
    n_periods: int
    tail_specs: Tuple[LayerSpec, ...]

    @property
    def n_layers(self) -> int:
        return (
            len(self.prefix_specs)
            + self.period * self.n_periods
            + len(self.tail_specs)
        )


def stack_layout(cfg: ArchConfig) -> StackLayout:
    specs = cfg.layer_specs()
    n = len(specs)
    prefix = cfg.moe.first_k_dense if cfg.moe else 0
    p = cfg.pattern_period
    n_periods = (n - prefix) // p
    tail = n - prefix - n_periods * p
    return StackLayout(
        prefix_specs=specs[:prefix],
        period=p,
        n_periods=n_periods,
        tail_specs=specs[n - tail :] if tail else (),
    )


def _stack_trees(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def init_params(key, cfg: ArchConfig, dtype=jnp.float32) -> Dict:
    lay = stack_layout(cfg)
    keys = jax.random.split(key, 6)
    params: Dict[str, Any] = {
        "embed": {"table": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype)},
        "final_norm": init_norm(cfg.norm, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = {"w": dense_init(keys[1], cfg.d_model, cfg.vocab_size, dtype)}

    # prefix (dense-FFN variants; deepseek-v2 layer 0 keeps the big d_ff)
    kp = jax.random.split(keys[2], max(len(lay.prefix_specs), 1))
    params["prefix"] = tuple(
        init_block(
            kp[i], cfg, dataclasses.replace(spec, ffn="dense"),
            dense_ffn_width=cfg.d_ff, dtype=dtype,
        )
        for i, spec in enumerate(lay.prefix_specs)
    )

    # scanned stack: one stacked tree per pattern position
    stack = []
    for j in range(lay.period):
        spec = cfg.layer_pattern[j]
        kj = jax.random.split(jax.random.fold_in(keys[3], j), max(lay.n_periods, 1))
        blocks = [
            init_block(kj[i], cfg, spec, dtype=dtype) for i in range(lay.n_periods)
        ]
        stack.append(_stack_trees(blocks) if blocks else {})
    params["stack"] = tuple(stack)

    kt = jax.random.split(keys[4], max(len(lay.tail_specs), 1))
    params["tail"] = tuple(
        init_block(kt[i], cfg, spec, dtype=dtype)
        for i, spec in enumerate(lay.tail_specs)
    )

    if cfg.is_encoder_decoder:
        ke = jax.random.split(keys[5], cfg.n_encoder_layers + 1)
        enc_blocks = [
            init_block(ke[i], cfg, LayerSpec("attn", "dense"), dtype=dtype)
            for i in range(cfg.n_encoder_layers)
        ]
        params["encoder"] = {
            "stack": _stack_trees(enc_blocks),
            "final_norm": init_norm(cfg.norm, cfg.d_model, dtype),
        }
    return params


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------
def init_cache(
    cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.float32,
    prefill_chunk: int = 1,
) -> Dict:
    kw = dict(dtype=dtype, prefill_chunk=prefill_chunk)
    lay = stack_layout(cfg)
    cache: Dict[str, Any] = {
        "prefix": tuple(
            init_block_cache(cfg, dataclasses.replace(s, ffn="dense"), batch,
                             max_len, **kw)
            for s in lay.prefix_specs
        ),
        "stack": tuple(
            _stack_trees(
                [
                    init_block_cache(cfg, cfg.layer_pattern[j], batch, max_len,
                                     **kw)
                    for _ in range(lay.n_periods)
                ]
            )
            if lay.n_periods
            else {}
            for j in range(lay.period)
        ),
        "tail": tuple(
            init_block_cache(cfg, s, batch, max_len, **kw)
            for s in lay.tail_specs
        ),
    }
    return cache


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def encode(params, cfg: ArchConfig, modal_embeds: jax.Array,
           unroll: bool = False) -> jax.Array:
    """Run the (bidirectional) encoder over stub-frontend embeddings."""
    assert cfg.is_encoder_decoder
    x = constrain(modal_embeds, ("batch", "modal", "embed"))
    spec = LayerSpec("attn", "dense")

    def body(x, block_p):
        x, _, _, _ = apply_block(block_p, x, cfg=cfg, spec=spec, causal=False)
        return x, None

    x, _ = jax.lax.scan(
        body, x, params["encoder"]["stack"],
        unroll=cfg.n_encoder_layers if (unroll or scan_unroll_enabled()) else 1,
    )
    return apply_norm(params["encoder"]["final_norm"], x, cfg.norm)


def forward(
    params,
    cfg: ArchConfig,
    tokens: jax.Array,                  # [B, S] int32
    *,
    memory: Optional[jax.Array] = None,  # [B, M, d] modality/encoder memory
    cache: Optional[Dict] = None,
    pos: int | jax.Array = 0,
    kv_length: Optional[jax.Array] = None,
    fill_cross_cache: bool = False,
    remat: bool = True,
    head: bool = True,
    unroll: bool = False,
) -> Tuple[jax.Array, Optional[Dict], jax.Array, Dict[str, jax.Array]]:
    """Returns (logits [B,S,V], new_cache, aux_loss, expert counters);
    with ``head=False`` the final-norm hidden states [B,S,d] replace the
    logits (the chunked loss path applies the LM head itself).  The
    counters are the expert layers' (``moe.STAT_KEYS``; {} for a model
    without experts)."""
    lay = stack_layout(cfg)
    x = params["embed"]["table"][tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    x = constrain(x, ("batch", None, "embed"))
    aux = jnp.zeros((), jnp.float32)
    stats = zero_stats(cfg)

    block_kw = dict(
        cfg=cfg, pos=pos, memory=memory, fill_cross_cache=fill_cross_cache,
        kv_length=kv_length,
    )

    def run_block(p, x, spec, c):
        return apply_block(p, x, spec=spec, cache=c, **block_kw)

    maybe_ckpt = (
        jax.checkpoint(run_block, static_argnums=(2,)) if remat else run_block
    )

    new_prefix = []
    for i, spec in enumerate(lay.prefix_specs):
        spec_d = dataclasses.replace(spec, ffn="dense")
        c = cache["prefix"][i] if cache is not None else None
        x, nc, a, st = maybe_ckpt(params["prefix"][i], x, spec_d, c)
        new_prefix.append(nc)
        aux = aux + a
        stats = add_stats(stats, st)

    def period_body(carry, xs):
        x, aux, stats = carry
        stacked_p, stacked_c = xs
        new_cs = []
        for j in range(lay.period):
            c = stacked_c[j] if stacked_c is not None else None
            x, nc, a, st = maybe_ckpt(stacked_p[j], x, cfg.layer_pattern[j],
                                      c)
            aux = aux + a
            stats = add_stats(stats, st)
            new_cs.append(nc if nc is not None else {})
        return (x, aux, stats), tuple(new_cs)

    new_stack = None
    if lay.n_periods:
        stacked_c = tuple(cache["stack"]) if cache is not None else None
        xs = (tuple(params["stack"]), stacked_c)
        if cache is None:
            xs = (tuple(params["stack"]), None)
            (x, aux, stats), _ = jax.lax.scan(
                lambda c, p: (period_body(c, (p, None))[0], None),
                (x, aux, stats), xs[0],
                unroll=lay.n_periods if (unroll or scan_unroll_enabled()) else 1,
            )
        else:
            (x, aux, stats), new_stack = jax.lax.scan(
                period_body, (x, aux, stats), xs,
                unroll=lay.n_periods if (unroll or scan_unroll_enabled()) else 1,
            )

    new_tail = []
    for i, spec in enumerate(lay.tail_specs):
        c = cache["tail"][i] if cache is not None else None
        x, nc, a, st = maybe_ckpt(params["tail"][i], x, spec, c)
        new_tail.append(nc)
        aux = aux + a
        stats = add_stats(stats, st)

    x = apply_norm(params["final_norm"], x, cfg.norm)
    if not head:
        new_cache = None
        if cache is not None:
            new_cache = {
                "prefix": tuple(new_prefix),
                "stack": new_stack if new_stack is not None else cache["stack"],
                "tail": tuple(new_tail),
            }
        return x, new_cache, aux, stats
    logits = head_logits(params, cfg, x)

    new_cache = None
    if cache is not None:
        new_cache = {
            "prefix": tuple(new_prefix),
            "stack": new_stack if new_stack is not None else cache["stack"],
            "tail": tuple(new_tail),
        }
    return logits, new_cache, aux, stats


# ---------------------------------------------------------------------------
# LM head + loss
# ---------------------------------------------------------------------------
def head_logits(params, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    """Final-norm hidden states -> vocab logits (+ softcap)."""
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["table"].T
    else:
        logits = x @ params["head"]["w"]
    logits = constrain(logits, ("batch", None, "vocab"))
    if cfg.final_logit_softcap:
        logits = softcap(logits, cfg.final_logit_softcap)
    return logits


def head_slice_width(rows: int, seq: int, chunk: int) -> int:
    """Rows a vocabulary slice of the LM head's backward takes from each
    shard of ``rows``: the largest multiple of 128 whose ``[B, seq, Vc]``
    f32 block is no larger than the forward's ``[B, chunk, rows]`` block,
    at least 128 and at most ``rows``."""
    return min(rows, max(128, chunk * rows // seq // 128 * 128))


def chunked_ce(
    params,
    cfg: ArchConfig,
    x: jax.Array,                     # [B, S, d] final-norm hidden states
    targets: jax.Array,               # [B, S] int32
    mask: Optional[jax.Array],        # [B, S] or None
    chunk: int,
    unroll: bool = False,
) -> jax.Array:
    """LM head + cross entropy that never holds the [B, S, V] logits.

    The [B, S, V] logits tensor dominates train-step memory at production
    shapes (gemma2-2b train_4k: ~4 TB of f32 logits+softmax temporaries
    globally).  The forward runs the head and CE per sequence chunk, so
    its live logits block is [B, chunk, V], and keeps only the per-token
    log-partition ``logz`` ([B, S] f32) for the backward.

    The backward goes by vocabulary slices instead, each over all B*S
    tokens at once: recompute the slice's logits from x, form
    dlogits = (softmax - onehot) * mask * g / count (times softcap's
    derivative), write that slice's rows of the head's [V, d] gradient
    with one product over K = B*S tokens, and add its part of dx into an
    f32 accumulator.  Each row of the weight gradient is written once;
    a backward by sequence chunks would instead add a whole [V, d]
    partial product into an accumulator once per chunk, reading and
    writing the full table gradient S / chunk times.  The slice width
    Vc is :func:`head_slice_width`: the largest multiple of 128 whose
    [B, S, Vc] f32 block is no larger than the forward's [B, chunk, V]
    block (qwen3-4b, S = 4095, chunk 256: Vc = 9472); a vocabulary that
    Vc does not divide ends in one narrower slice.  Where the rules split
    the vocabulary over a mesh axis, V and Vc are counted per shard and a
    slice takes the same rows of every shard, so no table row moves
    between chips.  Both passes run under the named scope ``lm_head``."""
    if cfg.tie_embeddings:
        table = params["embed"]["table"]
    else:
        table = params["head"]["w"].T
    if mask is None:
        mask = jnp.ones(targets.shape, jnp.float32)
    return _head_ce(table, x, targets, mask.astype(jnp.float32),
                    float(cfg.final_logit_softcap or 0.0), chunk,
                    bool(unroll or scan_unroll_enabled()))


def _f32_logits(raw, cap):
    """Head products (compute dtype) -> f32 logits (+ softcap)."""
    logits = raw.astype(jnp.float32)
    if cap:
        logits = cap * jnp.tanh(logits / cap)
    return logits


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _head_ce(table, x, targets, mask, cap, chunk, unroll):
    return _head_ce_fwd(table, x, targets, mask, cap, chunk, unroll)[0]


def _head_ce_fwd(table, x, targets, mask, cap, chunk, unroll):
    with jax.named_scope("lm_head"):
        b, s, d = x.shape
        chunk = min(chunk, s)
        pad = (-s) % chunk
        pads = ((0, 0), (0, pad))
        xp = jnp.pad(x, (*pads, (0, 0)))
        n = xp.shape[1] // chunk
        xs = xp.reshape(b, n, chunk, d).swapaxes(0, 1)
        ys = jnp.pad(targets, pads).reshape(b, n, chunk).swapaxes(0, 1)
        ms = jnp.pad(mask, pads).reshape(b, n, chunk).swapaxes(0, 1)
        xs = constrain(xs, (None, "batch", None, "embed"))

        def body(carry, sl):
            xc, yc, mc = sl
            xc = constrain(xc, ("batch", None, "embed"))
            raw = constrain(xc @ table.T, ("batch", None, "vocab"))
            logits = _f32_logits(raw, cap)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
            return carry + jnp.sum((logz - gold) * mc), logz

        total, logz = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                   (xs, ys, ms), unroll=n if unroll else 1)
        logz = logz.swapaxes(0, 1).reshape(b, n * chunk)[:, :s]
        count = jnp.sum(mask)
        loss = total / jnp.maximum(count, 1.0)
        # shard-major view for the backward: row r of shard k is
        # vocabulary id k*span + r (one shard without a vocab axis)
        v = table.shape[0]
        m = shard_count("vocab", v)
        shards = table.reshape(m, v // m, d)
    return loss, (shards, x, targets, mask, logz, count)


def _head_ce_bwd(cap, chunk, unroll, res, g):
    shards, x, targets, mask, logz, count = res
    with jax.named_scope("lm_head"):
        b, s, _ = x.shape
        # each slice takes the same rows of every vocabulary shard, so
        # nothing crosses shards inside the loop
        m, span, d = shards.shape
        vc = head_slice_width(span, s, min(chunk, s))
        n, rem = divmod(span, vc)
        shards = constrain(shards, ("vocab", None, "embed"))
        first = (jnp.arange(m) * span)[:, None]
        x = constrain(x, ("batch", None, "embed"))
        scale = (mask * (g / jnp.maximum(count, 1.0)))[..., None, None]

        def slice_grads(dx, start, w):          # w: [m, width, d]
            raw = jnp.einsum("bsd,mvd->bsmv", x, w)
            raw = constrain(raw, ("batch", None, "vocab", None))
            logits = _f32_logits(raw, cap)
            ids = first + start + jnp.arange(w.shape[1])
            onehot = targets[..., None, None] == ids
            dl = (jnp.exp(logits - logz[..., None, None]) - onehot) * scale
            if cap:
                dl = dl * (1.0 - jnp.square(logits / cap))
            dl = constrain(dl.astype(raw.dtype),
                           ("batch", None, "vocab", None))
            dw = jnp.einsum("bsmv,bsd->mvd", dl, x,
                            preferred_element_type=jnp.float32)
            # dx stays split by shard until the loop ends
            dx = dx + jnp.einsum("bsmv,mvd->mbsd", dl, w,
                                 preferred_element_type=jnp.float32)
            return dx, dw.astype(shards.dtype)

        def body(dx, i):
            w = jax.lax.dynamic_slice_in_dim(shards, i * vc, vc, axis=1)
            return slice_grads(dx, i * vc, w)

        dx = constrain(jnp.zeros((m, b, s, d), jnp.float32),
                       ("vocab", "batch", None, "embed"))
        dx, dws = jax.lax.scan(body, dx, jnp.arange(n),
                               unroll=n if unroll else 1)
        dw = dws.swapaxes(0, 1).reshape(m, n * vc, d)
        if rem:
            dx, tail = slice_grads(dx, n * vc, shards[:, n * vc:])
            dw = jnp.concatenate([dw, tail], axis=1)
    return dw.reshape(m * span, d), dx.sum(0).astype(x.dtype), None, None


_head_ce.defvjp(_head_ce_fwd, _head_ce_bwd)


# ---------------------------------------------------------------------------
# Train / serve entry points
# ---------------------------------------------------------------------------
def loss_fn(
    params,
    cfg: ArchConfig,
    batch: Dict[str, jax.Array],
    *,
    remat: bool = True,
    loss_chunk: int = 0,
    unroll: bool = False,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross entropy (+ MoE aux). batch: tokens, labels, and the
    optional stub-frontend 'memory' embeddings (audio frames / image
    patches).  ``loss_chunk > 0`` switches to the sequence-chunked LM-head
    path (memory: see chunked_ce).  The metrics are ``ce``, ``aux`` and,
    for a model with expert layers, their counters (``moe.STAT_KEYS``)."""
    memory = batch.get("memory")
    if cfg.is_encoder_decoder:
        memory = encode(params, cfg, memory, unroll=unroll)
    mask = batch.get("mask")
    mask = mask[:, 1:] if mask is not None else None
    if loss_chunk:
        x, _, aux, stats = forward(
            params, cfg, batch["tokens"], memory=memory, remat=remat,
            head=False, unroll=unroll,
        )
        loss = chunked_ce(
            params, cfg, x[:, :-1], batch["labels"][:, 1:], mask, loss_chunk,
            unroll=unroll,
        )
    else:
        logits, _, aux, stats = forward(
            params, cfg, batch["tokens"], memory=memory, remat=remat,
            unroll=unroll,
        )
        loss = cross_entropy_loss(
            logits[:, :-1], batch["labels"][:, 1:], mask
        )
    return loss + aux, {"ce": loss, "aux": aux, **stats}


def prefill(
    params, cfg: ArchConfig, tokens: jax.Array, cache: Dict,
    *, memory: Optional[jax.Array] = None, unroll: bool = False,
) -> Tuple[jax.Array, Dict]:
    """Fill the cache with a prompt; returns (last-position logits, cache)."""
    if cfg.is_encoder_decoder and memory is not None:
        memory = encode(params, cfg, memory, unroll=unroll)
    logits, cache, _, _ = forward(
        params, cfg, tokens, memory=memory, cache=cache, pos=0,
        fill_cross_cache=True, remat=False, unroll=unroll,
    )
    return logits[:, -1], cache


def decode_step(
    params, cfg: ArchConfig, token: jax.Array, cache: Dict, pos: jax.Array,
    *, kv_length: Optional[jax.Array] = None, unroll: bool = False,
) -> Tuple[jax.Array, Dict]:
    """One decode step: token [B] int32, absolute position ``pos``."""
    logits, cache, _, _ = forward(
        params, cfg, token[:, None], cache=cache, pos=pos, kv_length=kv_length,
        remat=False, unroll=unroll,
    )
    return logits[:, 0], cache
