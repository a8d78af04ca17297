"""DeepSeek-V2-Lite's mechanisms at a tiny size on seeded weights, in f32:
the expert layer that holds a share of the experts, latent attention
without a q LoRA under YaRN, the attention kernel with q/k heads wider
than v heads and an explicit softmax scale, and their prices in the
planner."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import MLAConfig
from repro.core.profiler import _layer_flops_fwd
from repro.kernels.flash_attention import attention_reference, flash_attention
from repro.models import attention as attn
from repro.models import moe
from repro.models.common import (
    gated_act,
    rope_frequencies,
    yarn_correction_range,
    yarn_mscale,
)

LITE = get_config("deepseek-v2-lite")


def _tiny(held=0, **moe_kw):
    """V2-Lite's router (64 experts, top 6, no renormalisation, per
    sequence aux) and rotary scaling at tiny widths."""
    return dataclasses.replace(
        LITE, d_model=32, n_heads=2, n_kv_heads=2, vocab_size=64,
        mla=MLAConfig(kv_lora_rank=16, q_lora_rank=None, qk_nope_head_dim=8,
                      qk_rope_head_dim=8, v_head_dim=8),
        moe=dataclasses.replace(LITE.moe, d_expert=16, experts_held=held,
                                **moe_kw))


def _shared_ffn(p, x, cfg):
    sh = p["shared"]
    return gated_act(cfg.ffn_activation, x @ sh["gate"], x @ sh["up"]) \
        @ sh["down"]


def test_eight_shares_add_up_to_the_uncut_layer():
    """Eight devices' shares of 8 of the 64 experts each (ids 8s ..
    8s + 7), plus the shared experts once, give the uncut layer."""
    full = _tiny()
    p = moe.init_moe(jax.random.PRNGKey(0), full)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, full.d_model))
    want, _, _ = moe.apply_moe(p, x, cfg=full)
    xt = x.reshape(-1, full.d_model)
    _, top_p, top_e = moe.route(p["router"], xt, full.moe)
    got, rows = _shared_ffn(p, xt, full), 0.0
    for s in range(8):
        ex = jax.tree.map(lambda w: w[8 * s:8 * s + 8], p["experts"])
        y, st = moe.routed_experts(xt, top_p, top_e, ex, 8 * s, "silu")
        got = got + y
        rows += float(st["moe_rows"])
        assert float(st["moe_dropped"]) == 0.0
    np.testing.assert_allclose(np.asarray(got.reshape(x.shape)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    assert rows == 2 * 24 * full.moe.experts_per_token


def test_no_token_dropped_when_every_choice_is_held():
    """A router that sends every token's six choices to the eight held
    experts, most of them to expert 0: the layer takes all T*k rows
    (the buffer's worst case) and agrees with a loop over tokens."""
    cfg = _tiny(held=8)
    me = cfg.moe
    p = moe.init_moe(jax.random.PRNGKey(2), cfg)
    bias = jnp.zeros((cfg.d_model, me.n_experts)).at[0, :8].set(
        jnp.linspace(40.0, 20.0, 8))
    p["router"] = p["router"] * 0.01 + bias
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 16, cfg.d_model))
    x = x.at[..., 0].set(1.0 + jnp.abs(x[..., 0]))
    y, _, st = moe.apply_moe(p, x, cfg=cfg)
    t, k = 16, me.experts_per_token
    assert float(st["moe_dropped"]) == 0.0
    assert float(st["moe_rows"]) == t * k
    assert float(st["moe_max_rows"]) == t
    xt = x[0]
    probs = jax.nn.softmax(xt @ p["router"], -1)
    top_p, top_e = jax.lax.top_k(probs, k)
    ex = p["experts"]
    want = []
    for i in range(t):
        out = _shared_ffn(p, xt[i], cfg)
        for w, e in zip(top_p[i], top_e[i]):
            h = gated_act("silu", xt[i] @ ex["gate"][e], xt[i] @ ex["up"][e])
            out = out + w * (h @ ex["down"][e])
        want.append(out)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(jnp.stack(want)),
                               rtol=1e-5, atol=1e-5)


def _gathers_by_autodiff(xt, top_p, top_e, ex, offset):
    """The expert layer with its two row gathers left to autodiff, whose
    backward passes are scatter-adds."""
    (t, k), d = top_e.shape, xt.shape[-1]
    pair, sizes, pos, _ = moe._dispatch(top_e, ex["gate"].shape[0], offset)
    xs = jnp.concatenate([xt, jnp.zeros((1, d))])[pair // k]
    h = gated_act("silu", jax.lax.ragged_dot(xs, ex["gate"], sizes),
                  jax.lax.ragged_dot(xs, ex["up"], sizes))
    ys = jax.lax.ragged_dot(h, ex["down"], sizes)
    ys = jnp.concatenate([ys, jnp.zeros((1, d))])
    w = jnp.where(pos < ys.shape[0] - 1, top_p, 0.0)
    return jnp.einsum("tk,tkd->td", w, ys[pos])


@pytest.mark.parametrize("held,offset,k,held_only", [
    (8, 0, 6, False),      # held >= k, most of the buffer padding
    (8, 0, 6, True),       # every choice held: the buffer full
    (4, 0, 6, False),      # held < k: a buffer of T * held rows
    (8, 24, 6, False),     # a share at offset 24
    (8, 8, 1, False),      # top-1
])
def test_expert_layer_backward_by_gathers_matches_autodiff(held, offset, k,
                                                           held_only):
    """The expert layer's value and its gradients in the tokens, the
    routing weights and each expert tensor, with both row gathers
    differentiated by gathers (``moe.take_rows``), equal those that
    autodiff's scatter-adds give."""
    t, e, d, de = 24, 32, 16, 8
    keys = jax.random.split(jax.random.PRNGKey(8), 6)
    # each token's k distinct experts, from the held ones alone if asked
    ids = jnp.arange(offset, offset + held) if held_only else jnp.arange(e)
    top_e = jax.vmap(lambda kk: jax.random.permutation(kk, ids)[:k])(
        jax.random.split(keys[0], t))
    top_p = jax.nn.softmax(jax.random.normal(keys[1], (t, k)), -1)
    xt = jax.random.normal(keys[2], (t, d))
    ex = {"gate": jax.random.normal(keys[3], (held, d, de)),
          "up": jax.random.normal(keys[4], (held, d, de)),
          "down": jax.random.normal(keys[5], (held, de, d)) / 4}
    cot = jax.random.normal(jax.random.PRNGKey(9), (t, d))

    def loss(fn):
        return lambda xt, top_p, ex: jnp.sum(fn(xt, top_p, top_e, ex) * cot)

    ours = loss(lambda *a: moe.routed_experts(*a, offset, "silu")[0])
    plain = loss(lambda *a: _gathers_by_autodiff(*a, offset))
    rows = float(moe.routed_experts(xt, top_p, top_e, ex, offset,
                                    "silu")[1]["moe_rows"])
    assert 0 < rows <= t * min(k, held)
    assert (rows == t * k) == held_only
    (v, g), (v0, g0) = (jax.value_and_grad(f, (0, 1, 2))(xt, top_p, ex)
                        for f in (ours, plain))
    assert float(v) == float(v0)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)


def test_yarn_frequency_mask_has_the_published_ramp():
    rs = LITE.rope_scaling
    assert yarn_correction_range(rs, 64, 10_000.0) == (10, 23)
    base = np.asarray(rope_frequencies(64, 10_000.0))
    f = np.asarray(rope_frequencies(64, 10_000.0, rs))
    np.testing.assert_allclose(f[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], base[23:] / 40.0, rtol=1e-6)
    ratio = base[11:23] / f[11:23]
    assert np.all((ratio > 1.0) & (ratio < 40.0))
    assert np.all(np.diff(ratio) > 0)
    assert yarn_mscale(40.0, 0.707) ** 2 == pytest.approx(1.5896, abs=1e-4)


def _rope_plain(x, theta, scaling, s):
    """Half-split rotation with YaRN, written out per frequency."""
    d = x.shape[-1]
    freqs = np.asarray(rope_frequencies(d, theta, scaling))
    ang = np.arange(s)[:, None] * freqs
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def test_mla_without_q_lora_under_yarn_matches_plain_attention():
    cfg = _tiny()
    m = cfg.mla
    p = attn.init_mla(jax.random.PRNGKey(4), cfg)
    assert "wq" in p and "wdq" not in p and "wuq" not in p
    s, h = 20, cfg.n_heads
    x = jax.random.normal(jax.random.PRNGKey(5), (1, s, cfg.d_model))
    got, _ = attn.apply_mla(p, x, cfg=cfg)
    xn = np.asarray(x[0], np.float64)
    pn = {k: np.asarray(v, np.float64) for k, v in p.items()}
    q = (xn @ pn["wq"]).reshape(s, h, -1)
    kv = xn @ pn["wdkv"]
    c = kv[:, :m.kv_lora_rank]
    c = c / np.sqrt(np.mean(c * c, -1, keepdims=True) + 1e-6) \
        * (1 + pn["kv_norm"])
    kr = _rope_plain(kv[:, None, m.kv_lora_rank:], cfg.rope_theta,
                     cfg.rope_scaling, s)
    qn, qr = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    qr = _rope_plain(qr, cfg.rope_theta, cfg.rope_scaling, s)
    kn = (c @ pn["wuk"]).reshape(s, h, -1)
    v = (c @ pn["wuv"]).reshape(s, h, -1)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5 \
        * yarn_mscale(40.0, 0.707) ** 2
    sc = (np.einsum("qhd,khd->hqk", qn, kn)
          + np.einsum("qhd,kd->hqk", qr, kr[:, 0])) * scale
    sc = np.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    want = np.einsum("hqk,khd->qhd", pr, v).reshape(s, -1) @ pn["wo"]
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("impl", ["pallas", "blocked"])
def test_attention_q192_v128_explicit_scale(impl):
    b, s, h = 1, 256, 2
    keys = jax.random.split(jax.random.PRNGKey(6), 4)
    q = jax.random.normal(keys[0], (b, s, h, 192))
    k = jax.random.normal(keys[1], (b, s, h, 192))
    v = jax.random.normal(keys[2], (b, s, h, 128))
    g = jax.random.normal(keys[3], (b, s, h, 128))
    scale = 192 ** -0.5 * 1.5896

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * g)

    ours = loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, impl=impl, sm_scale=scale, block_q=128,
        block_kv=128, interpret=True))
    ref = loss(lambda q, k, v: attention_reference(
        q, k, v, causal=True, sm_scale=scale))
    out = flash_attention(q, k, v, causal=True, impl=impl, sm_scale=scale,
                          block_q=128, block_kv=128, interpret=True)
    assert out.shape == (b, s, h, 128)
    want = attention_reference(q, k, v, causal=True, sm_scale=scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4,
                               atol=2e-5)
    for a, r in zip(jax.grad(ours, (0, 1, 2))(q, k, v),
                    jax.grad(ref, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=2e-4,
                                   atol=2e-4)


def test_profiler_prices_k_held_over_e_routed_experts():
    held = dataclasses.replace(
        LITE, moe=dataclasses.replace(LITE.moe, experts_held=8))
    tokens, d, de = 4096, LITE.d_model, LITE.moe.d_expert
    a = _layer_flops_fwd(LITE, 4096, 1)[2]     # embed, layer 0, layer 1
    b = _layer_flops_fwd(held, 4096, 1)[2]
    # every expert held: k experts a token; 8 of 64 held: k * 8 / 64
    assert a - b == pytest.approx(2.0 * tokens * 3 * d * de * 6 * (1 - 8 / 64))
    per_layer = held.layer_param_counts()[1] - held.layer_param_counts()[0]
    assert per_layer == 3 * d * de * (8 + 2) + d * 64 - 3 * d * 10944


def test_attn_params_count_mla_without_q_lora():
    from repro.models.model import init_params

    cfg = dataclasses.replace(_tiny(held=4), n_layers=2)
    params = init_params(jax.random.PRNGKey(7), cfg)
    mixer = params["prefix"][0]["mixer"]
    matrices = sum(int(np.prod(w.shape)) for n, w in mixer.items()
                   if n != "kv_norm")
    assert matrices == cfg._attn_params(cfg.layer_pattern[0])
    m = cfg.mla
    assert matrices == (cfg.d_model * cfg.n_heads * 16
                        + cfg.d_model * (m.kv_lora_rank + 8)
                        + m.kv_lora_rank * cfg.n_heads * 16
                        + cfg.n_heads * 8 * cfg.d_model)
    assert math.isclose(cfg.active_params(), cfg.total_params()
                        - (4 - 6 * 4 / 64) * 3 * cfg.d_model * 16)
