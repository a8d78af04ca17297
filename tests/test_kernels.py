"""Kernel correctness: Pallas kernels (interpret=True) and the flash
custom-VJP twins, swept over shapes/dtypes against the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.flash import flash_global, flash_local
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.rglru.kernel import rglru_scan_pallas
from repro.kernels.rglru.ops import rglru_scan
from repro.kernels.rglru.ref import rglru_scan_reference
from repro.kernels.rwkv6.kernel import rwkv6_pallas
from repro.kernels.rwkv6.ops import rwkv6_mix
from repro.kernels.rwkv6.ref import rwkv6_reference


def _qkv(key, b, sq, sk, h, kvh, d, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, sq, h, d)).astype(dtype)
    k = jax.random.normal(kk, (b, sk, kvh, d)).astype(dtype)
    v = jax.random.normal(kv, (b, sk, kvh, d)).astype(dtype)
    return q, k, v


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# Pallas flash attention (interpret mode) vs oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "sq,h,kvh,d,causal,window,softcap",
    [
        (128, 4, 4, 64, True, 0, 0.0),
        (128, 4, 2, 64, True, 0, 50.0),
        (256, 4, 1, 32, True, 64, 0.0),     # sliding window + GQA
        (128, 2, 2, 128, False, 0, 0.0),    # bidirectional (encoder)
    ],
)
def test_pallas_flash_vs_ref(sq, h, kvh, d, causal, window, softcap, dtype):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, sq, sq, h, kvh, d, dtype)
    got, lse = flash_attention_pallas(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal, window=window, softcap=softcap,
        block_q=64, block_kv=64, interpret=True,
    )
    got = got.transpose(0, 2, 1, 3)
    want = attention_reference(q, k, v, causal=causal, window=window,
                               softcap=softcap)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )
    np.testing.assert_allclose(
        lse, _reference_lse(q, k, causal=causal, window=window,
                            softcap=softcap),
        atol=1e-4, rtol=1e-5,
    )


def _reference_lse(q, k, *, causal, window, softcap):
    """Row log-sum-exp of the masked scaled scores, [B, H, Sq]."""
    b, sq, h, d = q.shape
    kf = jnp.repeat(k.astype(jnp.float32), h // k.shape[2], axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kf) / np.sqrt(d)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qpos, kpos = jnp.arange(sq)[:, None], jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    return jax.nn.logsumexp(jnp.where(mask, s, -1e30), axis=-1)


# ---------------------------------------------------------------------------
# Pallas path of flash_attention: custom VJP (interpret mode) vs oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "sq,h,kvh,d,window,softcap",
    [
        (128, 4, 2, 32, 0, 0.0),       # causal GQA
        (128, 4, 1, 32, 0, 30.0),      # causal GQA + softcap
        (128, 4, 2, 32, 48, 0.0),      # sliding window + GQA
    ],
)
def test_pallas_attention_grad_matches_reference(sq, h, kvh, d, window,
                                                  softcap):
    q, k, v = _qkv(jax.random.PRNGKey(3), 2, sq, sq, h, kvh, d, jnp.float32)
    f_new = lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
        q, k, v, causal=True, window=window, softcap=softcap,
        impl="pallas", block_q=64, block_kv=64, interpret=True)))
    f_ref = lambda q, k, v: jnp.sum(jnp.sin(attention_reference(
        q, k, v, causal=True, window=window, softcap=softcap)))
    np.testing.assert_allclose(f_new(q, k, v), f_ref(q, k, v), rtol=1e-5)
    g_new = jax.grad(f_new, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_new, g_ref):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_pallas_attention_rejects_q_offset():
    q, k, v = _qkv(jax.random.PRNGKey(4), 1, 64, 64, 2, 2, 32, jnp.float32)
    with pytest.raises(ValueError, match="q_offset"):
        flash_attention(q, k, v, causal=True, q_offset=8, impl="pallas",
                        block_q=64, block_kv=64, interpret=True)


# ---------------------------------------------------------------------------
# flash custom-VJP twins vs oracle (values AND gradients)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize(
    "sq,sk,h,kvh,d,causal,softcap,qoff,chunk",
    [
        (64, 64, 4, 2, 32, True, 0.0, 0, 16),
        (64, 64, 4, 4, 32, True, 50.0, 0, 32),
        (48, 80, 4, 1, 16, False, 0.0, 0, 32),
        (37, 53, 2, 2, 8, True, 0.0, 16, 16),   # ragged + offset
    ],
)
def test_flash_global_value_and_grad(sq, sk, h, kvh, d, causal, softcap,
                                     qoff, chunk, dtype):
    q, k, v = _qkv(jax.random.PRNGKey(1), 2, sq, sk, h, kvh, d, dtype)

    f_new = lambda q, k, v: jnp.sum(
        jnp.sin(flash_global(q, k, v, causal, softcap, qoff, chunk))
    )
    f_ref = lambda q, k, v: jnp.sum(
        jnp.sin(attention_reference(q, k, v, causal=causal, softcap=softcap,
                                    q_offset=qoff))
    )
    np.testing.assert_allclose(f_new(q, k, v), f_ref(q, k, v), rtol=1e-5)
    g_new = jax.grad(f_new, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_new, g_ref):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize(
    "sq,h,kvh,d,window,softcap,bq",
    [
        (128, 4, 2, 32, 32, 0.0, 32),
        (100, 4, 4, 16, 48, 30.0, 32),   # ragged q + softcap
        (64, 2, 1, 8, 16, 0.0, 64),
    ],
)
def test_flash_local_value_and_grad(sq, h, kvh, d, window, softcap, bq):
    q, k, v = _qkv(jax.random.PRNGKey(2), 2, sq, sq, h, kvh, d, jnp.float32)
    f_new = lambda q, k, v: jnp.sum(
        jnp.sin(flash_local(q, k, v, window, softcap, 0, bq))
    )
    f_ref = lambda q, k, v: jnp.sum(
        jnp.sin(attention_reference(q, k, v, causal=True, window=window,
                                    softcap=softcap))
    )
    np.testing.assert_allclose(f_new(q, k, v), f_ref(q, k, v), rtol=1e-5)
    g_new = jax.grad(f_new, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_new, g_ref):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# RG-LRU Pallas kernel (interpret) vs oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,w", [(2, 64, 128), (1, 128, 256), (3, 33, 128)])
def test_rglru_pallas_vs_ref(b, s, w, dtype):
    key = jax.random.PRNGKey(3)
    bt = jax.random.normal(key, (b, s, w)).astype(dtype)
    a = jax.random.uniform(jax.random.fold_in(key, 1), (b, s, w),
                           minval=0.1, maxval=0.95).astype(dtype)
    h0 = jax.random.normal(jax.random.fold_in(key, 2), (b, w)).astype(dtype)
    got_y, got_h = rglru_scan_pallas(bt, a, h0, interpret=True)
    want_y, want_h = rglru_scan_reference(bt, a, h0)
    tol = TOL[dtype] * 10  # sequential accumulation over s steps
    np.testing.assert_allclose(got_y.astype(jnp.float32),
                               want_y.astype(jnp.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(got_h.astype(jnp.float32),
                               want_h.astype(jnp.float32), atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# RWKV-6 Pallas kernel (interpret) vs oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,s,h,d", [(2, 32, 2, 16), (1, 64, 4, 32)])
def test_rwkv6_pallas_vs_ref(b, s, h, d):
    key = jax.random.PRNGKey(4)
    mk = lambda i: jax.random.normal(jax.random.fold_in(key, i), (b, s, h, d))
    r, k, v = mk(0), mk(1), mk(2)
    w = jax.nn.sigmoid(mk(3)) * 0.9 + 0.05      # decay in (0, 1)
    u = jax.random.normal(jax.random.fold_in(key, 5), (h, d))
    s0 = jax.random.normal(jax.random.fold_in(key, 6), (b, h, d, d))
    # pallas kernel runs on [B*H, S, D]-flattened operands
    flat = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    uf = jnp.broadcast_to(u[None], (b, h, d)).reshape(b * h, d)
    s0f = s0.reshape(b * h, d, d)
    got_y, got_s = rwkv6_pallas(
        flat(r), flat(k), flat(v), flat(w), uf, s0f,
        chunk=16, interpret=True,
    )
    want_y, want_s = rwkv6_reference(r, k, v, w, u, s0)
    want_yf = flat(want_y)
    want_sf = want_s.reshape(b * h, d, d)
    np.testing.assert_allclose(got_y, want_yf, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(got_s, want_sf, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# ops-level dispatchers (associative scan path, chunked rwkv path)
# ---------------------------------------------------------------------------
def test_rglru_ops_associative_matches_ref():
    key = jax.random.PRNGKey(7)
    bt = jax.random.normal(key, (2, 48, 64))
    a = jax.random.uniform(jax.random.fold_in(key, 1), (2, 48, 64),
                           minval=0.1, maxval=0.95)
    h0 = jax.random.normal(jax.random.fold_in(key, 2), (2, 64))
    got_y, got_h = rglru_scan(bt, a, h0, impl="associative")
    want_y, want_h = rglru_scan_reference(bt, a, h0)
    np.testing.assert_allclose(got_y, want_y, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got_h, want_h, atol=1e-5, rtol=1e-5)


def test_rwkv6_ops_chunked_matches_ref():
    key = jax.random.PRNGKey(8)
    b, s, h, d = 2, 32, 2, 16
    mk = lambda i: jax.random.normal(jax.random.fold_in(key, i), (b, s, h, d))
    r, k, v = mk(0), mk(1), mk(2)
    w = jax.nn.sigmoid(mk(3)) * 0.9 + 0.05
    u = jax.random.normal(jax.random.fold_in(key, 5), (h, d))
    got_y, got_s = rwkv6_mix(r, k, v, w, u, None, impl="chunked")
    want_y, want_s = rwkv6_reference(r, k, v, w, u, None)
    np.testing.assert_allclose(got_y, want_y, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(got_s, want_s, atol=1e-4, rtol=1e-4)
