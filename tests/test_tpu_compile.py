"""Compile-only rehearsals of the main path's TPU kernels for a described
(not attached) v5e chip, at qwen3-4b's published widths: what the chip's
compiler would refuse (tiling, VMEM, aliasing) fails here at no chip
time.  Nothing runs, so nothing here measures a time or checks a value.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker given
this file loads the TPU compiler."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.kernels.bucket_update.kernel import bucket_update_pallas
from repro.kernels.flash_attention import flash_attention
from repro.kernels.quantize.kernel import (
    dequantize_int8_pallas,
    quantize_int8_pallas,
    stochastic_round_bf16_pallas,
)
from repro.optim.optimizers import adamw
from repro.sharding import logical_rules, rules_pjit

# qwen3-4b: 32 query heads, 8 KV heads of 128; one chip's batch 8 x 512
B, S, H, KV, D = 8, 512, 32, 8, 128
# one transformer layer's bucket of qwen3-4b (f32 elements)
BUCKET = 49_807_360


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _qkv(one_chip):
    return (_spec(one_chip, (B, S, H, D), jnp.bfloat16),
            _spec(one_chip, (B, S, KV, D), jnp.bfloat16),
            _spec(one_chip, (B, S, KV, D), jnp.bfloat16))


_attn = functools.partial(flash_attention, causal=True, impl="pallas")


def test_flash_attention_forward_compiles(one_chip):
    compiled = jax.jit(_attn).lower(*_qkv(one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "flash_attention_fwd" in text


def test_flash_attention_grad_compiles(one_chip):
    def loss(q, k, v):
        return jnp.sum(_attn(q, k, v).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_qkv(one_chip)).compile()
    text = compiled.as_text()
    assert "flash_attention_fwd" in text
    # the chosen backward is named, so it cannot be a silent fallback
    assert "flash_attention_bwd_blocked" in text


def test_flash_attention_grad_compiles_under_plain_jit_on_four_chips(topo):
    # the DDP step's form: one jit over a data=4 mesh, no shard_map
    mesh = jax.sharding.Mesh(
        np.array(topo.devices).reshape(4, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    split = NamedSharding(mesh, PartitionSpec("data"))
    qkv = [jax.ShapeDtypeStruct((4 * B,) + s.shape[1:], s.dtype,
                                sharding=split)
           for s in _qkv(SingleDeviceSharding(topo.devices[0]))]

    def loss(q, k, v):
        with logical_rules(rules_pjit(False, False)):
            return jnp.sum(_attn(q, k, v).astype(jnp.float32))

    with jax.set_mesh(mesh):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            *qkv).compile()
    assert "flash_attention_fwd" in compiled.as_text()


def test_bucket_update_aliases_in_place(one_chip):
    buf = _spec(one_chip, (BUCKET,), jnp.float32)
    scalars = _spec(one_chip, (1, 128), jnp.float32)

    def update(p, m, v, g, s):
        return bucket_update_pallas(adamw(1e-3), p, m, v, g, s,
                                    n_valid=BUCKET, uniform=(1.0, 0.01),
                                    zero_grads=True)

    compiled = jax.jit(update, donate_argnums=(0, 1, 2, 3)).lower(
        buf, buf, buf, buf, scalars).compile()
    assert "bucket_update" in compiled.as_text()
    # p, m, v and the zeroed accumulator are all updated in place
    assert compiled.memory_analysis().alias_size_in_bytes == 4 * 4 * BUCKET


def test_int8_quantize_roundtrip_compiles(one_chip):
    buf = _spec(one_chip, (BUCKET,), jnp.float32)

    def roundtrip(x):
        q, s = quantize_int8_pallas(x)
        return dequantize_int8_pallas(q, s)

    text = jax.jit(roundtrip).lower(buf).compile().as_text()
    assert "quantize_int8" in text and "dequantize_int8" in text


def test_stochastic_round_bf16_compiles(one_chip):
    buf = _spec(one_chip, (BUCKET,), jnp.float32)
    seed = _spec(one_chip, (), jnp.int32)
    compiled = jax.jit(stochastic_round_bf16_pallas).lower(buf, seed).compile()
    assert "stochastic_round_bf16" in compiled.as_text()
