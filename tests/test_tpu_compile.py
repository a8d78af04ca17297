"""Compile-only rehearsals of the main path's TPU kernels for a described
(not attached) v5e chip, at qwen3-4b's published widths: what the chip's
compiler would refuse (tiling, VMEM, aliasing) fails here at no chip
time.  Nothing runs, so nothing here measures a time or checks a value.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker given
this file loads the TPU compiler."""
from __future__ import annotations

import dataclasses
import functools
import re

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


def test_flat_phase_names_buckets_links_and_update_on_four_chips(topo):
    """A four-chip phase of the replicated flat engine at test widths,
    with a plan that uses both links: every collective the compiled
    program holds, rewritten or combined by XLA or not, names the sync
    scope of its buckets on their planned link and generation (or the
    metrics psum); every synced bucket is named; every bucket_update
    kernel runs under ``deft_update``."""
    from jax.sharding import AxisType

    from repro.configs import get_config
    from repro.core.scheduler import DeftSchedule, PhaseSpec
    from repro.data.pipeline import batch_spec
    from repro.models.model import init_params
    from repro.obs.hlo_scopes import METRICS_SCOPE, innermost, parse_sync
    from repro.train import assign_buckets, build_bucket_layout
    from repro.train.runtime import DeftRuntime, RuntimeConfig

    cfg = dataclasses.replace(
        get_config("qwen3-4b"), n_layers=2, d_model=128, n_heads=4,
        n_kv_heads=2, head_dim=32, d_ff=256, vocab_size=1024)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    bucket_of, nb = assign_buckets(params, cfg, partition_elems=60_000)
    layout = build_bucket_layout(params, bucket_of, nb)
    assert nb >= 4
    # the four-chip cell's form: the oldest bucket syncs its older
    # generation, the rest this step's; links alternate
    phase = PhaseSpec(
        route_new=("current",) + ("sync",) * (nb - 1),
        sync_cur=(True,) + (False,) * (nb - 1),
        secondary=tuple(b % 3 != 1 for b in range(nb)),
        rotate=True, do_update=True, update_k=1, update_source="cur")
    schedule = DeftSchedule(plans=(), phases=(phase,), period=1,
                            updates_per_period=1, batch_size_sequence=(1,))
    mesh = jax.sharding.Mesh(np.array(topo.devices).reshape(4, 1),
                             ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
    rt = DeftRuntime(cfg, adamw(3e-4), schedule, layout, mesh,
                     config=RuntimeConfig(compute_dtype=jnp.bfloat16,
                                          update_impl="pallas"))
    rep = NamedSharding(mesh, PartitionSpec())
    split = NamedSharding(mesh, PartitionSpec("data"))

    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    bufs = tuple(sds((n,), jnp.float32, rep) for n in layout.buf_sizes)
    accs = tuple(sds((4, n), jnp.float32, split) for n in layout.buf_sizes)
    state = {"pbuf": bufs, "opt": {"step": sds((), jnp.int32, rep),
                                   "m": bufs, "v": bufs},
             "cur": accs, "fut": accs}
    batch = jax.tree.map(lambda s: sds(s.shape, s.dtype, split),
                         batch_spec(cfg, 8, 128))
    rt.compile(state, batch)

    (scopes,) = rt.phase_collective_scopes()
    assert scopes
    named = set()
    for name, found in scopes.items():
        assert found, f"{name} names no scope"
        for scope in found - {METRICS_SCOPE}:
            kind, b, link, gen = parse_sync(scope)
            assert link == ("secondary" if phase.secondary[b]
                            else "primary"), (name, scope)
            assert gen == ("cur" if phase.sync_cur[b] else "new"), scope
            named.add(b)
    assert named == set(range(nb))
    assert any(METRICS_SCOPE in found for found in scopes.values())

    text = rt.phase_executable(0).as_text()
    kernels = re.findall(
        r"^\s*%(bucket_update[\w.-]*) = .*?custom_call_target="
        r"\"tpu_custom_call\".*?op_name=\"([^\"]*)\"", text, re.M)
    assert kernels and all(innermost(op) == "deft_update"
                           for _, op in kernels), kernels


def _replica_groups(line: str):
    """The device groups of one collective in compiled HLO text, in the
    iota form (``[2,2]<=[2,2]T(1,0)``) or the listed one."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\]"
                  r"(?:T\(([\d,]+)\))?", line)
    if m:
        ids = np.arange(4).reshape([int(x) for x in m[3].split(",")])
        if m[4]:
            ids = ids.transpose([int(x) for x in m[4].split(",")])
        return {frozenset(g) for g in
                ids.reshape(int(m[1]), int(m[2])).tolist()}
    m = re.search(r"replica_groups=\{((?:\{[\d,]+\},?)+)\}", line)
    return {frozenset(int(x) for x in g.split(","))
            for g in re.findall(r"\{([\d,]+)\}", m[1])}


def test_expert_weights_stay_on_their_model_shard_on_four_chips(topo):
    """The DDP step's form of an expert-parallel model (deepseek-v2-236b
    at smoke widths, one jit over data 2 x model 2, experts split over
    'model'): the compiled step gathers no tensor of the expert layer
    across 'model' — each chip runs its own experts on its own tokens
    and only activations are summed across the expert shards."""
    from jax.sharding import AxisType

    from repro.configs import get_config, reduce_for_smoke
    from repro.models.model import init_params, loss_fn
    from repro.sharding.specs import param_rules, spec_tree

    mesh = jax.sharding.Mesh(np.array(topo.devices).reshape(2, 2),
                             ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
    cfg = reduce_for_smoke(get_config("deepseek-v2-236b"))
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    specs = spec_tree(params, param_rules(cfg.name, False, "tp"), mesh)
    for w in jax.tree.leaves(specs["stack"][0]["ffn"]["experts"]):
        assert w[1] == "model"      # [layers, experts, ...]
    p_in = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        params, specs)
    tok = jax.ShapeDtypeStruct((4, 32), jnp.int32,
                               sharding=NamedSharding(mesh,
                                                      PartitionSpec("data")))

    def loss(p, b):
        with logical_rules(rules_pjit(False, False)):
            return loss_fn(p, cfg, b)[0]

    with jax.set_mesh(mesh):
        text = jax.jit(jax.grad(loss)).lower(
            p_in, {"tokens": tok, "labels": tok}).compile().as_text()
    # devices in mesh order: a 'model' group is a row of the mesh
    over_model = {frozenset({0, 1}), frozenset({2, 3})}
    gathers = [line for line in text.splitlines()
               if re.search(r"all-gather(-start)?\(", line)
               and re.search(r'op_name="[^"]*/moe/(?!moe_router)', line)]
    assert gathers      # the FSDP gathers over 'data' are there
    across = [line for line in gathers if _replica_groups(line) == over_model]
    assert not across, across[0][:300]
