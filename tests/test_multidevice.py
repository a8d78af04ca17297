"""True multi-device checks, run in a subprocess with 8 forced host
devices (the test process itself must keep the real single-device view —
see the dry-run instructions about not forcing device counts globally)."""
import os
import pathlib
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from repro.configs import get_config, reduce_for_smoke
from repro.core.bucket import BucketTimes
from repro.core.deft import solve_schedule
from repro.core.scheduler import SchedulerConfig
from repro.core.profiler import HardwareModel
from repro.data.pipeline import make_batch
from repro.models.model import loss_fn
from repro.optim.optimizers import adamw, apply_updates, init_opt_state
from repro.train import (DeftRuntime, assign_buckets, build_bucket_layout,
                         init_train_state, leaf_bucket_times)

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = reduce_for_smoke(get_config("qwen3-4b"))
opt = adamw(1e-3)
key = jax.random.PRNGKey(0)
probe = init_train_state(key, cfg, opt)
bucket_of, nb = assign_buckets(probe["params"], cfg, partition_elems=150_000)
hw = HardwareModel(dp_degree=4)
B, S = 8, 32
times = leaf_bucket_times(probe["params"], cfg, bucket_of, nb, hw, S, 2)
scale = 1.8 * (times.fwd_total + times.bwd_total) / times.comm_total
times = BucketTimes(times.fwd, times.bwd, tuple(c * scale for c in times.comm))
sched = solve_schedule(times, SchedulerConfig())
assert sched.updates_per_period < sched.period, "want a merging schedule"

# ---- fused DeftRuntime (production path): bucket-fused psums over the
# real 4-way data axis + donation, vs the grad-accumulation reference ----
layout = build_bucket_layout(probe["params"], bucket_of, nb)
ref_params = probe["params"]
ref_opt = init_opt_state(opt, ref_params)
zeros = lambda: jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             ref_params)
ref_cur, ref_fut = zeros(), zeros()
gfn = jax.jit(jax.grad(lambda p, b: loss_fn(p, cfg, b)[0]))

with mesh:
    rt = DeftRuntime(cfg, opt, sched, layout, mesh)
    state = rt.init_state(key)
    rt.compile(state, make_batch(cfg, 0, 0, B, S))
    for step in range(2 * sched.period):
        batch = make_batch(cfg, 0, step, B, S)
        ph = sched.phases[step % sched.period]
        prev = state
        state, m = rt.step(step, state, batch)
        assert all(x.is_deleted() for x in jax.tree.leaves(prev)), \
            "donation must hold on the multi-device mesh"
        g = gfn(ref_params, batch)
        if ph.rotate:
            gen = jax.tree.map(lambda a, b: a.astype(jnp.float32) + b, g,
                               ref_fut)
            ref_fut = jax.tree.map(jnp.zeros_like, ref_fut)
        else:
            ref_fut = jax.tree.map(lambda f, a: f + a.astype(jnp.float32),
                                   ref_fut, g)
            gen = None
        if ph.do_update:
            src = ref_cur if ph.update_source == "cur" else gen
            ref_params, ref_opt = apply_updates(
                opt, ref_params, src, ref_opt, grad_scale=1.0 / ph.update_k)
            ref_cur = gen if ph.update_source == "cur" else \
                jax.tree.map(jnp.zeros_like, ref_cur)
        elif ph.rotate:
            ref_cur = gen
        diff = max(float(jnp.max(jnp.abs(a - b)))
                   for a, b in zip(jax.tree.leaves(rt.params_tree(state)),
                                   jax.tree.leaves(ref_params)))
        assert diff < 1e-4, f"step {step}: diverged by {diff}"

# ---- DeFT-RS (manual over 'pod', FSDP arch) lowers + runs at small scale --
mesh_rs = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                        axis_types=(jax.sharding.AxisType.Auto,) * 3)
cfg_rs = reduce_for_smoke(get_config("deepseek-v2-236b"))
probe_rs = init_train_state(jax.random.PRNGKey(5), cfg_rs, opt)
bo_rs, nb_rs = assign_buckets(probe_rs["params"], cfg_rs,
                              partition_elems=150_000)
t_rs = leaf_bucket_times(probe_rs["params"], cfg_rs, bo_rs, nb_rs,
                         HardwareModel(dp_degree=2), 32, 4)
t_rs = BucketTimes(t_rs.fwd, t_rs.bwd,
                   tuple(c * 50 for c in t_rs.comm))
sched_rs = solve_schedule(t_rs, SchedulerConfig())
# sharded flat engine (the fsdp default): layout split into 2 spans
# to match the mesh's 2-way 'data' axis
lay_rs = build_bucket_layout(probe_rs["params"], bo_rs, nb_rs,
                             shard_count=2)
with mesh_rs:
    rt_rs = DeftRuntime(cfg_rs, opt, sched_rs, lay_rs, mesh_rs, fsdp=True)
    assert rt_rs.flat_state, "fsdp now defaults to the sharded engine"
    state_rs = rt_rs.init_state(jax.random.PRNGKey(5))
    for step in range(min(sched_rs.period + 1, 4)):
        b_rs = make_batch(cfg_rs, 0, step, 8, 32)
        state_rs, m_rs = rt_rs.step(step, state_rs, b_rs)
        assert jnp.isfinite(m_rs["loss"])
# the tree-state RS path (flat_state=False) stays available and is
# exercised against the flat engine in test_flat_fsdp.py

# ---- sharded flash-decode (distributed softmax) vs oracle ----
import numpy as np
from repro.kernels.flash_attention.sharded_decode import sharded_flash_decode
from repro.kernels.flash_attention.ref import attention_reference
mesh2 = jax.make_mesh((2, 4), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)
key = jax.random.PRNGKey(3)
q = jax.random.normal(key, (4, 1, 8, 16))
k = jax.random.normal(jax.random.fold_in(key, 1), (4, 64, 2, 16))
v = jax.random.normal(jax.random.fold_in(key, 2), (4, 64, 2, 16))
length = jnp.asarray([13, 64, 1, 40], jnp.int32)
with jax.set_mesh(mesh2):
    out = jax.jit(
        lambda q, k, v, l: sharded_flash_decode(q, k, v, l, softcap=30.0)
    )(q, k, v, length)
want = attention_reference(q, k, v, causal=False, softcap=30.0,
                           kv_length=length)
np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                           atol=2e-5, rtol=2e-5)
print("MULTIDEVICE_OK")
"""


def _run(tmp_path, script_text: str, timeout: int) -> str:
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    script = tmp_path / "run.py"
    script.write_text(script_text)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, str(script), src],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


@pytest.mark.slow
@pytest.mark.multidevice
def test_deft_equivalence_on_8_devices(tmp_path):
    assert "MULTIDEVICE_OK" in _run(tmp_path, _SCRIPT, 900)


# A plain jit over several devices (the DDP step's form) cannot partition
# a Mosaic kernel: the Pallas attention path runs per shard in a
# shard_map, batch over 'data', heads over 'model' when q and kv heads
# both divide it (else replicated).  Interpret mode on forced host
# devices checks the split arithmetic against the oracle.
_PALLAS_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_reference
from repro.sharding import logical_rules, rules_pjit

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
for kvh in (2, 1):
    key = jax.random.PRNGKey(kvh)
    q = jax.random.normal(key, (4, 128, 4, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (4, 128, kvh, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (4, 128, kvh, 32))

    def f_new(q, k, v):
        with logical_rules(rules_pjit(False, False)):
            return jnp.sum(jnp.sin(flash_attention(
                q, k, v, causal=True, impl="pallas", block_q=64,
                block_kv=64, interpret=True)))

    f_ref = lambda q, k, v: jnp.sum(jnp.sin(attention_reference(
        q, k, v, causal=True)))
    with jax.set_mesh(mesh):
        placed = jax.device_put((q, k, v),
                                NamedSharding(mesh, P("data")))
        step = jax.jit(jax.value_and_grad(f_new, argnums=(0, 1, 2)))
        assert "shard_map" in str(step.trace(*placed).jaxpr)
        got, g_new = step(*placed)
    want, g_ref = jax.value_and_grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(g_new, g_ref):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
print("PALLAS_SHARDED_OK")
"""


@pytest.mark.multidevice
def test_pallas_attention_runs_per_shard_under_plain_jit(tmp_path):
    assert "PALLAS_SHARDED_OK" in _run(tmp_path, _PALLAS_SHARDED_SCRIPT, 300)


# The chunked LM head's backward under a plain jit with the vocabulary
# split over 'model' (the DDP step's form): each vocabulary slice takes
# the same rows of every shard, so GSPMD moves no table rows between
# chips (no all-gather) and loss and gradients match the whole-sequence
# loss.  700 rows: 350 a shard, two 128-row slices and a 94-row tail.
_HEAD_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, sys.argv[1])
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, reduce_for_smoke
from repro.data.pipeline import make_batch
from repro.models.model import init_params, loss_fn
from repro.sharding import logical_rules, rules_pjit
from repro.sharding.specs import param_rules, spec_tree

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
for name in ("gemma2-2b", "deepseek-7b"):
    cfg = dataclasses.replace(reduce_for_smoke(get_config(name)),
                              vocab_size=700)
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg, 0, 0, 4, 32)
    grads = {}
    with jax.set_mesh(mesh):
        specs = spec_tree(params, param_rules(cfg.name, False, "tp"), mesh)
        placed = jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            params, specs)
        b = jax.tree.map(
            lambda a: jax.device_put(a, NamedSharding(mesh, P("data"))), batch)
        for chunk in (0, 8):
            def f(p, b, chunk=chunk):
                with logical_rules(rules_pjit(False, False)):
                    return loss_fn(p, cfg, b, loss_chunk=chunk)[0]
            step = jax.jit(jax.value_and_grad(f))
            if chunk:
                hlo = step.lower(placed, b).compile().as_text()
                assert "all-gather" not in hlo, name
            grads[chunk] = step(placed, b)
    (l0, g0), (l8, g8) = grads[0], grads[8]
    np.testing.assert_allclose(l8, l0, rtol=1e-6)
    for a, c in zip(jax.tree.leaves(g8), jax.tree.leaves(g0)):
        np.testing.assert_allclose(a, c, atol=1e-5)
print("HEAD_SHARDED_OK")
"""


@pytest.mark.multidevice
def test_chunked_head_backward_stays_on_its_vocab_shard(tmp_path):
    assert "HEAD_SHARDED_OK" in _run(tmp_path, _HEAD_SHARDED_SCRIPT, 300)



# An expert-parallel layer under a plain jit (the DDP step's form):
# deepseek-v2-236b at smoke widths, 4 experts split over 'model' of a
# data 2 x model 2 mesh.  Each device runs its own experts on its own
# tokens in a shard_map, and loss and gradients equal the one-device
# computation.
_EXPERT_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, reduce_for_smoke
from repro.data.pipeline import make_batch
from repro.models.model import init_params, loss_fn
from repro.sharding import logical_rules, rules_pjit
from repro.sharding.specs import param_rules, spec_tree

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = reduce_for_smoke(get_config("deepseek-v2-236b"))
params = init_params(jax.random.PRNGKey(0), cfg)
batch = make_batch(cfg, 0, 0, 4, 32)
(want, parts), g_want = jax.value_and_grad(
    lambda p: loss_fn(p, cfg, batch), has_aux=True)(params)
with jax.set_mesh(mesh):
    specs = spec_tree(params, param_rules(cfg.name, False, "tp"), mesh)
    placed = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs)
    b = jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P("data"))), batch)

    def f(p, b):
        with logical_rules(rules_pjit(False, False)):
            return loss_fn(p, cfg, b)

    step = jax.jit(jax.value_and_grad(f, has_aux=True))
    assert "shard_map" in str(step.trace(placed, b).jaxpr)
    (got, got_parts), g_got = step(placed, b)
np.testing.assert_allclose(got, want, rtol=1e-6)
for k in ("moe_rows", "moe_dropped"):
    assert float(got_parts[k]) == float(parts[k]), k
# the most rows one expert takes on one device: half the tokens each
assert 0 < float(got_parts["moe_max_rows"]) <= float(parts["moe_max_rows"])
for a, c in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
    np.testing.assert_allclose(a, c, atol=2e-6)
print("EXPERT_SHARDED_OK")
"""


@pytest.mark.multidevice
def test_expert_layer_runs_per_expert_shard_under_plain_jit(tmp_path):
    assert "EXPERT_SHARDED_OK" in _run(tmp_path, _EXPERT_SHARDED_SCRIPT, 300)
