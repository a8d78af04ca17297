"""The dense architecture module (bench/archs/dense.py) computes what the
harness computed before its dense code moved there.

``data/dense_golden.json`` was recorded from the harness as it stood
before the move, for the benchmark's Qwen3 configuration and the
StarCoder2 test configuration: the seeded weights and the reference's
loss and gradient at test widths (float32 and the fp8 control, all
targets and the first half), and the counts at published widths.  Every
array is held by a digest of its bytes and every number exactly, so
each must match to the bit.  Bit for bit holds on the CPU backend of
the pinned JAX at its default flags: a flag that picks another matrix
kernel (``--xla_cpu_multi_thread_eigen=false``) rounds the gradient
differently.
"""
import hashlib
import json

import numpy as np
import pytest

from conftest import DATA, tiny

from bench import flops, reference, run, spec, weights

GOLDEN = json.loads((DATA / "dense_golden.json").read_text())
SEED = 2147483659
SEQ = 64
CONFIGS = {
    "qwen3-4b-l1": lambda: spec.config("qwen3-4b-l1"),
    "starcoder2-7b-l1": lambda: json.loads(
        (DATA / "starcoder2-7b-l1.json").read_text()),
}


def _digest(x) -> str:
    a = np.ascontiguousarray(np.asarray(x))
    return (f"{a.dtype.str}{list(a.shape)}:"
            + hashlib.sha256(a.tobytes()).hexdigest())


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def case(request):
    """(name, configuration at test widths, parameter struct, seeded
    weights, tokens)."""
    import jax

    from repro.models.model import init_params

    c = tiny(CONFIGS[request.param](), SEQ)
    acfg = run.program_config(c)
    struct = jax.eval_shape(lambda k: init_params(k, acfg),
                            jax.random.PRNGKey(0))
    key = weights.base_key(SEED)
    params = jax.jit(lambda k: weights.init_params(struct, c, k))(key)
    tokens = jax.jit(lambda k: weights.token_pool(
        k, 1, SEQ, c["vocab_size"], 1.1, 0.7))(key)[0]
    return request.param, c, struct, params, tokens


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_published_counts(name):
    c = CONFIGS[name]()
    want = GOLDEN[name]["published"]
    assert flops.total_params(c) == want["total_params"]
    assert flops.model_flops_per_token(c, 4096) == \
        want["model_flops_per_token"]
    assert flops.attn_fwd_cost(c, 4096, 1) == want["attn_fwd_cost"]


def test_seeded_weights(case):
    name, _, struct, params, tokens = case
    import jax

    names = reference.leaf_names(struct)
    got = dict(zip(names, map(_digest, jax.tree.leaves(params))))
    assert got == GOLDEN[name]["init_params"]
    assert _digest(tokens) == GOLDEN[name]["tokens"]


@pytest.mark.parametrize("precision", ["f32", "fp8"])
@pytest.mark.parametrize("positions", [None, 31])
def test_row_loss_and_gradient(case, precision, positions):
    import jax

    name, c, struct, params, tokens = case
    f = jax.jit(jax.value_and_grad(lambda p, x: reference.row_loss(
        p, x, c, precision, block=16, positions=positions)))
    loss, grad = f(params, tokens)
    want = GOLDEN[name][f"{precision}.positions={positions}"]
    assert float(loss) == want["loss"]
    names = reference.leaf_names(struct)
    assert dict(zip(names, map(_digest, jax.tree.leaves(grad)))) == \
        want["grad"]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_total_params_is_the_programs_tree(name):
    import jax

    from repro.models.model import init_params

    c = CONFIGS[name]()
    acfg = run.program_config(c)
    struct = jax.eval_shape(lambda k: init_params(k, acfg),
                            jax.random.PRNGKey(0))
    assert flops.total_params(c) == sum(
        int(np.prod(s.shape)) for s in jax.tree.leaves(struct))
