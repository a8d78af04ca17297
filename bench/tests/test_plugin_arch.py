"""A new architecture is a configuration file and a module: the tests-only
module ``data/plugin_arch.py``, named by ``data/plugin-mla-moe.json``
alone, goes through every shared part of the harness (``spec.validate``,
``run.program_config``, ``weights.init_params``, ``reference.train``,
the ``flops`` counts and the tests' ``tiny``).  Its model has what the
dense module does not know: a leading ``prefix`` layer, an untied head
and one-dimensional latent norm weights.  And no shared file of the
harness names a model key."""
import copy
import json
import math

import numpy as np
import pytest

from conftest import DATA, ROOT, tiny

from bench import flops, reference, run, spec, weights
from bench.archs import dense

CONFIG = DATA / "plugin-mla-moe.json"
SEED = 3000000019
SEQ = 32
SHARED = ["run.py", "reference.py", "weights.py", "flops.py", "compare.py",
          "control.py", "spec.py", "tests/conftest.py"]
DENSE_KEYS = ["num_key_value_heads", "hidden_act", "sliding_window",
              "qk_norm", "intermediate_size", "norm_epsilon", "rms_norm_eps"]


@pytest.fixture(scope="module")
def model():
    """(the file's configuration, at test widths, its module, the
    program's parameter struct)."""
    import jax

    from repro.models.model import init_params

    c = json.loads(CONFIG.read_text())
    t = tiny(c, SEQ)
    acfg = run.program_config(t)
    struct = jax.eval_shape(lambda k: init_params(k, acfg),
                            jax.random.PRNGKey(0))
    return c, t, spec.arch(c), struct


def test_validate_takes_the_configuration():
    man = copy.deepcopy(spec.manifest())
    man["configs"].append({
        "name": "plugin-mla-moe", "source": "tests only",
        "file": str(CONFIG.relative_to(ROOT)), "reduced": [],
        "why": "a toy of latent attention and sparse experts"})
    assert spec.validate(man) == []


def test_tiny_is_the_modules(model):
    c, t, mod, _ = model
    assert t == mod.tiny(c, SEQ) != c


def test_program_config_and_tree(model):
    _, t, mod, struct = model
    acfg = run.program_config(t)
    assert acfg.moe.first_k_dense == 1 and not acfg.tie_embeddings
    assert len(struct["prefix"]) == 1 and "head" in struct
    assert struct["prefix"][0]["mixer"]["kv_norm"].shape == (32,)


def test_seeded_weights_by_the_modules_kinds(model):
    import jax

    _, t, _, struct = model
    params = jax.jit(lambda k: weights.init_params(struct, t, k))(
        weights.base_key(SEED))
    kv_norm = np.asarray(params["prefix"][0]["mixer"]["kv_norm"])
    assert 0 < np.std(kv_norm) < 0.05          # an RMSNorm weight's draw
    head = np.asarray(params["head"]["w"])     # a matrix over d_model
    assert np.std(head) == pytest.approx(1 / math.sqrt(64), rel=0.1)
    with pytest.raises(ValueError, match="kv_norm"):
        dense.leaf_kind(("prefix", "0", "mixer", "kv_norm"), 1, t)


def test_reference_trains_two_steps(model):
    import jax

    _, t, _, struct = model
    key = weights.base_key(SEED)
    make = lambda: jax.jit(  # noqa: E731
        lambda k: weights.init_params(struct, t, k))(key)
    pool = jax.jit(lambda k: weights.token_pool(
        k, 4, SEQ, t["vocab_size"], 1.1, 0.7))(key)
    rows = [[pool[0], pool[1]], [pool[2], pool[3]]]
    opt = spec.traffic("1chip.s4096")["optimizer"]
    out = reference.train(make, rows, [((0,), 1), ((1,), 1)], t, opt)
    assert len(out["losses"]) == 2
    assert all(math.isfinite(x) for x in out["losses"])
    names = reference.leaf_names(struct)
    grads = dict(zip(names, out["first_grad"]))
    assert len(grads) == len(jax.tree.leaves(struct))
    for leaf in ("head/w", "prefix/0/mixer/kv_norm",
                 "stack/0/ffn/experts/down", "stack/0/ffn/router"):
        assert np.linalg.norm(grads[leaf]) > 0, leaf
    assert np.all(np.asarray(out["delta"]) > 0)


def test_counts_are_the_modules(model):
    import jax

    _, t, mod, struct = model
    assert flops.total_params(t) == mod.total_params(t) == sum(
        int(np.prod(s.shape)) for s in jax.tree.leaves(struct))
    assert flops.model_flops_per_token(t, SEQ) == \
        mod.model_flops_per_token(t, SEQ) > 0
    assert flops.attn_fwd_cost(t, SEQ, 2) == mod.attn_fwd_cost(t, SEQ, 2)


@pytest.mark.parametrize("path", SHARED)
def test_shared_file_names_no_model(path):
    text = (ROOT / "bench" / path).read_text()
    assert "plugin" not in text
    assert [k for k in DENSE_KEYS if k in text] == []
