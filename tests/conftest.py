"""Shared fixtures.  NOTE: no XLA_FLAGS device-count forcing here — tests
run with the real (single) CPU device; multi-device behaviour is covered
by the subprocess test in test_multidevice.py and by the dry-run."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import pytest

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def single_mesh():
    """1x1 (data, model) mesh — exercises the full pjit/shard_map machinery
    on one device (psum over a size-1 axis is an identity with the same
    graph structure)."""
    return jax.make_mesh(
        (1, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
