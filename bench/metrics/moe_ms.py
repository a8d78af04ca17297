"""Device time per step of the expert layers (``models/moe.py``), forward
and backward: the ops under the named scope ``moe`` (the router, the
dispatch, the experts and the combine) and the grouped matmuls of the
held experts, averaged over the chips.  A TPU compiles
``jax.lax.ragged_dot`` to kernels named ``ragged-dot-*`` whose op_name
no longer carries the caller's scope, so those are found by their
instruction name.  None where no op carries the scope."""
from bench import trace_reduce as tr

SCOPE = "moe"
GROUPED = "ragged-dot"


def in_scope(op_name: str) -> bool:
    return SCOPE in op_name.split("/")


def read(ctx):
    t, lo, hi, scopes = ctx["trace"], ctx["lo"], ctx["hi"], ctx["scopes"]
    per_dev = []
    for d in ctx["devices"]:
        ops = tr.ops_matching(t, d, lambda o: in_scope(scopes.get(o.name, "")))
        if ops:
            ops += tr.ops_matching(t, d, lambda o: o.name.startswith(GROUPED))
            ivs = tr.clip(tr.union((o.start, o.end) for o in ops), lo, hi)
            per_dev.append(tr.total(ivs))
    if not per_dev:
        return None
    return sum(per_dev) / len(per_dev) * 1e-6 / ctx["steps"]
