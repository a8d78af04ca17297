"""Least time of the held experts' grouped matmuls over their device
time: the kernels a TPU compiles ``jax.lax.ragged_dot`` to (instructions
``ragged-dot-*``, the group metadata included), forward and backward,
in the traced steps.  The work is counted from the configuration and
the traffic alone by the architecture module's ``expert_mm_cost``
(``bench/archs/mla_moe.py``: rows T k held / E at a balanced load,
FLOPs 18 rows d de a layer, the held experts' weights read twice and
their gradients written once and the rows in and out, bf16).  None for
an architecture without the count or a program without the kernels."""
from bench import flops, spec
from bench import trace_reduce as tr

GROUPED = "ragged-dot"


def read(ctx):
    cost_of = getattr(spec.arch(ctx["config"]), "expert_mm_cost", None)
    if cost_of is None:
        return None
    t, lo, hi = ctx["trace"], ctx["lo"], ctx["hi"]
    cost = cost_of(ctx["config"], ctx["traffic"]["seq"],
                   ctx["traffic"]["batch_per_chip"])
    least = flops.least_time_s(cost["flops"], cost["bytes"], ctx["peak"])
    shares = []
    for d in ctx["devices"]:
        ops = [o for o in tr.ops_matching(
            t, d, lambda o: o.name.startswith(GROUPED)) if lo <= o.start < hi]
        spent = tr.total(tr.union((o.start, o.end) for o in ops)) * 1e-9
        if spent > 0:
            shares.append(ctx["steps"] * least / spent)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
