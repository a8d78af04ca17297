"""Least time of the Pallas attention forward (``flash_attention_fwd``)
from its FLOPs and bytes by shape, over its device time; every call in
the window counts, the recomputed forward of the backward pass too."""
from bench import flops
from bench import trace_reduce as tr

KERNEL = "flash_attention_fwd"


def read(ctx):
    t, lo, hi = ctx["trace"], ctx["lo"], ctx["hi"]
    cost = flops.attn_fwd_cost(ctx["config"], ctx["traffic"]["seq"],
                               ctx["traffic"]["batch_per_chip"])
    least = flops.least_time_s(cost["flops"], cost["bytes"], ctx["peak"])
    shares = []
    for d in ctx["devices"]:
        calls, spent = tr.kernel_calls(t, d, KERNEL, lo, hi)
        if calls and spent > 0:
            shares.append(calls * least / spent)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
