"""HBM time of the fused AdamW update (``bucket_update``) from the bytes
it must move (f32 p, m, v, g read, p, m, v written, g written where the
update zeroes it) at the chip's bandwidth, over its device time."""
from bench import flops
from bench import trace_reduce as tr

KERNEL = "bucket_update"


def read(ctx):
    t, lo, hi = ctx["trace"], ctx["lo"], ctx["hi"]
    n = flops.total_params(ctx["config"])
    zeroing = ctx["zeroing_update_steps"]
    plain = ctx["update_steps"] - zeroing
    nbytes = (plain * flops.bucket_update_bytes(n, False)
              + zeroing * flops.bucket_update_bytes(n, True))
    least = nbytes / ctx["peak"]["hbm_bytes_per_s"]
    shares = []
    for d in ctx["devices"]:
        calls, spent = tr.kernel_calls(t, d, KERNEL, lo, hi)
        if calls and spent > 0 and least > 0:
            shares.append(least / spent)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
