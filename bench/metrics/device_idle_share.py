"""Share of the traced window in which no op runs on a chip, averaged
over the chips."""
from bench import trace_reduce as tr


def read(ctx):
    t, lo, hi = ctx["trace"], ctx["lo"], ctx["hi"]
    busy = [tr.total(tr.busy(t, d, lo, hi)) for d in ctx["devices"]]
    if not busy:
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
