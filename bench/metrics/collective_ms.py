"""Device time per step in which a collective runs or is in flight:
collectives run alone, compute fusions that carry one, and the span
from each asynchronous collective's start to its done; averaged over
the chips."""
from bench import trace_reduce as tr


def read(ctx):
    t, lo, hi, hlo = ctx["trace"], ctx["lo"], ctx["hi"], ctx.get("hlo")
    per_dev = [tr.total(tr.collectives(t, d, lo, hi, hlo))
               for d in ctx["devices"]]
    if not any(per_dev):
        return None
    return sum(per_dev) / len(per_dev) * 1e-6 / ctx["steps"]
