"""The part of ``collective_ms`` in which no compute op runs on that
chip (a start or done that waits, a collective run alone), per step,
averaged over the chips."""
from bench import trace_reduce as tr


def read(ctx):
    t, lo, hi, hlo = ctx["trace"], ctx["lo"], ctx["hi"], ctx.get("hlo")
    per_dev = []
    for d in ctx["devices"]:
        coll = tr.collectives(t, d, lo, hi, hlo)
        if coll:
            per_dev.append(tr.total(tr.subtract(
                coll, tr.compute(t, d, lo, hi, hlo))))
    if not per_dev:
        return None
    return sum(per_dev) / len(per_dev) * 1e-6 / ctx["steps"]
