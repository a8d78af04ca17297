"""Device time per step of the ops under the named scope ``lm_head``
(the LM head and cross entropy, forward and backward, of
``models/model.py`` ``chunked_ce``), averaged over the chips.  None
where no op carries the scope: the whole-sequence loss path, or a
program that does not name it."""
from bench import trace_reduce as tr

SCOPE = "lm_head"


def read(ctx):
    t, lo, hi, scopes = ctx["trace"], ctx["lo"], ctx["hi"], ctx["scopes"]
    per_dev = []
    for d in ctx["devices"]:
        ops = tr.ops_matching(t, d, lambda o: SCOPE in scopes.get(o.name, ""))
        if ops:
            ivs = tr.clip(tr.union((o.start, o.end) for o in ops), lo, hi)
            per_dev.append(tr.total(ivs))
    if not per_dev:
        return None
    return sum(per_dev) / len(per_dev) * 1e-6 / ctx["steps"]
