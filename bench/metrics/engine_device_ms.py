"""Device time per step of the DeFT engine's own work: ops whose
innermost named scope is ``deft_grads``, ``deft_route`` or
``deft_update`` (train/runtime.py), collectives and the
``bucket_update`` kernel's instructions left out
(``bucket_update_roofline`` reads those); union over the ops, averaged
over the chips.  None for an engine that names no scope."""
from bench import engine_scopes


def read(ctx):
    return engine_scopes.engine_ms(ctx)
