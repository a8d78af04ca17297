"""Model FLOPs of the traced steps over the chips' bf16 peak for the
traced window (``bench/flops.py``, counted by the configuration's
architecture module: for the dense block 6 x matmul parameters plus
attention over the visible keys, no recompute)."""
from bench import flops


def read(ctx):
    per_tok = flops.model_flops_per_token(ctx["config"], ctx["traffic"]["seq"])
    done = per_tok * ctx["tokens_per_step"] * ctx["steps"]
    avail = ctx["window_s"] * ctx["chips"] * ctx["peak"]["bf16_flops"]
    return 100.0 * done / avail
