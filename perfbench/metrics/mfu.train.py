"""Model FLOPs of the traced training steps (forward and backward,
recompute excluded; ``perfbench/flops.py``) over the traced stretch,
as a share of the chips' bf16 peak. Moves ``train_tokens_per_s``."""
from perfbench import flops


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["window_s"]:
        return None
    work = flops.train_flops_per_token(ctx["cfg"], ctx["seq"]) \
        * ctx["tokens_per_step"] * ctx["steps_traced"]
    return 100.0 * work / tr["window_s"] / (ctx["peaks"]["bf16_flops"] * ctx["chips"])
