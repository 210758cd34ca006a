"""Forward model FLOPs of every prompt and output token the engine
processed in the traced stretch (each token at its own causal context;
``perfbench/flops.py``), over the stretch, as a share of the chips'
bf16 peak. Moves ``serve_tokens_per_s``."""
from perfbench import flops


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["window_s"] or not ctx.get("decode_ctx"):
        return None
    cfg = ctx["cfg"]
    work = sum(flops.forward_flops(cfg, n) for lens in ctx["decode_ctx"] for n in lens)
    work += sum(flops.prompt_flops(cfg, s, c) for s, c in ctx["chunks"])
    return 100.0 * work / tr["window_s"] / (ctx["peaks"]["bf16_flops"] * ctx["chips"])
