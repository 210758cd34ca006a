"""Share of its roofline that the paged decode kernel
(``kernels/paged_decode.py``) reaches in the traced stretch: the least
time the chip needs for the bytes and FLOPs the kernel must spend in
every traced decode step (K/V of each decoding slot's live positions,
the queries and the outputs, over every layer; ``perfbench/flops.py``),
over the kernel's device time in the trace. Moves
``serve_tokens_per_s``."""
from perfbench import flops

# the decode program's only Mosaic kernel is the paged one; XLA's own
# custom calls carry other targets
KERNEL_TARGET = "custom-call tpu_custom_call"


def kernel_seconds(ops: dict) -> float:
    return sum(v for k, v in ops.items() if k.endswith(KERNEL_TARGET))


def read(ctx):
    tr = ctx["trace"]
    if not tr or not ctx.get("decode_ctx"):
        return None
    busy = kernel_seconds(tr["ops"])
    if busy <= 0:
        return None
    pk = ctx["peaks"]
    least = 0.0
    for lens in ctx["decode_ctx"]:
        f, b = flops.paged_decode_terms(ctx["cfg"], lens)
        least += max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"])
    return 100.0 * least / busy
