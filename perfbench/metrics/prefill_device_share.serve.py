"""Share of the traced stretch of a serving window that the device
spent in the prompt-chunk program (``models/decode.py:
prefill_chunk_lm_paged``). Moves ``serve_itl_p95_ms``: a decode step
that waits behind a chunk is a long inter-token gap.

The engine jits its chunk and decode steps as two lambdas, which the
trace names alike (``jit__lambda(<hash>)``); the decode program is the
one whose runs hold the paged kernel (a Mosaic ``tpu_custom_call``),
the chunk program the other."""

ENGINE_STEP = "jit__lambda("
KERNEL_TARGET = "tpu_custom_call"


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["window_s"] or not ctx.get("chunks"):
        return None
    kernels = sorted(s for name, s, _ in tr["custom_calls"] if KERNEL_TARGET in name)
    runs = {}
    for name, s, e in tr["module_events"]:
        if name.startswith(ENGINE_STEP):
            runs.setdefault(name, []).append((s, e))

    def holds_kernel(s, e):
        import bisect

        i = bisect.bisect_left(kernels, s)
        return i < len(kernels) and kernels[i] <= e

    chunk_s = sum((e - s) / 1e9 for iv in runs.values()
                  if not any(holds_kernel(s, e) for s, e in iv) for s, e in iv)
    return 100.0 * chunk_s / tr["window_s"] if chunk_s > 0 else None
