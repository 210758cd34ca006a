"""Share of the traced stretch of a training window in which no
operation ran on the device (``perfbench/trace.py``). Moves
``train_tokens_per_s``."""


def read(ctx):
    tr = ctx["trace"]
    return None if not tr or tr["idle_share"] is None else 100.0 * tr["idle_share"]
