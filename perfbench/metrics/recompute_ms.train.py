"""Device milliseconds per traced training step spent recomputing the
forward of remat'd layers in the backward pass (ops whose ``op_name``
holds ``rematted_computation``; this cuts across the scoped layers), by
``perfbench/scopes.py``. Read only where the program names its scopes.
Moves ``train_tokens_per_s``."""
import os

from perfbench import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def read(ctx):
    s = scopes.of_run(ctx, ROOT)
    return None if s is None else 1000.0 * s["recompute_s"] / ctx["steps_traced"]
