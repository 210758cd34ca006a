"""Device milliseconds per traced training step in the MLP layers
(scope ``sct.mlp``: pre-MLP norm, the spectral SwiGLU MLP, residual
add; forward, backward and recompute), by ``perfbench/scopes.py``.
Moves ``train_tokens_per_s``."""
import os

from perfbench import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def read(ctx):
    return scopes.scope_ms(ctx, ROOT, "sct.mlp")
