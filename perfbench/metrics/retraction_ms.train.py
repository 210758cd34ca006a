"""Device milliseconds per traced training step in the Stiefel QR
retraction of the spectral factors (scope ``sct.retraction``), by
``perfbench/scopes.py``. Moves ``train_tokens_per_s``."""
import os

from perfbench import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def read(ctx):
    return scopes.scope_ms(ctx, ROOT, "sct.retraction")
