"""Device milliseconds per traced training step in the embedding, the
LM head and the loss (scopes ``sct.embed`` and ``sct.head``), by
``perfbench/scopes.py``. Moves ``train_tokens_per_s``."""
import os

from perfbench import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def read(ctx):
    return scopes.scope_ms(ctx, ROOT, "sct.embed", "sct.head")
