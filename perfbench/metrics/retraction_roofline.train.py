"""Share of its roofline that the retraction of the spectral factors
(scope ``sct.retraction``) reaches in the traced training steps: the
least time the chip needs to read every U and V once and write it once
in fp32 (``perfbench/retraction_bytes.py``) at the HBM peak, over the
scope's device time per step (``perfbench/scopes.py``). Moves
``train_tokens_per_s``."""
import os

from perfbench import scopes
from perfbench.retraction_bytes import retraction_bytes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def read(ctx):
    ms = scopes.scope_ms(ctx, ROOT, "sct.retraction")
    if not ms:
        return None
    least_s = retraction_bytes(ctx["cfg"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1000.0)
