"""Mean host milliseconds of the program's call of the jitted train
step (span ``sct.train.dispatch`` in ``Trainer.step``: enqueue only) in
the traced stretch, by ``perfbench/scopes.py``. Moves
``train_tokens_per_s``."""
import os

from perfbench import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def read(ctx):
    s = scopes.of_run(ctx, ROOT)
    spans = s["program_spans"].get("sct.train.dispatch") if s else None
    return 1000.0 * sum(spans) / len(spans) if spans else None
