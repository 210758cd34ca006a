"""Names of the train step's device scopes and of the training host
spans, fixed strings shared by the code that records them and the code
that reads a profile.

Device scopes are ``jax.named_scope``s. Each sits where its work is
traced, so the optimized HLO's ``metadata={op_name=...}`` carries it on
the forward ops, on the backward ops (``transpose(...)``) and on the
ops a remat recomputes (``rematted_computation``). Where scopes nest,
the innermost ``sct.*`` component of an ``op_name`` names the op's
layer. Scopes change metadata only, never the compiled program.

Host spans are ``jax.profiler.TraceAnnotation``s on the profiler's
clock; every ``TrainLoop`` iteration is also a
``jax.profiler.StepTraceAnnotation`` named :data:`TRAIN_STEP`.
"""
from __future__ import annotations

import re

PREFIX = "sct."

# device scopes (jax.named_scope)
ATTENTION = "sct.attention"    # pre-attention norm, attention, residual add
MLP = "sct.mlp"                # pre-MLP norm, spectral/dense MLP or MoE, residual add
EMBED = "sct.embed"            # token embedding
HEAD = "sct.head"              # final norm, LM head, cross-entropy, aux-loss sum
OPTIMIZER = "sct.optimizer"    # schedule, clipping, AdamW, loss-scale unscale
RETRACTION = "sct.retraction"  # Stiefel retraction of every spectral U/V

DEVICE_SCOPES = (ATTENTION, MLP, EMBED, HEAD, OPTIMIZER, RETRACTION)

# host spans (jax.profiler.TraceAnnotation)
TRAIN_STEP = "sct.train"                   # StepTraceAnnotation of a TrainLoop step
DISPATCH = "sct.train.dispatch"            # call of the jitted step (enqueue only)
BATCH = "sct.train.batch"                  # next(batches)
LOSS_SYNC = "sct.train.loss_sync"          # wait for the loss, read it to the host
CHECKPOINT = "sct.train.checkpoint"        # device_get + save, and the final wait
RANK_RESIZE = "sct.train.rank_resize"      # rank controller consult (and resize)

HOST_SPANS = (DISPATCH, BATCH, LOSS_SYNC, CHECKPOINT, RANK_RESIZE)

REMAT_MARK = "rematted_computation"


_SCOPE = re.compile(r"(?<![\w.])" + re.escape(PREFIX) + r"[a-z_]+")


def scope_of(op_name: str):
    """The innermost ``sct.*`` scope in an HLO ``op_name``, or None. A
    scope at the top of a differentiated function shows inside the
    transform's name (``transpose(jvp(sct.head))/mul``), one inside a
    loop body as a path component of its own (``.../sct.mlp/dot``)."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def is_recomputed(op_name: str) -> bool:
    """Whether an HLO ``op_name`` belongs to a remat's recompute."""
    return REMAT_MARK in op_name
