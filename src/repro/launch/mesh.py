"""Mesh construction: every mesh in the repo is built by :func:`make_mesh`.

Functions, not module-level constants — importing this module never
touches jax device state (required: smoke tests see 1 CPU device, only
dryrun.py forces 512 host devices).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """A mesh of ``shape`` named ``axes`` over the first ``prod(shape)``
    of ``devices`` (default: all visible devices), with every axis
    ``Auto``. ``jax.make_mesh`` defaults to ``Explicit`` axes, on which
    the sharding rules' ``with_sharding_constraint`` calls are refused;
    the partitioning here is written for the automatic partitioner."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (v5e pod); 2 pods = 512 chips multi-pod.
    Axis order puts 'model' innermost — ICI-contiguous for the TP
    collectives, with 'pod' outermost crossing the (slower) inter-pod
    links only for DP gradient all-reduces."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
