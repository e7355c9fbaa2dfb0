"""Mesh construction (TPU v5e).

``make_mesh`` is the one mesh constructor of the repo: every axis is
``AxisType.Auto``. ``jax.make_mesh`` alone makes Explicit axes, on which
``with_sharding_constraint`` refuses the train step's Megatron specs.

Axes (data, model): 'data' is the learner/chain axis (one SAFE learner
per data rank), 'model' the tensor-parallel axis. A leading 'pod' axis
is the hierarchical-federation axis (paper §5.10): intra-pod SAFE chains,
then a plain mean of the already-anonymized pod averages across pods.

A function, so importing this module never touches device state.
"""
from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Sequence | None = None) -> Mesh:
    """Mesh of ``shape`` over the first ``prod(shape)`` devices (or the
    given ``devices``, e.g. a described TPU topology), all axes Auto."""
    shape, axes = tuple(shape), tuple(axes)
    n = int(np.prod(shape))
    if devices is None:
        devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)}")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=list(devices)[:n])

