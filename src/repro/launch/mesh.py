"""Production meshes.

Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model) — the ``pod`` axis
extends data parallelism across the inter-pod network (DCN/Ethernet — the
fabric the paper's transport runs on); gradient all-reduce becomes
hierarchical: reduce-scatter over ICI inside the pod, then the small
cross-pod exchange rides SMaRTT.

Defined as a *function* so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh for CPU smoke paths."""
    return _auto_mesh((1, 1), ("data", "model"))


def _auto_mesh(shape, axes):
    # the models place activations with ``with_sharding_constraint`` and
    # let the partitioner choose the rest, which needs Auto axes
    # (``jax.make_mesh`` defaults to Explicit ones)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
