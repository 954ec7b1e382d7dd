"""Pallas kernels for the simulator's hot loop (and the model stack).

Each kernel package holds ``ref.py`` (the pure-jnp oracle), ``kernel.py``
(the blocked Pallas program) and ``ops.py`` (backend dispatch).  Every
kernel entry point takes ``interpret`` without a default; the value comes
from :func:`interpret_mode`, the one place it is decided.
"""

from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Run Pallas kernels in the interpreter everywhere but on a TPU,
    where they compile through Mosaic."""
    return jax.default_backend() != "tpu"
