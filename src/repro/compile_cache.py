"""JAX's persistent compilation cache, placed from outside the program.

The entry point that drives the simulator on the chip (``chip_smoke.py``)
calls :func:`use_compile_cache` first; importing this module, or any
other, changes nothing, and tests never call it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> None:
    """Keep compiled programs across processes.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this does nothing.  Otherwise the cache lives in ``.jax_cache/`` at
    the repository root: a fixed path, because the path is part of what
    makes a later process find an entry again."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
