"""Jit'd wrapper for the red_mark kernel."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.red_mark.kernel import red_mark


def red_mark_op(q_size, arrivals, *, cap: int, kmin: float, kmax: float,
                tick, salt: int = 0xECD):
    return red_mark(jnp.asarray(q_size, jnp.int32),
                    jnp.asarray(arrivals, jnp.int32),
                    cap, kmin, kmax, tick, salt, interpret=interpret_mode())
