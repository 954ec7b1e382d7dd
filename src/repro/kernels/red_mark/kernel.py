"""Pallas TPU kernel: RED ECN dequeue-marking + trim admission.

The switch datapath of the paper (Sec. 2.1: RED with dequeue marking,
Sec. 3.3: trim-on-full).  At 51.2 Tb/s a switch marks/trims millions of
packets per millisecond; as with cc_update, the TPU-native formulation is a
vector sweep over all port queues: occupancy planes stream through VMEM in
(8, 128) tiles, the marking coin-flips come from the same splitmix32
counter hash the engine uses (deterministic, stateless).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 8
LANES = 128


def _kernel(scal_ref, qsz_ref, arr_ref, qidx_ref, mark_ref, admit_ref, trim_ref):
    cap, kmin, kmax, tick, salt = (scal_ref[0, i] for i in range(5))
    q_size = qsz_ref[...]
    arrivals = arr_ref[...]
    qf = q_size.astype(jnp.float32)
    p = jnp.clip((qf - kmin) / jnp.maximum(kmax - kmin, 1e-6), 0.0, 1.0)
    # splitmix32 coin flip — same hash lanes as the oracle, computed on the
    # *global* queue index plane so tiling never changes the decision
    from repro.netsim.hashing import uniform01
    u = uniform01(tick.astype(jnp.int32) * jnp.int32(131071) + qidx_ref[...],
                  salt.astype(jnp.int32))
    mark_ref[...] = ((u < p) & (q_size > 0)).astype(jnp.int32)
    space = jnp.maximum(cap.astype(jnp.int32) - q_size, 0)
    admit = jnp.minimum(arrivals, space)
    admit_ref[...] = admit
    trim_ref[...] = arrivals - admit


@functools.partial(jax.jit, static_argnames=("interpret",))
def red_mark(q_size, arrivals, cap, kmin, kmax, tick, salt, *,
             interpret: bool):
    """Blocked RED marking over all port queues.  Shapes: i32[Q] -> i32[Q]x3."""
    Q = q_size.shape[0]
    rows = max(1, -(-Q // LANES))
    rows_pad = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
    Qp = rows_pad * LANES

    def shape2d(x, fill=0):
        return jnp.pad(x, (0, Qp - Q), constant_values=fill).reshape(rows_pad, LANES)

    qidx = shape2d(jnp.arange(Q, dtype=jnp.int32))
    scal = jnp.stack([jnp.asarray(v, jnp.float32) for v in
                      (cap, kmin, kmax, tick, salt)]).reshape(1, 5)
    tile = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    outs = pl.pallas_call(
        _kernel,
        grid=(rows_pad // BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((1, 5), lambda i: (0, 0)), tile, tile, tile],
        out_specs=[tile] * 3,
        out_shape=[jax.ShapeDtypeStruct((rows_pad, LANES), jnp.int32)] * 3,
        interpret=interpret,
    )(scal, shape2d(q_size), shape2d(arrivals), qidx)
    mark, admit, trim = (o.reshape(-1)[:Q] for o in outs)
    return mark != 0, admit, trim
