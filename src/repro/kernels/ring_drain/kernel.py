"""Pallas TPU kernel: packed sent-ring ACK/trim/timeout drain.

Phase 3's hot loop as a blocked vector program: the [NF, W] sent-ring
planes stream through VMEM in (8, W-padded) tiles together with one
[8, 128] lane-packed per-flow scalar tile each for the i32 event inputs
(has_ack / ack_seq / started) and the f32 timeout threshold; the tick
``t`` sits in SMEM.  The whole free/lose/timeout cascade plus the
per-flow reductions happen on-tile, on 2-D values only.  The kernel body
calls the shared jnp reference (``ref.drain_tile``) on the VMEM tiles —
the ``kernels/cc_update`` discipline — so kernel and oracle cannot
drift apart.  Padded rows/lanes hold zeros, which the reference leaves
inert (a zero state is never freed, lost, or timed out).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ring_drain import ref as R

BLOCK_ROWS = 8
LANES = 128

I32 = jnp.int32
F32 = jnp.float32


def _pad2(x, rows_pad: int, cols_pad: int):
    r, c = x.shape
    return jnp.pad(x, ((0, rows_pad - r), (0, cols_pad - c)))


def _kernel(t_ref, scal_i_ref, scal_f_ref, lbits_ref, bitmap_ref,
            s0_ref, s1_ref, s2_ref, state_ref, counts_ref,
            *, w: int, ww: int, maxw: int):
    si = scal_i_ref[...]
    state, n_to, spur, un = R.drain_tile(
        t_ref[0], scal_f_ref[:, 0:1], si[:, 2:3] == 1, si[:, 0:1] == 1,
        si[:, 1:2], lbits_ref[...], bitmap_ref[...],
        s0_ref[...], s1_ref[...], s2_ref[...], w=w, ww=ww, maxw=maxw)
    state_ref[...] = state
    # counts in lanes 0/1/2 of a lane-dense tile (rest zero)
    lane = jax.lax.broadcasted_iota(I32, counts_ref.shape, 1)
    counts_ref[...] = jnp.where(
        lane == 0, n_to, jnp.where(lane == 1, spur,
                                   jnp.where(lane == 2, un, 0)))


@functools.partial(jax.jit, static_argnames=("w", "ww", "maxw", "interpret"))
def ring_drain(t, rto, started, has_ack, ack_seq, lbits, bitmap,
               sent0, sent1, sent2, *, w: int, ww: int, maxw: int,
               interpret: bool):
    """Blocked sent-ring drain over the flow table.

    Same contract as ``ref.ring_drain_ref`` with unpadded [F]/[F, w]/
    [F, ww]/[F, maxw] inputs; returns ``(state', n_to, spur,
    unacked_pkts)`` with original shapes.
    """
    f = sent0.shape[0]
    fp = -(-f // BLOCK_ROWS) * BLOCK_ROWS
    wp = -(-w // LANES) * LANES
    wwp = -(-ww // LANES) * LANES
    mwp = -(-maxw // LANES) * LANES

    scal_i = _pad2(jnp.stack(
        [has_ack.astype(I32), ack_seq, started.astype(I32)], axis=1),
        fp, LANES)
    scal_f = _pad2(rto.astype(F32)[:, None], fp, LANES)

    def tile(cols):
        return pl.BlockSpec((BLOCK_ROWS, cols), lambda i: (i, 0))

    state, counts = pl.pallas_call(
        functools.partial(_kernel, w=w, ww=ww, maxw=maxw),
        grid=(fp // BLOCK_ROWS,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  tile(LANES), tile(LANES), tile(wwp), tile(mwp),
                  tile(wp), tile(wp), tile(wp)],
        out_specs=[tile(wp), tile(LANES)],
        out_shape=[jax.ShapeDtypeStruct((fp, wp), I32),
                   jax.ShapeDtypeStruct((fp, LANES), I32)],
        interpret=interpret,
    )(jnp.asarray(t, I32).reshape(1), scal_i, scal_f,
      _pad2(lbits, fp, wwp), _pad2(bitmap, fp, mwp),
      _pad2(sent0, fp, wp), _pad2(sent1, fp, wp), _pad2(sent2, fp, wp))
    return (state[:f, :w], counts[:f, 0], counts[:f, 1], counts[:f, 2])
