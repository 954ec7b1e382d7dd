"""Pallas TPU kernels: fused enqueue-rank + round-robin arbitration.

The fabric's two per-tick arbitration problems as blocked vector programs:

  * ``enqueue_rank`` — same-destination enqueue ranking + capacity
    acceptance + ring-position assignment, one row per switch fan-in group
    ([NSW, DMAX] after the topology's ``in_tbl`` gather).  The pairwise
    compare+reduce runs entirely inside the tile, so the O(DMAX^2) work
    never touches HBM.
  * ``rr_pick`` — per-row round-robin argmin (sender flow arbitration,
    EQDS grant arbitration) over [N, K] eligibility tiles.

Both kernel bodies call the shared jnp reference (``ref.py``) on
VMEM-resident tiles — the ``kernels/cc_update`` discipline — so kernel and
oracle cannot drift apart.  Rows pad to the 8-sublane boundary and lanes to
128; padded destination slots carry the sentinel queue id ``nq`` (rank
contributions to real slots come only from *lower* slot indices, and pads
sit above every real slot, so padding never perturbs a real rank) and
padded eligibility slots are False (their keys tie with ineligible real
slots at higher indices, leaving the first-min argmin unchanged).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.enqueue_arb import ref as R

BLOCK_ROWS = 8
LANES = 128

I32 = jnp.int32


def _pad2(x, rows_pad: int, cols_pad: int, fill):
    r, c = x.shape
    return jnp.pad(x, ((0, rows_pad - r), (0, cols_pad - c)),
                   constant_values=fill)


def _enqueue_kernel(gdst_ref, ghead_ref, gsize_ref,
                    rank_ref, acc_ref, pos_ref, *, cap: int, nq: int):
    rank, acc, pos = R.enqueue_rank_ref(
        gdst_ref[...], ghead_ref[...], gsize_ref[...], cap=cap, nq=nq)
    rank_ref[...] = rank
    acc_ref[...] = acc.astype(I32)
    pos_ref[...] = pos


@functools.partial(jax.jit,
                   static_argnames=("cap", "nq", "interpret"))
def enqueue_rank(gdst, ghead, gsize, *, cap: int, nq: int,
                 interpret: bool):
    """Blocked enqueue-rank over the switch fan-in groups.

    Args: i32 [S, D] per-slot destination queue / queue head / queue
    occupancy (``D = fan_max``).  Returns ``(rank, acc, pos)`` as
    i32/bool/i32 [S, D] (see ``ref.enqueue_rank_ref``).
    """
    s, d = gdst.shape
    sp = -(-s // BLOCK_ROWS) * BLOCK_ROWS
    dp = -(-d // LANES) * LANES
    outs = pl.pallas_call(
        functools.partial(_enqueue_kernel, cap=cap, nq=nq),
        grid=(sp // BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((BLOCK_ROWS, dp), lambda i: (i, 0))] * 3,
        out_specs=[pl.BlockSpec((BLOCK_ROWS, dp), lambda i: (i, 0))] * 3,
        out_shape=[jax.ShapeDtypeStruct((sp, dp), I32)] * 3,
        interpret=interpret,
    )(_pad2(gdst, sp, dp, nq), _pad2(ghead, sp, dp, 0),
      _pad2(gsize, sp, dp, 0))
    rank, acc, pos = (o[:s, :d] for o in outs)
    return rank, acc != 0, pos


def _rr_kernel(elig_ref, rr_ref, has_ref, sel_ref, *, kmax: int):
    has, sel = R.rr_pick_tile(elig_ref[...] != 0, rr_ref[:, 0:1], kmax)
    has_ref[...] = jnp.broadcast_to(has.astype(I32), has_ref.shape)
    sel_ref[...] = jnp.broadcast_to(sel, sel_ref.shape)


@functools.partial(jax.jit, static_argnames=("kmax", "interpret"))
def rr_pick(elig, rr, *, kmax: int, interpret: bool):
    """Blocked round-robin argmin over [N, K] eligibility rows.

    Returns ``(has, sel)`` as bool[N] / i32[N] (see ``ref.rr_pick_ref``).
    """
    n, k = elig.shape
    np_ = -(-n // BLOCK_ROWS) * BLOCK_ROWS
    kp = -(-k // LANES) * LANES
    elig2 = _pad2(elig.astype(I32), np_, kp, 0)
    rr2 = _pad2(jnp.broadcast_to(rr[:, None], (n, 1)), np_, kp, 0)
    has, sel = pl.pallas_call(
        functools.partial(_rr_kernel, kmax=kmax),
        grid=(np_ // BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((BLOCK_ROWS, kp), lambda i: (i, 0))] * 2,
        out_specs=[pl.BlockSpec((BLOCK_ROWS, kp), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((np_, kp), I32)] * 2,
        interpret=interpret,
    )(elig2, rr2)
    return has[:n, 0] != 0, sel[:n, 0]
