"""Pallas TPU kernel: SMaRTT per-flow congestion-window update.

This is the NIC datapath of the paper (Sec. 1.1.3: one packet every 40 ns at
800 Gb/s — the CC update must be branch-free and memory-lean).  On TPU the
natural analogue is a struct-of-arrays sweep over the flow table: flow state
lives in HBM as (F/128, 128)-shaped f32/i32 planes, the kernel streams
(8, 128) VMEM tiles through the VPU, applying the entire Alg. 1-3 update as
a branchless vector program.  The scalar parameters and the tick sit in
SMEM.

The arithmetic is *shared* with the engine: the kernel body calls
``repro.core.smartt.smartt_update`` on VMEM-resident tiles, so kernel and
oracle cannot drift apart.  The Pallas layer contributes blocking, padding
and the VMEM working-set contract.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cc_update import ref as R

# VMEM tile: 8 sublanes x 128 lanes (f32 native TPU tile)
BLOCK_ROWS = 8
LANES = 128

N_STATE_F32 = len(R.STATE_F32)
N_STATE_I32 = len(R.STATE_I32)
N_EVENT_F32 = len(R.EVENT_F32)
N_EVENT_I32 = len(R.EVENT_I32)


def _kernel(param_ref, now_ref, brtt_ref, trtt_ref, mi_ref,
            *refs):
    # param_ref / now_ref live in SMEM: the oracle reads the parameter
    # vector one scalar at a time (``param_vec[i]``), which a ref does too
    sf = [refs[i][...] for i in range(N_STATE_F32)]
    off = N_STATE_F32
    si = [refs[off + i][...] for i in range(N_STATE_I32)]
    off += N_STATE_I32
    ef = [refs[off + i][...] for i in range(N_EVENT_F32)]
    off += N_EVENT_F32
    ei = [refs[off + i][...] for i in range(N_EVENT_I32)]
    off += N_EVENT_I32
    out_f = refs[off:off + N_STATE_F32]
    out_i = refs[off + N_STATE_F32:]

    f32s, i32s = R.cc_update_ref(
        param_ref, brtt_ref[...], trtt_ref[...], mi_ref[...], now_ref[0],
        sf, si, ef, ei)
    for dst, val in zip(out_f, f32s):
        dst[...] = val
    for dst, val in zip(out_i, i32s):
        dst[...] = val


@functools.partial(jax.jit, static_argnames=("interpret",))
def cc_update(param_vec, now, brtt, trtt, mi,
              state_f32s, state_i32s, event_f32s, event_i32s,
              *, interpret: bool):
    """Blocked SMaRTT update over the flow table.

    Args:
      param_vec: f32[NP] scalar parameters (layout ``ref.PARAM_FIELDS``).
      now: scalar tick.
      brtt/trtt/mi: f32[F] per-flow constants.
      state_*: tuples of f32[F]/i32[F] per-flow state planes.
      event_*: tuples of f32[F]/i32[F] per-flow event planes.

    Returns (state_f32s', state_i32s') with original length F.
    """
    F = brtt.shape[0]
    rows = max(1, -(-F // LANES))
    rows_pad = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
    Fp = rows_pad * LANES

    def shape2d(x):
        x = jnp.pad(x, (0, Fp - F))
        return x.reshape(rows_pad, LANES)

    brtt2, trtt2, mi2 = shape2d(brtt), shape2d(jnp.broadcast_to(trtt, (F,))), shape2d(jnp.broadcast_to(mi, (F,)))
    # avoid div-by-zero on padded lanes of (trtt - brtt), rtt etc.
    brtt2 = jnp.where(brtt2 == 0, 1.0, brtt2)
    trtt2 = jnp.where(trtt2 == 0, 2.0, trtt2)
    ins = [shape2d(x) for x in (*state_f32s, *state_i32s, *event_f32s, *event_i32s)]

    grid = (rows_pad // BLOCK_ROWS,)
    tile = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    out_shapes = (
        [jax.ShapeDtypeStruct((rows_pad, LANES), jnp.float32)] * N_STATE_F32
        + [jax.ShapeDtypeStruct((rows_pad, LANES), jnp.int32)] * N_STATE_I32
    )
    outs = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[smem, smem] + [tile] * (3 + len(ins)),
        out_specs=[tile] * len(out_shapes),
        out_shape=out_shapes,
        interpret=interpret,
    )(param_vec.astype(jnp.float32),
      jnp.asarray(now, jnp.float32).reshape(1),
      brtt2, trtt2, mi2, *ins)

    def unshape(x):
        return x.reshape(-1)[:F]

    f32s = tuple(unshape(o) for o in outs[:N_STATE_F32])
    i32s = tuple(unshape(o) for o in outs[N_STATE_F32:])
    return f32s, i32s
