"""Pallas TPU kernel: Mamba-2 SSD chunked scan (intra-chunk portion).

The SSD trick (Dao & Gu, arXiv:2405.21060) splits the linear recurrence into
(a) an intra-chunk quadratic part — attention-shaped matmuls that feed the
MXU — and (b) a tiny inter-chunk state recurrence.  This kernel computes,
per (sequence, chunk) grid cell with everything VMEM-resident:

    L        = cumsum(loga)                       # [C]
    y_intra  = ((C B^T) ∘ exp(L_i - L_j) ∘ causal) x   # [C, P]
    S_chunk  = (B ∘ exp(L_end - L))^T x           # [N, P]
    T_chunk  = exp(L_end)                         # scalar chunk decay

The O(n_chunks) inter-chunk recurrence and the rank-1 correction
``y_inter = exp(L) * C @ S_prev`` run in plain jnp in ``ops.py`` — they are
bandwidth-trivial compared to the chunk matmuls.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_BIG = -1e30


def _ssd_kernel(x_ref, loga_ref, b_ref, c_ref, y_ref, s_ref, t_ref):
    _, C, P = x_ref.shape
    x = x_ref[0].astype(jnp.float32)          # [C, P]
    la = loga_ref[0].astype(jnp.float32)      # [C]
    Bm = b_ref[0].astype(jnp.float32)         # [C, N]
    Cm = c_ref[0].astype(jnp.float32)         # [C, N]

    L = jnp.cumsum(la)                        # inclusive cumsum of log-decay
    # decay matrix M[i, j] = exp(L_i - L_j) for j <= i (segment-sum form)
    diff = L[:, None] - L[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    M = jnp.exp(jnp.where(jj <= ii, diff, NEG_BIG))
    G = (Cm @ Bm.T) * M                       # [C, C] gated attention scores
    y_ref[0] = (G @ x).astype(y_ref.dtype)

    decay_end = jnp.exp(L[-1] - L)            # [C]
    s_ref[0, 0] = ((Bm * decay_end[:, None]).T @ x).astype(s_ref.dtype)  # [N, P]
    t_ref[0, 0] = jnp.exp(L[-1]).astype(t_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan(x, loga, B, C, *, chunk: int = 128, interpret: bool):
    """Intra-chunk SSD pass.

    Args:
      x: [BH, L, P] (pre-scaled by dt), loga: [BH, L], B/C: [BH, L, N].
      chunk: chunk length (L % chunk == 0).

    Returns:
      y_intra: [BH, L, P], s_chunk: [BH, L/chunk, N, P], t_chunk: [BH, L/chunk]
    """
    BH, L, P = x.shape
    N = B.shape[-1]
    if L % chunk:
        raise ValueError(f"L={L} must be a multiple of chunk={chunk}")
    NC = L // chunk

    y, s, t = pl.pallas_call(
        _ssd_kernel,
        grid=(BH, NC),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk), lambda i, c: (i, c)),
            pl.BlockSpec((1, chunk, N), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda i, c: (i, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, 1, N, P), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1), lambda i, c: (i, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, L, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, NC, N, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, NC), jnp.float32),
        ],
        interpret=interpret,
    )(x, loga, B, C)
    return y, s, t
