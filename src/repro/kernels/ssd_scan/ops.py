"""Full SSD op = Pallas intra-chunk kernel + jnp inter-chunk recurrence.

Also provides ``ssd_jnp`` — the identical chunked algorithm in pure jnp —
which the model zoo uses on CPU / in the dry-run (XLA path), so the Pallas
kernel and the deployed math share one decomposition.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.ssd_scan.kernel import ssd_chunk_scan


def _inter_chunk(y_intra, s_chunk, t_chunk, loga, C_mat, chunk):
    """Combine chunk states and add the cross-chunk correction.
    Returns (y, final_state [BH, N, P])."""
    BH, L, P = y_intra.shape
    NC = L // chunk
    S0 = jnp.zeros(s_chunk.shape[2:], jnp.float32)

    def scan_one(sc, tc):
        def step(S, inp):
            Sc, Tc = inp
            return Tc * S + Sc, S   # emit state *before* the chunk
        S_final, prev = jax.lax.scan(step, S0, (sc, tc[:, None, None]))
        return prev, S_final        # [NC, N, P], [N, P]

    prev_states, final_state = jax.vmap(scan_one)(s_chunk, t_chunk)

    # y_inter[t] = exp(L_t) * C_t @ S_prev(chunk(t))
    la = loga.reshape(BH, NC, chunk).astype(jnp.float32)
    Lc = jnp.cumsum(la, axis=-1)                             # [BH, NC, C]
    Cr = C_mat.reshape(BH, NC, chunk, -1).astype(jnp.float32)
    y_inter = jnp.einsum("bcin,bcnp->bcip", Cr, prev_states) * \
        jnp.exp(Lc)[..., None]
    return y_intra + y_inter.reshape(BH, L, P), final_state


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd(x, loga, B, C, *, chunk: int = 128):
    """Pallas-backed SSD: x,[BH,L,P] loga,[BH,L] B/C,[BH,L,N] -> y [BH,L,P]."""
    y_intra, s_chunk, t_chunk = ssd_chunk_scan(x, loga, B, C, chunk=chunk,
                                               interpret=interpret_mode())
    y, _ = _inter_chunk(y_intra, s_chunk, t_chunk, loga, C, chunk)
    return y


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_jnp(x, loga, B, C, *, chunk: int = 128):
    """Same chunked decomposition in pure jnp (XLA path for CPU/dry-run)."""
    y, _ = ssd_jnp_with_state(x, loga, B, C, chunk=chunk)
    return y


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_jnp_with_state(x, loga, B, C, *, chunk: int = 128):
    """As ssd_jnp but also returns the final SSM state [BH, N, P]
    (needed when a prefill hands off to recurrent decode)."""
    BH, L, P = x.shape
    N = B.shape[-1]
    NC = L // chunk
    xr = x.reshape(BH, NC, chunk, P).astype(jnp.float32)
    lar = loga.reshape(BH, NC, chunk).astype(jnp.float32)
    Br = B.reshape(BH, NC, chunk, N).astype(jnp.float32)
    Cr = C.reshape(BH, NC, chunk, N).astype(jnp.float32)
    Lc = jnp.cumsum(lar, axis=-1)
    diff = Lc[..., :, None] - Lc[..., None, :]
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    M = jnp.exp(jnp.where(mask, diff, -1e30))
    G = jnp.einsum("bcin,bcjn->bcij", Cr, Br) * M
    y_intra = jnp.einsum("bcij,bcjp->bcip", G, xr)
    decay_end = jnp.exp(Lc[..., -1:] - Lc)                   # [BH, NC, C]
    s_chunk = jnp.einsum("bcjn,bcj,bcjp->bcnp", Br, decay_end, xr)
    t_chunk = jnp.exp(Lc[..., -1])
    return _inter_chunk(y_intra.reshape(BH, L, P), s_chunk, t_chunk, loga,
                        C, chunk)
