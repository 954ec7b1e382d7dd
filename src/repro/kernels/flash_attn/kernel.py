"""Pallas TPU kernel: FlashAttention-style blocked causal attention.

Online-softmax over KV blocks with the query block resident in VMEM.
Tiling targets the MXU: (BLOCK_Q, D) x (D, BLOCK_K) matmuls with
128-aligned dimensions.  Grid = (batch*heads, q_blocks); the KV loop runs
inside the kernel with ``jax.lax.fori_loop`` so the working set stays
(BLOCK_Q + 2*BLOCK_K) x D in VMEM.

Used by the model zoo when ``use_pallas=True`` (TPU runtime); the pure-JAX
chunked equivalent in ``repro.models.attention`` is the XLA path used for
CPU smoke tests and the dry-run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, sk, block_k, causal, window, scale):
    _, bq, d = q_ref.shape
    q = q_ref[0].astype(jnp.float32) * scale
    qi = pl.program_id(1)
    q_off = qi * bq + (sk - pl.num_programs(1) * bq)   # align ends

    m = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)

    n_kb = sk // block_k

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = q @ k.T                                             # [bq, bk]
        qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
        kpos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
        mask = jnp.ones((bq, block_k), bool)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = alpha * acc + p @ v
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, n_kb, body, (m, l, acc))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool):
    """q: [B, H, Sq, D]; k/v: [B, H, Sk, D] (kv heads pre-broadcast).
    Sq % block_q == 0 and Sk % block_k == 0 required (pad upstream)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if sq % block_q or sk % block_k:
        raise ValueError(f"seq lens ({sq},{sk}) must tile ({block_q},{block_k})")
    scale = 1.0 / (d ** 0.5)

    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)

    kernel = functools.partial(_attn_kernel, sk=sk, block_k=block_k,
                               causal=causal, window=window, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d)
