"""High-level attention op: GQA head broadcasting + padding + kernel dispatch."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attn.kernel import flash_attention


def gqa_flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                        block_q: int = 128, block_k: int = 128):
    """q: [B, Hq, Sq, D]; k/v: [B, Hkv, Sk, D] with Hq % Hkv == 0."""
    hq, hkv = q.shape[1], k.shape[1]
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    sq, sk = q.shape[2], k.shape[2]
    bq = min(block_q, sq) if sq % block_q else block_q
    while sq % bq:
        bq //= 2
    bk = min(block_k, sk) if sk % block_k else block_k
    while sk % bk:
        bk //= 2
    return flash_attention(q, k, v, causal=causal, window=window,
                           block_q=max(bq, 1), block_k=max(bk, 1),
                           interpret=interpret_mode())
