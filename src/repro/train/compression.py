"""Gradient compression: int8 block-quantized all-reduce with error
feedback.

Wire cost per gradient element: 2 bytes (reduce-scatter of int8 chunks via
all_to_all + all_gather of the int8 result) versus 8 bytes for a ring
all-reduce in f32 — a 4x reduction of the DP collective, which is exactly
the traffic the paper's transport carries (bulk-synchronous all-reduce,
Sec. 1).  Error feedback carries the quantization residual into the next
step, preserving convergence (1-bit-Adam-style).

Implemented with ``shard_map`` over the data axis; validated in
``tests/test_compression.py`` on a fake 8-device mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BLOCK = 256


def quantize(x, block: int = BLOCK):
    """f32[N] (N % block == 0) -> (int8[N], f32[N/block] scales)."""
    xb = x.reshape(-1, block)
    scale = jnp.max(jnp.abs(xb), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale[:, 0]


def dequantize(q, scale, block: int = BLOCK):
    return (q.astype(jnp.float32).reshape(-1, block)
            * scale[:, None]).reshape(-1)


def compressed_psum_mean(g, err, axis_name: str, world: int):
    """Inside shard_map: mean-all-reduce g (f32[N]) in int8.

    Returns (g_mean f32[N], new_err f32[N]).  N must be divisible by
    world * BLOCK.
    """
    g_fb = g + err                      # error feedback
    q, scale = quantize(g_fb)
    residual = g_fb - dequantize(q, scale)

    # reduce-scatter: exchange int8 chunks, each rank sums its chunk
    n = g.shape[0]
    chunk = n // world
    qs = q.reshape(world, chunk)
    ss = scale.reshape(world, chunk // BLOCK)
    q_x = jax.lax.all_to_all(qs, axis_name, split_axis=0, concat_axis=0,
                             tiled=False)          # [world, chunk] others' data
    s_x = jax.lax.all_to_all(ss, axis_name, split_axis=0, concat_axis=0,
                             tiled=False)
    part = jnp.sum(jax.vmap(dequantize)(q_x, s_x), axis=0) / world  # f32[chunk]

    # all-gather the (re-quantized) reduced chunks
    pq, pscale = quantize(part)
    res2 = part - dequantize(pq, pscale)
    gq = jax.lax.all_gather(pq, axis_name)          # [world, chunk] int8
    gs = jax.lax.all_gather(pscale, axis_name)
    out = jax.vmap(dequantize)(gq, gs).reshape(-1)

    # local residual of stage-2 re-quantization also folds into feedback
    idx = jax.lax.axis_index(axis_name)
    cur = jax.lax.dynamic_slice(residual, (idx * chunk,), (chunk,))
    err_new = jax.lax.dynamic_update_slice(residual, cur + res2,
                                           (idx * chunk,))
    return out, err_new


def make_compressed_allreduce(mesh, axis_name: str = "data"):
    """Returns fn(g, err) -> (mean_g, err').

    ``g``/``err`` are [world, N]: row r is replica r's full (distinct)
    gradient vector — exactly what per-replica backward passes produce.
    The result rows all equal the int8-compressed mean.
    """
    from jax.sharding import PartitionSpec as P

    world = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(axis_name, None), P(axis_name, None)),
                       out_specs=(P(axis_name, None), P(axis_name, None)),
                       check_vma=False)
    def _run(g_local, err_local):
        out, err = compressed_psum_mean(g_local[0], err_local[0],
                                        axis_name, world)
        return out[None], err[None]

    return _run, world
