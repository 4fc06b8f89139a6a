"""Distributed-optimization primitives: gradient compression + overlap.

``compressed_psum`` — int8 stochastic-rounding all-reduce: blockwise scale,
quantize, psum int32, dequantize.  Unbiased (E[deq] = x); cuts gradient
all-reduce bytes 4x vs fp32 (2x vs bf16).  Off by default; enabled per
RunConfig for bandwidth-bound meshes.

``reduce_scatter_grads`` — psum_scatter along the FSDP axis so each shard
only materializes its own gradient slice (ZeRO-2 shape), letting XLA's
latency-hiding scheduler overlap the scatter with backprop compute.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with the repo's keyword spelling (one call site
    convention for the distributed engines and the tests)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def _quantize_block(x, key, block: int = 256):
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % block
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    scaled = blocks / scale
    noise = jax.random.uniform(key, scaled.shape) - 0.5
    q = jnp.clip(jnp.round(scaled + noise), -127, 127).astype(jnp.int8)
    return q, scale, x.shape, pad


def _dequantize_block(q, scale, shape, pad):
    flat = (q.astype(jnp.float32) * scale).reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(shape)


def compressed_psum(x, axis_name: str, key, block: int = 256):
    """Unbiased int8 stochastic-rounding all-reduce over ``axis_name``.

    int8 payloads + per-block fp32 scales are all-gathered and the exact
    dequantized sum is formed locally — 1/4 the wire bytes of an fp32 ring
    all-reduce (scales add 4/block overhead).  Stochastic rounding keeps
    E[result] equal to the uncompressed psum; variance is O(scale^2/12) per
    element (tested for unbiasedness in tests/test_collectives.py).
    """
    q, scale, shape, pad = _quantize_block(x, key, block)
    qg = jax.lax.all_gather(q, axis_name)            # (P, nblk, block) int8
    sg = jax.lax.all_gather(scale, axis_name)        # (P, nblk, 1) fp32
    total = jnp.sum(qg.astype(jnp.float32) * sg, axis=0)
    return _dequantize_block(total, jnp.ones_like(scale), shape, pad)


def reduce_scatter_grads(grads, axis_name: str, tiled_axis: int = 0):
    """psum_scatter every leaf along ``axis_name`` (ZeRO-2 gradient shape).
    Leaves whose dim 0 does not divide the axis size are psum'd whole."""
    size = jax.lax.axis_size(axis_name)

    def one(g):
        if g.ndim and g.shape[0] % size == 0 and g.shape[0] >= size:
            return jax.lax.psum_scatter(g, axis_name, scatter_dimension=0,
                                        tiled=True)
        return jax.lax.psum(g, axis_name)

    return jax.tree.map(one, grads)
