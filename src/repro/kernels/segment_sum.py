"""Sorted segment sum on TPU: a blocked one-hot Pallas kernel.

The engines' sparse reduction sums level-``lvl`` fiber rows into their
level-``out_lvl`` parents, by a CSF segment map that is ascending and
fixed with the pattern.  XLA lowers ``jax.ops.segment_sum`` to a
scatter-add, which ignores both facts.  This kernel walks the rows in
order instead, in blocks of :data:`IN_BLOCK` rows, and adds each block
into the block of :data:`OUT_BLOCK` segments it overlaps through a
one-hot matmul on the MXU.

The pairs of (output block, input block) that overlap are a merge path
over the two block sequences (:func:`merge_path`): pattern-static, at
most ``nseg / OUT_BLOCK + n / IN_BLOCK + 1`` of them.  The list is built
on the host from the segment map and scalar-prefetched, one grid step
per pair.  Steps that share an output block are consecutive, so the
block stays in VMEM and accumulates; its first step zeroes it.

Rows travel fiber-minor: the kernel reads ``x`` as ``(w, n)`` and
writes ``(w, nseg)``, so a narrow row (``w`` = 16 at rank 16) fills the
128 lanes with fibers instead of padding 16 lanes to 128.  The one-hot
is exact in bfloat16, and each block of ``x`` is split exactly into
three bfloat16 parts, stacked into one matmul whose products accumulate
in float32, so the sum keeps float32 accuracy.

The engines attach the list to the operand (:func:`attached_work`) and
fall back to ``jax.ops.segment_sum`` off TPU, for dtypes other than
float32, and where the operand carries no list (a traced operand that
was not prepared, such as a shard inside ``shard_map``).
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.executor import default_interpret

#: segments per output block and rows per input block.  Both are lane
#: multiples; the segment map's 1-D blocks must also match XLA's
#: ``T(1024)`` tiling of an ``s32[n]``, so rows come in 1024s.  Chosen
#: on a v5e at the benchmark cell's three reductions (PERF.md, Findings).
OUT_BLOCK = 256
IN_BLOCK = 1024
#: rows of ``x.T`` per block: wider rows take a second grid axis
MAX_WIDTH_BLOCK = 256


def on_tpu() -> bool:
    """True where the kernel is compiled, not interpreted."""
    return not default_interpret()


def merge_path(seg: np.ndarray, nseg: int, out_block: int = OUT_BLOCK,
               in_block: int = IN_BLOCK) -> np.ndarray:
    """The ``(out_block, in_block)`` pairs whose ranges overlap, in
    order, interleaved into one int32 array ``[o0, i0, o1, i1, ...]``.

    ``seg`` is ascending.  Every output block gets at least one pair, so
    each is zeroed even where no row falls in it.
    """
    seg = np.asarray(seg)
    n = seg.shape[0]
    nob = -(-nseg // out_block)
    nib = max(-(-n // in_block), 1)
    bounds = np.searchsorted(seg, np.arange(nob + 1) * out_block)
    lo, hi = bounds[:-1], bounds[1:]
    first = np.minimum(lo // in_block, nib - 1)
    last = np.where(hi > lo, (hi - 1) // in_block, first)
    per = last - first + 1
    ob = np.repeat(np.arange(nob), per)
    ib = np.arange(per.sum()) - np.repeat(np.cumsum(per) - per, per) \
        + np.repeat(first, per)
    return np.stack([ob, ib], axis=1).reshape(-1).astype(np.int32)


def attached_work(csf, lvl: int, out_lvl: int, dtype):
    """The operand's merge path for the ``(lvl, out_lvl)`` reduction,
    built from the host segment map on first use and kept in the
    operand's layout cache (so ``prepare_operand`` passes it as a jit
    argument).  ``None`` where the kernel does not apply: off TPU, for
    dtypes other than float32, for a reduction to level 0, and on a
    traced operand that carries no list."""
    if not on_tpu() or jnp.dtype(dtype) != jnp.float32 or out_lvl == 0:
        return None
    cache = csf.__dict__.setdefault("_codegen_layouts", {})
    key = ("segsum", lvl, out_lvl, OUT_BLOCK, IN_BLOCK)
    if key not in cache:
        seg = csf.seg[(lvl, out_lvl)]
        if isinstance(seg, jax.core.Tracer):
            return None
        nseg = csf.nfib[out_lvl]
        # numpy, like the block layouts: an entry made inside one trace
        # is reused by later ones
        cache[key] = (nseg, merge_path(np.asarray(seg), nseg))
    return cache[key][1]


def _split3(x):
    """``x`` (float32) as three bfloat16 parts whose sum is ``x``."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _kernel(work_ref, x_ref, seg_ref, o_ref, *, n: int, out_block: int,
            in_block: int):
    i = pl.program_id(1)
    ob = work_ref[2 * i]
    ib = work_ref[2 * i + 1]

    @pl.when(jnp.logical_or(i == 0,
                            work_ref[2 * jnp.maximum(i - 1, 0)] != ob))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    # rows past n (the last block's tail) match nothing and add zero
    row = ib * in_block + jax.lax.broadcasted_iota(jnp.int32,
                                                   (1, in_block), 1)
    valid = row < n
    local = jnp.where(valid, seg_ref[...].reshape(1, in_block)
                      - ob * out_block, -1)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (out_block, in_block), 0)
              == local).astype(jnp.bfloat16)
    x = jnp.where(valid, x_ref[...], 0.0)
    w = x.shape[0]
    # the parts stacked into one matmul: twice as fast on a v5e as three
    part = jax.lax.dot_general(
        jnp.concatenate(_split3(x), axis=0), onehot,
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    o_ref[...] += part[:w] + part[w:2 * w] + part[2 * w:]


def sorted_segment_sum(x, seg, nseg: int, work, *,
                       out_block: int = OUT_BLOCK,
                       in_block: int = IN_BLOCK,
                       interpret: bool | None = None):
    """``jax.ops.segment_sum(x, seg, nseg)`` for float32 ``x`` of shape
    ``(n, ...)`` and an ascending ``seg``, through the one-hot kernel.

    ``work`` is :func:`merge_path` of ``seg`` at the same block sizes.
    Returns ``(nseg, ...)``; segments no row maps to are zero.
    """
    n, tail = x.shape[0], x.shape[1:]
    w = math.prod(tail)
    if n == 0 or nseg == 0 or w == 0:
        return jnp.zeros((nseg,) + tail, x.dtype)
    interpret = default_interpret() if interpret is None else interpret
    wb = w if w <= MAX_WIDTH_BLOCK else MAX_WIDTH_BLOCK
    steps = work.shape[0] // 2
    xt = x.reshape(n, w).T
    kernel = functools.partial(_kernel, n=n, out_block=out_block,
                               in_block=in_block)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(-(-w // wb), steps),
            in_specs=[
                pl.BlockSpec((wb, in_block),
                             lambda c, i, wk: (c, wk[2 * i + 1])),
                pl.BlockSpec((in_block,),
                             lambda c, i, wk: (wk[2 * i + 1],)),
            ],
            out_specs=pl.BlockSpec((wb, out_block),
                                   lambda c, i, wk: (c, wk[2 * i])),
        ),
        out_shape=jax.ShapeDtypeStruct((w, nseg), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="spttn_segsum",
    )(work, xt, seg.astype(jnp.int32))
    return out.T.reshape((nseg,) + tail)
