"""TPU stage lowering — the sequential-grid consumer of the stage IR.

The target-neutral stage descriptions live in kernels/codegen/ir.py
(:class:`Stage`, :class:`ChainLink`, :class:`StageIR`); this module is
the ``"tpu"`` :class:`~repro.kernels.codegen.ir.Lowering` registered for
them, plus the runner functions it is built from (kept as public API —
tests and the stacked distributed engine call them directly).

A fused SpTTN plan lowers to a sequence of *stages*, one per sparse
contraction term (DESIGN.md §6).  On TPU every stage is a
scalar-prefetched block-segment grid over level-``lvl`` CSF fibers,
generalizing the hand-written MTTKRP kernel's ``block_seg`` machinery
(kernels/util.py) to arbitrary CSF depth and arbitrary dense index
structure:

* the per-fiber dense contraction is :func:`~repro.kernels.codegen.ir.
  contract_block` — broadcast-multiply-sum on the VPU, because the
  stages' contractions (hadamard and outer products, per-fiber dots) have
  no contracting dimension Mosaic's matmul could take;
* a *reducing* stage accumulates block partials into its output-row
  crossing buffer, which lives in VMEM across the sequential grid and is
  zeroed exactly when a new segment's first block arrives — Algorithm 2's
  buffer-reset rule, read off the scalar-prefetched ``block_seg``;
* a *product* stage keeps the fiber axis (same-level output, e.g. the
  TTTP leaf or a final scatter term) and writes blocks 1:1;
* a *fused chain* stage (:func:`run_fused_chain_stage`) lowers a whole
  chain of reducing terms sharing the sparse operand's CSF path into ONE
  kernel: per chain level a VMEM scratch buffer holds that level's
  crossing buffer, reset at its level's segment starts (read off that
  level's scalar-prefetched segment map), and an inner buffer flushes
  through its link's contraction into the next level's buffer when its
  segment closes — Algorithm 2's reset rule applied at every depth of a
  single sequential grid, eliminating the inter-stage HBM round trip of
  the staged lowering.

Stages are pure descriptions (shapes, subscripts, block size); emission
happens at trace time, so one jit of the enclosing executor compiles the
whole plan.  All of this is correct *only because TPU grids execute
sequentially* — the output BlockSpec revisits a segment's row across its
blocks and the VMEM accumulator survives between grid steps.  The GPU
lowering (kernels/codegen/lower_gpu.py) makes no such assumption and
realizes the same IR as split-K partials plus a segment-combine pass.

Tile alignment (compiled mode, DESIGN.md §8)
--------------------------------------------
Real TPUs constrain VMEM blocks to hardware tiles: the last (lane)
dimension must be a multiple of :data:`TILE_LANE` (128) and the
second-to-last (sublane) dimension a multiple of :data:`TILE_SUBLANE`
(8) for float32.  ``Stage.tile`` turns on the pad-to-tile lowering:

* every operand/output block's flattened dense width is zero-padded up
  to the next lane multiple (``Stage.op_pad`` / ``Stage.out_pad``); the
  kernel slices the real width back out before the einsum, so padded
  lanes never enter the contraction and the result is bit-identical to
  the unpadded lowering;
* the ``(block, 1)`` pad-slot mask input — whose lane width cannot be
  tile-aligned without 128x waste — is folded into the first fiber
  operand *before* the kernel (:func:`_premask`), so padded rows and
  zero-nnz segment tails still contribute exact zeros;
* callers must supply ``block`` as a multiple of :data:`TILE_SUBLANE`
  (the executor rounds up; the autotuner sweeps aligned blocks only).

The pass changes only shapes, never values, so interpret mode with
``tile=True`` is the CPU-testable witness for the compiled lowering.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The IR layer moved to kernels/codegen/ir.py; the names are re-exported
# here because this module has always been their import surface (tests,
# the stacked distributed engine, and the executor all import from
# ``stages``) and because the TPU runners below are their first consumer.
from repro.kernels.codegen.ir import (TILE_LANE, TILE_SUBLANE,  # noqa: F401
                                      ChainLink, Lowering, Stage, StageIR,
                                      StageOperand, _check_block_grid,
                                      _lane_padded, _load_operands,
                                      _premask, accumulator_type,
                                      contract_block, lane_pad,
                                      register_lowering)


def _row_spec(width: int, row_of):
    """BlockSpec of one ``(1, width)`` row of a ``(rows, 1, width)``
    array, the row picked per grid step by ``row_of(i, *prefetch)``.
    The leading dim is squeezed, so the kernel sees ``(1, width)``; the
    block's last two dims equal the array's, which is what makes a
    one-row block tile-legal (a ``(1, w)`` block of a ``(rows, w)``
    array is not: Mosaic wants second-minor blocks in multiples of 8)."""
    return pl.BlockSpec((None, 1, width),
                        lambda i, *s: (row_of(i, *s), 0, 0))


def _opens(seg_ref, b):
    """True at the first block of a segment.  Derived from the block ->
    segment map (each segment owns at least one block, so consecutive
    segments differ), so SMEM holds one scalar map per level, not three."""
    return jnp.logical_or(b == 0,
                          seg_ref[b] != seg_ref[jnp.maximum(b - 1, 0)])


def _closes(seg_ref, b, nblocks: int):
    """True at the last block of a segment (see :func:`_opens`).  Inert
    padding blocks repeat the final segment id, so that segment closes at
    the grid's end after adding their zeros."""
    return jnp.logical_or(
        b == nblocks - 1,
        seg_ref[b] != seg_ref[jnp.minimum(b + 1, nblocks - 1)])


def run_reduce_stage(stage: Stage, block_seg: jnp.ndarray,
                     mask: jnp.ndarray, padded, dtype) -> jnp.ndarray:
    """Fused contract-and-accumulate: grid over padded fiber blocks, output
    row (the crossing buffer) resident in VMEM and revisited across its
    blocks; a segment's first block fires the Algorithm-2 reset.  The
    kernel finds those blocks in ``block_seg`` (:func:`_opens`), the one
    array scalar-prefetched into SMEM (1 MiB on v5e, 4 bytes per block).

    ``block_seg``/``mask`` may be traced values, not just host constants:
    the stacked distributed engine feeds per-shard slices of mesh-stacked
    layouts through here so one trace serves every shard.  Only the grid
    extent must be static — the index maps (``bs[i]``) handle dynamic
    block→row assignment.  Inert trailing blocks appended by cross-shard
    padding (mask 0, edge-value ``block_seg``) revisit the final output
    row and add zero, so the revisit runs of the output BlockSpec stay
    contiguous.
    """

    acc_t = accumulator_type(dtype)
    tile = stage.tile
    if tile:
        padded = _premask(stage, padded, mask)
        padded = [_lane_padded(a, stage.op_pad(op))
                  for a, op in zip(padded, stage.operands)]
    out_pad = stage.out_pad
    _check_block_grid(mask.shape[0], stage.block)

    def kernel(bs_ref, *refs):
        m_ref = None if tile else refs[0]
        in_refs = refs[(0 if tile else 1):-1]
        o_ref = refs[-1]
        b = pl.program_id(0)

        @pl.when(_opens(bs_ref, b))
        def _reset():
            o_ref[...] = jnp.zeros_like(o_ref)

        vals = _load_operands(stage, in_refs, m_ref)
        part = contract_block(stage.operands, stage.out_subs, vals, True,
                              acc_t)
        o_ref[...] += _lane_padded(part, out_pad).astype(o_ref.dtype)

    P = mask.shape[0]
    in_specs = []
    if not tile:
        in_specs.append(pl.BlockSpec((stage.block, 1),
                                     lambda i, bs: (i, 0)))
    for op in stage.operands:
        w = stage.op_pad(op)
        if op.fiber:
            in_specs.append(pl.BlockSpec((stage.block, w),
                                         lambda i, bs: (i, 0)))
        else:
            in_specs.append(pl.BlockSpec((1, w),
                                         lambda i, bs: (0, 0)))
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(P // stage.block,),
        in_specs=in_specs,
        out_specs=_row_spec(out_pad, lambda i, bs: bs[i]),
    )
    inputs = tuple(padded) if tile else (mask, *padded)
    out = pl.pallas_call(
        kernel,
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((stage.nseg, 1, out_pad), dtype),
        interpret=stage.interpret,
    )(block_seg, *inputs)
    out = out.reshape(stage.nseg, out_pad)
    return out[:, :stage.out_flat_dim] if out_pad != stage.out_flat_dim \
        else out


def run_product_stage(stage: Stage, padded, dtype) -> jnp.ndarray:
    """Per-fiber fused product (no sparse reduction): blocks map 1:1 to
    output blocks; pad rows are sliced off by the caller."""

    acc_t = accumulator_type(dtype)
    if stage.tile:
        padded = [_lane_padded(a, stage.op_pad(op))
                  for a, op in zip(padded, stage.operands)]
    out_pad = stage.out_pad

    def kernel(*refs):
        in_refs, o_ref = refs[:-1], refs[-1]
        vals = _load_operands(stage, in_refs, None)
        part = contract_block(stage.operands, stage.out_subs, vals, False,
                              acc_t)
        o_ref[...] = _lane_padded(part, out_pad).astype(o_ref.dtype)

    P = next(a.shape[0] for a, op in zip(padded, stage.operands) if op.fiber)
    _check_block_grid(P, stage.block)
    in_specs = []
    for op in stage.operands:
        w = stage.op_pad(op)
        if op.fiber:
            in_specs.append(pl.BlockSpec((stage.block, w),
                                         lambda i: (i, 0)))
        else:
            in_specs.append(pl.BlockSpec((1, w),
                                         lambda i: (0, 0)))
    out = pl.pallas_call(
        kernel,
        grid=(P // stage.block,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((stage.block, out_pad),
                               lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((P, out_pad), dtype),
        interpret=stage.interpret,
    )(*padded)
    return out[:, :stage.out_flat_dim] if out_pad != stage.out_flat_dim \
        else out


def run_fused_chain_stage(stage: Stage, links: tuple[ChainLink, ...],
                          seg_lvls, mask: jnp.ndarray, padded, link_arrays,
                          nseg_out: int, dtype) -> jnp.ndarray:
    """One kernel for a whole chain of reducing terms (Algorithm 2 at
    every depth of a single sequential grid).

    The innermost ``stage`` accumulates block partials into the first
    VMEM scratch buffer; when level ``k``'s segment closes
    (:func:`_closes` on ``seg_lvls[k]``), buffer ``k`` flushes through
    ``links[k]``'s contraction into buffer ``k+1`` (the last link flushes
    into the kernel output row, whose BlockSpec follows the outermost
    segment map).  A level's segment start (:func:`_opens`) fires that
    buffer's Algorithm-2 reset.  Segment maps are nested (CSF levels), so
    an outer segment's first block is also an inner segment's first
    block and flush order inner-to-outer within one grid step is exact.
    The C segment maps are the only scalar-prefetched arrays.

    ``seg_lvls[k]`` is the per-block segment id at chain level ``k`` —
    levels ``0..C-2`` drive the link operands' scalar-prefetched index
    maps, level ``C-1`` drives the output BlockSpec.

    Under ``stage.tile`` every operand/buffer/output lane width is padded
    to :data:`TILE_LANE` (sliced back before each einsum) and the mask is
    pre-folded into the innermost fiber operands, exactly as in the
    single-stage runners.
    """
    C = len(links) + 1           # chain length in terms
    acc_t = accumulator_type(dtype)
    tile = stage.tile
    out_flat = links[-1].out_flat_dim
    out_pad = lane_pad(out_flat) if tile else out_flat
    n_stage = len(stage.operands)
    link_ops_flat = [op for link in links for op in link.operands[1:]]
    # per-level crossing-buffer lane widths (scratch shapes + flush pads)
    buf_w = [lane_pad(link.operands[0].flat_dim) if tile
             else link.operands[0].flat_dim for link in links]
    if tile:
        padded = _premask(stage, padded, mask)
        padded = [_lane_padded(a, stage.op_pad(op))
                  for a, op in zip(padded, stage.operands)]
        link_arrays = [_lane_padded(a, lane_pad(op.flat_dim))
                       for a, op in zip(link_arrays, link_ops_flat)]

    nblocks = mask.shape[0] // stage.block

    def kernel(*refs):
        segs = refs[:C]
        off = C if tile else C + 1
        m_ref = None if tile else refs[C]
        in_refs = refs[off:off + n_stage]
        link_refs = refs[off + n_stage:-1 - (C - 1)]
        o_ref = refs[-1 - (C - 1)]
        bufs = refs[len(refs) - (C - 1):]
        b = pl.program_id(0)

        for j in range(C - 1):
            @pl.when(_opens(segs[j], b))
            def _reset(buf=bufs[j]):
                buf[...] = jnp.zeros_like(buf)

        @pl.when(_opens(segs[C - 1], b))
        def _reset_out():
            o_ref[...] = jnp.zeros_like(o_ref)

        vals = _load_operands(stage, in_refs, m_ref)
        part = contract_block(stage.operands, stage.out_subs, vals, True,
                              acc_t)
        bufs[0][...] += _lane_padded(part, buf_w[0])

        pos = 0
        for j, link in enumerate(links):
            dst = bufs[j + 1] if j + 1 < C - 1 else o_ref
            dst_w = buf_w[j + 1] if j + 1 < C - 1 else out_pad
            others = link_refs[pos:pos + len(link.operands) - 1]
            pos += len(link.operands) - 1

            @pl.when(_closes(segs[j], b, nblocks))
            def _flush(j=j, link=link, dst=dst, dst_w=dst_w, others=others):
                iv = []
                for ref, op in zip((bufs[j], *others), link.operands):
                    v = ref[...]
                    iv.append(v[:, :op.flat_dim]
                              if v.shape[-1] != op.flat_dim else v)
                out = contract_block(link.operands, link.out_subs, iv, True,
                                     acc_t)
                dst[...] += _lane_padded(out, dst_w).astype(dst.dtype)

    P = mask.shape[0]
    _check_block_grid(P, stage.block)
    in_specs = []
    if not tile:
        in_specs.append(pl.BlockSpec((stage.block, 1), lambda i, *s: (i, 0)))
    for op in stage.operands:
        w = stage.op_pad(op)
        if op.fiber:
            in_specs.append(pl.BlockSpec((stage.block, w),
                                         lambda i, *s: (i, 0)))
        else:
            in_specs.append(pl.BlockSpec((1, w),
                                         lambda i, *s: (0, 0)))
    for j, link in enumerate(links):
        for op in link.operands[1:]:
            w = lane_pad(op.flat_dim) if tile else op.flat_dim
            if op.fiber:
                in_specs.append(_row_spec(w, lambda i, *s, j=j: s[j][i]))
            else:
                in_specs.append(pl.BlockSpec((1, w),
                                             lambda i, *s: (0, 0)))
    # fiber link operands are read one row per segment: (rows, 1, w)
    link_arrays = [a[:, None, :] if op.fiber else a
                   for a, op in zip(link_arrays, link_ops_flat)]
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=C,
        grid=(P // stage.block,),
        in_specs=in_specs,
        out_specs=_row_spec(out_pad, lambda i, *s: s[C - 1][i]),
        scratch_shapes=[pltpu.VMEM((1, w), acc_t) for w in buf_w],
    )
    inputs = (*padded, *link_arrays) if tile else (mask, *padded,
                                                   *link_arrays)
    out = pl.pallas_call(
        kernel,
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((nseg_out, 1, out_pad), dtype),
        interpret=stage.interpret,
    )(*seg_lvls, *inputs)
    out = out.reshape(nseg_out, out_pad)
    # an output row whose segment owns no block is never stored by the
    # kernel (the revisit pattern only reaches segments present in the
    # outermost block->segment map), so it returns whatever memory
    # backed the buffer.  Single-device CSF layouts reach every row, but
    # the stacked engine's shards padded to the mesh-wide maximum (and
    # its all-padding empty shards) do not — mask those rows to the
    # exact zero an empty segment contributes.
    # (jnp.where, not a multiply — the garbage may be NaN/inf, which a
    # zero multiply would propagate instead of clearing)
    row_written = jnp.zeros((nseg_out,), jnp.int32).at[
        jnp.asarray(seg_lvls[-1])].set(1)
    out = jnp.where(row_written[:, None] != 0, out, jnp.zeros((), dtype))
    return out[:, :out_flat] if out_pad != out_flat else out


class TPULowering(Lowering):
    """The sequential-grid target: adapts :class:`StageIR` onto the
    runner functions above.  Registered as ``"tpu"`` — the lowering
    behind ``make_executor(backend="pallas")``."""

    target = "tpu"

    def reduce(self, ir: StageIR, block_seg, mask, padded, dtype):
        return run_reduce_stage(ir.stage, block_seg, mask, padded, dtype)

    def product(self, ir: StageIR, padded, dtype):
        return run_product_stage(ir.stage, padded, dtype)

    def chain(self, ir: StageIR, seg_lvls, mask, padded, link_arrays,
              dtype):
        return run_fused_chain_stage(ir.stage, ir.links, seg_lvls, mask,
                                     padded, link_arrays, ir.nseg_out, dtype)


register_lowering(TPULowering())
