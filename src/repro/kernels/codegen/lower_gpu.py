"""Mosaic-GPU-style stage lowering — split-K reduce over segment ranges.

The TPU lowering (stages.py) is correct only because TPU grids execute
sequentially: the output BlockSpec revisits one segment row across all
of its blocks and a VMEM accumulator carries the partial between grid
steps, with a segment's first block firing the Algorithm-2 reset.  GPU grids
give **no such guarantee** — programs launch in parallel and may run in
any order — so this lowering realizes the same stage IR with a
different reduce:

* **split-K partials** (:func:`splitk_partials`): every grid block
  computes its own partial over its ``block`` fibers and writes it to
  its own row of an ``(n_blocks, out_width)`` buffer.  The block→output
  mapping is 1:1 (``lambda i: (i, 0)``), so no two programs touch the
  same memory and the kernel is legal under any execution order — the
  canonical GPU split-K shape.
* **segment combine** (:func:`segment_combine`): a second pass sums each
  segment's block partials into its output row, keyed by the *same*
  ``block_seg`` array the TPU lowering scalar-prefetches.  ``block_seg``
  is sorted (padded_segment_layout emits segments in order), so the
  combine is a sorted ``segment_sum`` — and because it adds a segment's
  partials in ascending block order, it reproduces the TPU accumulator's
  addition order exactly: split-K-then-combine is **bit-for-bit** equal
  to sequential accumulation at any float width (the hypothesis suite
  asserts this on f64).

Product stages carry no cross-block state in either lowering (blocks
map 1:1 to output blocks already), so the GPU target reuses the shared
grid-parallel product kernel unchanged.

Fused chains cannot keep per-level crossing buffers resident across
grid steps without the sequential grid, so the GPU chain is *split-K at
the innermost level* plus one batched einsum + segment combine per link
(the flush of every level-``j`` row computed at once instead of at
segment close).  That trades the TPU lowering's single-kernel HBM
avoidance for legality — the chain is still one kernel launch plus
O(chain) XLA combines, and values are identical.

Pad blocks appended by the layouts (mask 0, edge-value ``block_seg``)
produce all-zero partials and combine into the final row as ``+0``, the
same inert-tail convention the stacked TPU path relies on.  This
container has no GPU, so ``interpret=True`` is the correctness witness
(PR 5's convention for TPU compiled mode); the kernels avoid every
TPU-only Pallas feature (no ``PrefetchScalarGridSpec``, no VMEM scratch,
no revisited output blocks) precisely so they stay inside the
Mosaic-GPU-expressible subset.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.codegen.ir import (Lowering, Stage, StageIR,
                                      _check_block_grid, _lane_padded,
                                      _load_operands, _premask,
                                      accumulator_type, contract_block,
                                      register_lowering)
from repro.kernels.codegen.stages import run_product_stage


def splitk_partials(stage: Stage, mask, padded):
    """Per-block partials of a reducing stage: ``(n_blocks, out_flat)``
    in the accumulation dtype, one row per grid block, no cross-block
    state.  ``mask``/``padded`` follow the same conventions as
    :func:`~repro.kernels.codegen.stages.run_reduce_stage` (tile mode
    pre-folds the mask and pads lane widths)."""
    acc_t = accumulator_type(jnp.result_type(*[a.dtype for a in padded]))
    tile = stage.tile
    if tile:
        padded = _premask(stage, padded, mask)
        padded = [_lane_padded(a, stage.op_pad(op))
                  for a, op in zip(padded, stage.operands)]
    out_pad = stage.out_pad
    P = mask.shape[0]
    _check_block_grid(P, stage.block)

    def kernel(*refs):
        m_ref = None if tile else refs[0]
        in_refs = refs[(0 if tile else 1):-1]
        o_ref = refs[-1]
        vals = _load_operands(stage, in_refs, m_ref)
        part = contract_block(stage.operands, stage.out_subs, vals, True,
                              acc_t)
        o_ref[...] = _lane_padded(part, out_pad).astype(o_ref.dtype)

    in_specs = []
    if not tile:
        in_specs.append(pl.BlockSpec((stage.block, 1), lambda i: (i, 0)))
    for op in stage.operands:
        w = stage.op_pad(op)
        if op.fiber:
            in_specs.append(pl.BlockSpec((stage.block, w),
                                         lambda i: (i, 0)))
        else:
            in_specs.append(pl.BlockSpec((1, w), lambda i: (0, 0)))
    inputs = tuple(padded) if tile else (mask, *padded)
    out = pl.pallas_call(
        kernel,
        grid=(P // stage.block,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, out_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((P // stage.block, out_pad), acc_t),
        interpret=stage.interpret,
    )(*inputs)
    return out[:, :stage.out_flat_dim] if out_pad != stage.out_flat_dim \
        else out


def segment_combine(partials, seg, nseg: int):
    """Sum per-block ``partials`` rows into ``nseg`` segment rows keyed
    by the sorted block→segment map ``seg`` — the final pass of the
    split-K reduce.  Adds each segment's partials in ascending block
    order, i.e. exactly the order the TPU sequential accumulator adds
    them, so the result is bit-identical to sequential accumulation
    (not merely close): reassociation never happens, only relocation.
    ``seg`` may be traced (per-shard slices of stacked layouts)."""
    return jax.ops.segment_sum(partials, seg, num_segments=nseg,
                               indices_are_sorted=True)


def _rows_to_parents(seg_child, seg_parent, n_child: int):
    """Child-row → parent-row map derived from the per-block segment ids
    of two adjacent chain levels: every child row owns at least one
    block (CSF fibers are nonempty), and all of a child's blocks agree
    on the parent, so a last-wins scatter is exact.  Works on traced
    arrays — no host round trip."""
    return jnp.zeros((n_child,), jnp.int32).at[seg_child].set(
        seg_parent.astype(jnp.int32))


class MosaicGPULowering(Lowering):
    """The parallel-grid target: split-K partials + segment combine.
    Registered as ``"gpu"`` — the lowering behind
    ``make_executor(backend="pallas-gpu")``."""

    target = "gpu"

    def reduce(self, ir: StageIR, block_seg, mask, padded, dtype):
        # split-K has no resets: the combine pass owns segment boundaries
        # via block_seg
        parts = splitk_partials(ir.stage, mask, padded)
        return segment_combine(parts, block_seg, ir.stage.nseg) \
            .astype(dtype)

    def product(self, ir: StageIR, padded, dtype):
        # 1:1 block→output products carry no cross-block state; the
        # shared grid-parallel kernel is already legal on GPU.
        return run_product_stage(ir.stage, padded, dtype)

    def chain(self, ir: StageIR, seg_lvls, mask, padded, link_arrays,
              dtype):
        acc_t = accumulator_type(dtype)
        parts = splitk_partials(ir.stage, mask, padded)
        rows = segment_combine(parts, seg_lvls[0], ir.nseg_lvls[0])
        pos = 0
        for j, link in enumerate(ir.links):
            # the level-j flush, batched over all rows at once: prepend
            # the row axis Z to the link einsum's output instead of
            # reducing the singleton fiber away per segment close
            buf_op = link.operands[0]
            iv = [rows.reshape((ir.nseg_lvls[j],) + buf_op.shape)]
            ins = ["Z" + buf_op.subs]
            n_other = len(link.operands) - 1
            for op, arr in zip(link.operands[1:],
                               link_arrays[pos:pos + n_other]):
                if op.fiber:
                    iv.append(arr.reshape((ir.nseg_lvls[j],) + op.shape))
                    ins.append("Z" + op.subs)
                else:
                    iv.append(arr.reshape(op.shape))
                    ins.append(op.subs)
            pos += n_other
            expr = ",".join(ins) + "->Z" + link.out_subs
            per_row = jnp.einsum(expr, *iv, preferred_element_type=acc_t)
            parent = _rows_to_parents(seg_lvls[j], seg_lvls[j + 1],
                                      ir.nseg_lvls[j])
            rows = segment_combine(per_row.reshape(ir.nseg_lvls[j], -1),
                                   parent, ir.nseg_lvls[j + 1])
        return rows.astype(dtype)


register_lowering(MosaicGPULowering())
