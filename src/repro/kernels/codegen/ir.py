"""Target-neutral stage IR — what the code generator says, not how.

The plan executor (kernels/codegen/executor.py) lowers a fused SpTTN
plan into a sequence of *stage descriptions*: pure dataclasses carrying
the operand index maps (einsum subscripts + dense shapes), the block
layout request (block size, segment-row count), the reset/flush points
of Algorithm 2 (implied by ``reduce`` / the chain's per-level segment
maps), and the einsum links of a fused chain.  Nothing in this module
touches Pallas: a :class:`StageIR` is a complete, target-independent
statement of the work, and a registered :class:`Lowering` turns it into
kernels for one target:

* ``"tpu"`` (kernels/codegen/stages.py) — the sequential-grid lowering:
  scalar-prefetched block→row index maps, a VMEM crossing buffer
  revisited across a segment's blocks with the Algorithm-2 reset, VMEM
  scratch buffers per fused-chain level.  Correct **only** because TPU
  grids execute sequentially.
* ``"gpu"`` (kernels/codegen/lower_gpu.py) — the Mosaic-GPU-style
  lowering: GPU grids guarantee no sequential execution, so the reduce
  is *split-K over segment ranges* — every block writes its own partial
  (1:1 block→output mapping, grid-parallel legal) and a final
  segment-combine pass sums partials into segment rows.

The registry is keyed by target name; ``make_executor`` maps engine
backends onto targets via
:data:`repro.analysis.diagnostics.PALLAS_TARGETS` (``"pallas"`` → tpu,
``"pallas-gpu"`` → gpu), and the static verifier's ``SPTTN-E041``
rejects a plan whose backend has no registered lowering on this host.

Tile alignment (``Stage.tile``) is part of the IR, not the lowering:
both targets honor the pad-to-tile request identically (lane widths
padded to :data:`TILE_LANE`, mask pre-folded), so a tiled stage is
bit-identical across targets too.
"""
from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp

from repro.kernels.util import round_up

# float32 hardware tile: (sublane, lane) = (8, 128).  Wider dtypes only
# shrink the sublane constraint, so aligning to the float32 tile is valid
# for every dtype the stages accumulate at (>= float32).
TILE_LANE = 128
TILE_SUBLANE = 8


def lane_pad(dim: int) -> int:
    """Next multiple of :data:`TILE_LANE` at or above ``dim``."""
    return round_up(dim, TILE_LANE)


@dataclasses.dataclass(frozen=True)
class StageOperand:
    """One kernel input: ``subs`` are the dense-axis einsum letters,
    ``shape`` the dense shape.  ``fiber`` operands carry the padded fiber
    axis (einsum batch letter Z) and arrive as (P, prod(shape)) blocks;
    broadcast operands arrive as one (1, prod(shape)) block shared by
    every grid step."""

    subs: str
    shape: tuple[int, ...]
    fiber: bool

    @property
    def flat_dim(self) -> int:
        return math.prod(self.shape)


def accumulator_type(dtype):
    """Accumulation dtype for a stage's in-kernel einsum: at least float32
    (MXU accumulation width), widened to match wider operands — float64
    stages accumulate at float64, never silently at float32."""
    return jnp.promote_types(jnp.float32, dtype)


@dataclasses.dataclass(frozen=True)
class Stage:
    """A single generated kernel: ``einsum(operands) -> out_subs`` per
    block, reduced over the fiber axis into ``nseg`` segment rows when
    ``reduce`` is set.  ``tile`` selects the pad-to-tile lowering (lane
    widths padded to :data:`TILE_LANE`, mask pre-folded) required for
    ``interpret=False`` on real TPUs."""

    operands: tuple[StageOperand, ...]
    out_subs: str
    out_shape: tuple[int, ...]
    reduce: bool
    block: int
    nseg: int            # segment-row count (reduce stages only)
    interpret: bool
    tile: bool = False

    @property
    def out_flat_dim(self) -> int:
        return math.prod(self.out_shape)

    def op_pad(self, op: StageOperand) -> int:
        """Lane width of ``op``'s block (padded in tile mode)."""
        return lane_pad(op.flat_dim) if self.tile else op.flat_dim

    @property
    def out_pad(self) -> int:
        """Lane width of the output block (padded in tile mode)."""
        return lane_pad(self.out_flat_dim) if self.tile else self.out_flat_dim

    @property
    def expr(self) -> str:
        ins = ",".join(("Z" + op.subs) if op.fiber else op.subs
                       for op in self.operands)
        return f"{ins}->{'' if self.reduce else 'Z'}{self.out_subs}"


@dataclasses.dataclass(frozen=True)
class ChainLink:
    """One outer level of a fused reducing chain.

    ``operands[0]`` is the inner crossing buffer (always a fiber operand:
    one level-``lvl`` row per flush); the rest are the link term's other
    operands.  ``expr`` reduces the singleton fiber axis away, so a flush
    adds one ``out_shape`` partial into the next level's buffer — how a
    target realizes the flush (in-kernel segment-close trigger on TPU,
    batched per-row einsum + segment combine on GPU) is the lowering's
    business, not the link's."""

    operands: tuple[StageOperand, ...]
    out_subs: str
    out_shape: tuple[int, ...]

    @property
    def out_flat_dim(self) -> int:
        return math.prod(self.out_shape)

    @property
    def expr(self) -> str:
        ins = ",".join(("Z" + op.subs) if op.fiber else op.subs
                       for op in self.operands)
        return f"{ins}->{self.out_subs}"


@dataclasses.dataclass(frozen=True)
class StageIR:
    """One target-neutral lowering unit, as emitted by the executor.

    ``kind`` selects the lowering entry point:

    * ``"reduce"`` — a row-strategy reducing stage: ``stage`` plus the
      block layout (``block_seg``/``mask``) supplied at lowering time.
      Reset point: a segment's first block; flush point: a segment's
      last block (both implied by the sorted ``block_seg``).
    * ``"product"`` — a per-fiber product stage, blocks 1:1 with output
      blocks (no cross-block state, grid-parallel on every target).
    * ``"chain"`` — a fused reducing chain: innermost ``stage`` plus
      ``links`` outward; ``nseg_lvls[j]`` is the segment-row count at
      chain level ``j`` (innermost-first), ``nseg_out`` the final row
      count (== ``nseg_lvls[-1]``).

    The IR an executor emits is identical across targets — the
    differential tests assert exactly that — so ``emitted_ir`` equality
    is the cheap witness that a lowering disagreement is a lowering bug,
    never a construction bug."""

    kind: str
    stage: Stage
    links: tuple[ChainLink, ...] = ()
    nseg_out: int = 0
    nseg_lvls: tuple[int, ...] = ()


# --------------------------------------------------------------------- #
# Shared lowering helpers (value-level, target-independent)
# --------------------------------------------------------------------- #
def _premask(stage: Stage, padded, mask):
    """Fold the pad-slot mask into the first fiber operand ahead of the
    kernel (tile mode: the ``(block, 1)`` mask input has no tile-legal
    lane width, so masking happens in XLA where a (P, 1) broadcast is
    free).  Pad slots gather nonzero 0's values — one zero factor per
    product is necessary and sufficient for their partials to vanish."""
    out = list(padded)
    for i, op in enumerate(stage.operands):
        if op.fiber:
            out[i] = out[i] * mask.astype(out[i].dtype)
            break
    return out


def _lane_padded(arr, width: int):
    """Zero-pad the last dim of a 2-D array up to ``width`` — used both on
    operand arrays ahead of the kernel and on kernel partials before they
    accumulate, so output pad lanes only ever hold zeros and the caller's
    final column slice is exact."""
    if arr.shape[-1] == width:
        return arr
    return jnp.pad(arr, ((0, 0), (0, width - arr.shape[-1])))


def _check_block_grid(padded_len: int, block: int) -> None:
    """The stage grid covers ``padded_len // block`` blocks; a
    non-multiple length would silently drop the tail slots, so fail
    loudly instead (layout producers — ``padded_segment_layout``,
    ``pad_segment_layout``, the stacked distributed padding — all
    guarantee block multiples).  Thin wrapper over the verifier's
    :func:`repro.analysis.invariants.check_block_grid` (SPTTN-E022)."""
    from repro.analysis.invariants import check_block_grid
    d = check_block_grid(padded_len, block)
    if d is not None:
        raise ValueError(f"{d.message} [{d.code}]")


def _load_operands(stage: Stage, in_refs, mask_ref):
    """Read each operand block as its 2-D ``(rows, flat)`` value; the mask
    is folded into the first fiber operand so pad slots contribute zero.
    Tile mode slices the padded lanes back off, so the contraction always
    sees exact (unpadded) operands."""
    vals = []
    masked = mask_ref is None
    for ref, op in zip(in_refs, stage.operands):
        v = ref[...]
        if v.shape[-1] != op.flat_dim:
            v = v[:, :op.flat_dim]
        if op.fiber and not masked:
            v = v * mask_ref[...].astype(v.dtype)
            masked = True
        vals.append(v)
    return vals


def contract_block(operands, out_subs: str, vals, reduce: bool, acc_t):
    """One block's dense contraction, ``einsum(operands) -> out_subs``, as
    broadcast-multiply-sum over 2-D ``(rows, flat)`` operand values.

    This is what every lowering runs inside its kernels.  Mosaic has no
    ``dot_general`` without a contracting dimension (every hadamard and
    outer product of the SpTTN stages is one), and it cannot split or
    merge arbitrary lane dimensions.  So each operand is viewed over
    *groups* of letters: adjacent letters that the same operands carry,
    in the same order, and that the output keeps or drops alike.  An
    operand then needs one reshape that splits its lanes per group (a
    no-op in the common one-group case), the product broadcasts, dropped
    groups are summed, the kept ones merge back into ``(rows, out_flat)``
    lanes, and a reducing stage sums its rows.  Fiber operands have
    ``rows`` = the block, broadcast operands one row.  Returns
    ``(1 if reduce else rows, prod(out))`` in ``acc_t``.
    """
    dim = {}
    for op in operands:
        dim.update(zip(op.subs, op.shape))
    letters = list(out_subs)
    for op in operands:
        letters += [c for c in op.subs if c not in letters]

    def sig(c):
        return tuple(c in op.subs for op in operands), c in out_subs

    groups: list[list[str]] = []
    for c in letters:
        prev = groups[-1][-1] if groups else None
        if prev is not None and sig(prev) == sig(c) and all(
                op.subs.index(c) == op.subs.index(prev) + 1
                for op in operands if c in op.subs):
            groups[-1].append(c)
        else:
            groups.append([c])
    size = [math.prod(dim[c] for c in g) for g in groups]

    prod = None
    for op, v in zip(operands, vals):
        v = v.astype(acc_t)
        rows = v.shape[0]
        order = [c for c in letters if c in op.subs]
        if order != list(op.subs):
            v = v.reshape((rows,) + op.shape)
            v = jnp.transpose(v, [0] + [1 + op.subs.index(c)
                                        for c in order])
        shape = (rows,) + tuple(s if g[0] in op.subs else 1
                                for g, s in zip(groups, size))
        if v.shape != shape:
            v = v.reshape(shape)
        prod = v if prod is None else prod * v
    kept = [i for i, g in enumerate(groups) if g[0] in out_subs]
    dropped = tuple(1 + i for i, g in enumerate(groups)
                    if g[0] not in out_subs)
    if dropped:
        prod = jnp.sum(prod, axis=dropped, keepdims=not kept)
    out_flat = math.prod(size[i] for i in kept)
    if prod.shape != (prod.shape[0], out_flat):
        prod = prod.reshape(prod.shape[0], out_flat)
    if reduce and prod.shape[0] != 1:
        # (a one-row sum is the row itself; Mosaic cannot take the rank-1
        # reshape jnp.sum would emit for it)
        prod = jnp.sum(prod, axis=0, keepdims=True)
    return prod


# --------------------------------------------------------------------- #
# Per-target lowering registry
# --------------------------------------------------------------------- #
class Lowering:
    """Contract one target implements to consume the stage IR.

    Every method receives a :class:`StageIR` plus the already-gathered
    block arrays (layouts may be traced: the stacked distributed engine
    feeds per-shard slices through the TPU lowering) and returns the
    stage's logical 2-D output:

    * ``reduce``  → ``(stage.nseg, stage.out_flat_dim)`` in ``dtype``
    * ``product`` → ``(P, stage.out_flat_dim)`` in ``dtype`` (pad rows
      included; the executor slices ``[:nfib]``)
    * ``chain``   → ``(ir.nseg_out, links[-1].out_flat_dim)`` in
      ``dtype``

    Logical output shapes are part of the contract — the hypothesis
    property test drives random nests through every registered lowering
    and asserts the shapes match.
    """

    target: str = "?"

    def reduce(self, ir: StageIR, block_seg, mask, padded, dtype):
        raise NotImplementedError

    def product(self, ir: StageIR, padded, dtype):
        raise NotImplementedError

    def chain(self, ir: StageIR, seg_lvls, mask, padded, link_arrays,
              dtype):
        raise NotImplementedError


_LOWERINGS: dict[str, Lowering] = {}


def register_lowering(lowering: Lowering) -> Lowering:
    """Register ``lowering`` under its ``target`` name (last wins, so a
    test can shadow and restore a target)."""
    _LOWERINGS[lowering.target] = lowering
    return lowering


def lowering_targets() -> tuple[str, ...]:
    """Registered target names, sorted (``('gpu', 'tpu')`` after the
    package import registers both built-ins)."""
    return tuple(sorted(_LOWERINGS))


def get_lowering(target: str) -> Lowering:
    """The registered lowering for ``target``; raises ``ValueError``
    naming the registered targets otherwise (the executor surfaces this
    as the verifier's SPTTN-E041)."""
    try:
        return _LOWERINGS[target]
    except KeyError:
        raise ValueError(
            f"no stage lowering registered for target {target!r} "
            f"(registered: {lowering_targets()})") from None
