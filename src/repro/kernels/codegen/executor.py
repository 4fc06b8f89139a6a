"""PallasPlanExecutor — lower any fused SpTTN plan to Pallas kernels.

Structural sibling of :class:`~repro.core.executor.VectorizedExecutor`
(it *is* one, by inheritance): operand lifting, dense fallbacks, and
final-output materialization are shared, so the two engines agree by
construction everywhere except the lowering unit — ``_fiber_contract``,
where the XLA engine's einsum + sorted segment sum is replaced by
generated Pallas stages.  The executor emits *target-neutral* stage IR
(kernels/codegen/ir.py) and hands it to the registered
:class:`~repro.kernels.codegen.ir.Lowering` for its ``target``:
``"tpu"`` (stages.py, sequential-grid VMEM accumulator — the
``backend="pallas"`` engine) or ``"gpu"`` (lower_gpu.py, split-K +
segment combine — the ``backend="pallas-gpu"`` engine).  The emitted IR
is byte-identical across targets; only the lowering differs.

Per reducing term the generator picks one of two lowerings from the
static segment profile (pattern-known, so the choice is trace-time):

* **row** — the mttkrp-style fused kernel: fibers padded per output
  segment to block multiples (``padded_segment_layout`` at arbitrary
  (lvl, out_lvl), not just leaf->root), output row accumulated in VMEM
  with the Algorithm-2 reset.  Chosen when segments are block-sized —
  padding stays bounded.
* **segsum** — a fused product stage (hadamard/dot in VMEM) followed by
  the engines' sorted segment sum (``VectorizedExecutor._segment_sum``).
  Chosen when segments are tiny (e.g. leaf -> next level), where
  block-per-segment padding would explode.

Gathers stay in XLA on purpose: TPU-native big fast gathers feed the
kernels, matching the hand-written MTTKRP kernel this module retires as
a special case (it survives as the generator's regression fixture).
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

# Chain legality is a static invariant owned by the verifier; this
# module is where the chains are *lowered*, so it re-exports the
# detector — the tuner and the distributed engine import it from either
# place and get the same single implementation.
from repro.analysis.invariants import fusible_chains  # noqa: F401
from repro.core.executor import (CSFArrays, VectorizedExecutor,
                                 default_interpret)
from repro.core.loopnest import LoopOrder
from repro.core.paths import ContractionPath
from repro.core.spec import SpTTNSpec
# importing the lowering modules registers the built-in targets
from repro.kernels.codegen import lower_gpu, stages  # noqa: F401
from repro.kernels.codegen.ir import (TILE_SUBLANE, ChainLink, Stage,
                                      StageIR, StageOperand, get_lowering)
from repro.kernels.util import padded_segment_layout, round_up

DEFAULT_BLOCK = 128


@dataclasses.dataclass(frozen=True)
class SegmentProfile:
    """Static reduction profile of one (lvl → out_lvl) CSF segment map.

    This is everything the strategy choice reads about the pattern, and it
    is computed from the *operand actually being executed* — in the
    distributed engine that is a shard's local CSF, so each shard picks
    its lowering from its own nonzero distribution (a skewed shard may
    take ``row`` while a sparse one takes ``segsum``; DESIGN.md §7).
    """

    lvl: int
    out_lvl: int
    nfib: int            # level-``lvl`` fibers entering the reduction
    nseg: int            # level-``out_lvl`` output rows
    max_seg: int         # longest segment (fibers feeding one output row)
    mean_seg: float      # nfib / nseg

    @staticmethod
    def row_decision(nfib: int, nseg: int, block: int) -> bool:
        """The strategy formula on the O(1) fiber counts alone: row wins
        when block-per-segment padding stays within ~4x of the fiber
        count (small kernels always qualify via the absolute floor)."""
        return nseg * block <= max(4 * nfib, 4 * block)

    def prefers_row(self, block: int) -> bool:
        """True when the fused VMEM row accumulator is the better
        lowering for this profile; otherwise fall back to ``segsum``."""
        return self.row_decision(self.nfib, self.nseg, block)


# --------------------------------------------------------------------- #
# Static layout cache (pattern-fixed, stored on the CSFArrays instance)
#
# Entry formats — owned here so every producer agrees with the consumers
# in ``_fiber_contract`` / ``_exec_chain``:
#   stage key (lvl, out_lvl, block) ->
#       (nseg, gather, mask[:, None], block_seg)
#   chain key ("chain", lvl0, levels, block) ->
#       (nseg, gather, mask[:, None], segs)
# (the kernels derive a segment's first and last block from the block ->
# segment maps, so no flag arrays are kept)
# ``nseg`` is static; the array slots may be numpy (built while tracing
# over a concrete operand), device arrays (``prepare_operand``), or traced
# values: a jitted engine receives a prepared operand's layouts as
# arguments (``CSFArrays`` flattens the cache), and the stacked
# distributed engine installs per-shard slices of mesh-stacked layouts
# inside shard_map, which is what lets ONE kernel trace serve every
# shard.
# --------------------------------------------------------------------- #
def layout_cache(csf: CSFArrays) -> dict:
    """The per-operand static layout cache (created on first use)."""
    return csf.__dict__.setdefault("_codegen_layouts", {})


def stage_layout_key(lvl: int, out_lvl: int, block: int) -> tuple:
    return (lvl, out_lvl, block)


def chain_layout_key(lvl0: int, levels: tuple, block: int) -> tuple:
    return ("chain", lvl0, tuple(levels), block)


def stage_cache_entry(nseg: int, gather, mask, block_seg) -> tuple:
    """Assemble a row-stage cache entry; ``mask`` is the flat (P,) mask
    (the trailing unit lane is added here)."""
    return (int(nseg), gather, mask[:, None], block_seg)


def chain_cache_entry(nseg: int, gather, mask, segs) -> tuple:
    """Assemble a fused-chain cache entry."""
    return (int(nseg), gather, mask[:, None], tuple(segs))


def _host_seg(csf, key: tuple) -> np.ndarray:
    """A segment map as numpy, for building a layout while tracing.  A
    traced operand carries only the layouts attached before the trace."""
    seg = csf.seg[key]
    if isinstance(seg, jax.core.Tracer):
        raise ValueError(
            f"no block layout for segment map {key} on this traced "
            "operand; pass it through prepare_operand(ex, csf, factors) "
            "before jitting the engine")
    return np.asarray(seg)


def chain_block_arrays(csf, lvl0: int, levels: tuple, block: int):
    """Numpy block-level chain layout: padded innermost layout plus the
    per-block segment ids at every chain level.  ``csf`` needs only
    ``.seg`` and ``.nfib``, so the stacked distributed engine can feed
    padded per-shard numpy arrays through the same math it would trace
    with.
    """
    seg0 = _host_seg(csf, (lvl0, levels[0]))
    lay = padded_segment_layout(seg0, csf.nfib[levels[0]], block)
    segs = [lay.block_seg.astype(np.int32)]
    for prev, lvl in zip(levels, levels[1:]):
        up = (_host_seg(csf, (prev, lvl))[segs[-1]] if lvl > 0
              else np.zeros_like(segs[-1]))
        segs.append(up.astype(np.int32))
    return lay, segs


def segment_profile(csf: CSFArrays, lvl: int, out_lvl: int) -> SegmentProfile:
    """Profile the ``(lvl, out_lvl)`` segment map of ``csf`` (pattern-
    static; concrete per operand, hence per shard).  ``max_seg`` and
    ``mean_seg`` cost one O(nfib) pass — inspection/reporting callers
    only; the trace-time strategy choice reads just the O(1) counts."""
    nfib = csf.nfib[lvl]
    nseg = csf.nfib[out_lvl] if out_lvl > 0 else 1
    if nfib == 0:
        return SegmentProfile(lvl, out_lvl, 0, nseg, 0, 0.0)
    seg = np.asarray(csf.seg[(lvl, out_lvl)]) if out_lvl > 0 else \
        np.zeros(nfib, np.int64)
    counts = np.bincount(seg, minlength=max(nseg, 1))
    return SegmentProfile(lvl, out_lvl, nfib, nseg, int(counts.max()),
                          nfib / max(nseg, 1))


class PallasPlanExecutor(VectorizedExecutor):
    """Execute a (path, order) plan through generated Pallas kernels.

    ``strategy`` forces the reduction lowering (``"row"``/``"segsum"``)
    for tests; ``"auto"`` picks per stage from the segment profile.
    ``interpret=None`` resolves to True off-TPU (CPU validation mode).

    ``tile_align`` turns on the pad-to-tile lowering pass (DESIGN.md §8):
    every stage's lane widths are padded to ``TILE_LANE`` (128) and
    ``block`` is rounded up to a ``TILE_SUBLANE`` (8) multiple, which is
    what makes the generated kernels legal under ``interpret=False`` on
    real TPUs.  ``None`` resolves to compiled mode (``not interpret``) —
    interpret-mode validation stays unpadded by default, but the pass is
    value-preserving, so ``tile_align=True, interpret=True`` is the
    CPU-testable witness for the compiled lowering.

    ``target`` names the registered stage lowering (docs/backends.md):
    ``"tpu"`` — sequential-grid VMEM accumulation (``backend="pallas"``)
    or ``"gpu"`` — split-K + segment combine (``backend="pallas-gpu"``).
    The executor emits the same IR either way; strategy choice, layouts,
    and operand lifting are all target-independent.
    """

    def __init__(self, spec: SpTTNSpec, path: ContractionPath,
                 order: LoopOrder, block: int = DEFAULT_BLOCK,
                 interpret: bool | None = None, strategy: str = "auto",
                 tile_align: bool | None = None, target: str = "tpu"):
        super().__init__(spec, path, order)
        if strategy not in ("auto", "row", "segsum", "fused"):
            raise ValueError(f"unknown strategy {strategy!r}")
        if block < 1:
            raise ValueError(f"block must be positive, got {block}")
        self.target = target
        self.lowering = get_lowering(target)   # ValueError on unknown
        self.interpret = default_interpret() if interpret is None \
            else interpret
        self.tile_align = (not self.interpret) if tile_align is None \
            else bool(tile_align)
        self.block = round_up(block, TILE_SUBLANE) if self.tile_align \
            else block
        self.strategy = strategy
        # every Stage emitted at trace time, in emission order — the
        # shape-inspection surface for the tile-alignment tests (a fused
        # chain records (stage, links) in emitted_chains as well).  Reset
        # per trace in __call__ so a long-lived executor reflects only
        # its latest trace instead of accumulating every one.
        self.emitted_stages: list[Stage] = []
        self.emitted_chains: list[tuple[Stage, tuple[ChainLink, ...]]] = []
        # the full target-neutral IR, one entry per lowering-unit call —
        # identical across targets for the same plan/operand/settings
        # (the cross-backend tests assert it), which is what makes a
        # TPU-vs-GPU value disagreement attributable to a lowering
        self.emitted_ir: list[StageIR] = []
        # (lvl, out_lvl) -> "row" | "segsum" | "fused", recorded at trace
        # time for inspection (tests, distributed per-shard strategy
        # reporting).  A fused chain records ONE entry keyed by its
        # (innermost lvl, final out_lvl) — one entry == one kernel launch
        # for the whole chain.
        self.stage_strategy: dict[tuple[int, int], str] = {}
        # start tid -> member tids of each provably safe reducing chain;
        # executed as one kernel only under strategy="fused"
        self._chains = (fusible_chains(spec, path)
                        if strategy == "fused" else {})

    def __call__(self, csf, factors):
        self.emitted_stages.clear()
        self.emitted_chains.clear()
        self.emitted_ir.clear()
        self.stage_strategy.clear()
        return super().__call__(csf, factors)

    # -- static layouts (pattern-fixed, cached on the CSFArrays) -------- #
    def _layout(self, csf: CSFArrays, lvl: int, out_lvl: int):
        cache = layout_cache(csf)
        key = stage_layout_key(lvl, out_lvl, self.block)
        if key not in cache:
            seg = _host_seg(csf, (lvl, out_lvl))
            nseg = csf.nfib[out_lvl] if out_lvl > 0 else 1
            lay = padded_segment_layout(seg, nseg, self.block)
            # entries stay numpy: an entry first created INSIDE one jit
            # trace must be reusable by a later trace over the same
            # operand (tuner timing several pallas-family candidates), so
            # nothing trace-bound may be cached here
            cache[key] = stage_cache_entry(
                lay.nseg, lay.gather, lay.mask, lay.block_seg)
        return cache[key]

    def strategy_for(self, csf: CSFArrays, lvl: int, out_lvl: int) -> str:
        """Reduction lowering for this operand's (lvl, out_lvl) stage,
        chosen from its segment profile (per-shard in the distributed
        engine) unless forced by ``strategy``.  Reads only the O(1)
        fiber counts — :func:`segment_profile` exists for callers that
        want the full distribution.  Under ``strategy="fused"`` only
        chain members fuse; stages outside a chain fall back to the
        profile-driven choice here."""
        if self.strategy not in ("auto", "fused"):
            return self.strategy
        nfib = csf.nfib[lvl]
        nseg = csf.nfib[out_lvl] if out_lvl > 0 else 1
        row = SegmentProfile.row_decision(nfib, nseg, self.block)
        return "row" if row else "segsum"

    def _use_row(self, csf: CSFArrays, lvl: int, out_lvl: int) -> bool:
        choice = self.strategy_for(csf, lvl, out_lvl)
        self.stage_strategy[(lvl, out_lvl)] = choice
        return choice == "row"

    # -- fused reducing chains (DESIGN.md §6) --------------------------- #
    def _chain_len(self, tid: int) -> int:
        chain = self._chains.get(tid)
        return len(chain) if chain else 1

    def _chain_layout(self, csf: CSFArrays, lvl0: int, levels: tuple):
        """Per-block segment ids at every chain level, plus the padded
        innermost layout (pattern-static, cached on
        the CSFArrays like the single-stage layouts).

        ``levels`` are the chain's output levels innermost-first (e.g.
        MTTKRP's ``(2, 1)``); nesting of the CSF segment maps makes each
        outer array a composition of the inner one.
        """
        cache = layout_cache(csf)
        key = chain_layout_key(lvl0, levels, self.block)
        if key in cache:
            return cache[key]
        lay, segs = chain_block_arrays(csf, lvl0, levels, self.block)
        # numpy, not jnp: see _layout — cache entries outlive any single
        # jit trace, so they must never hold trace-bound values
        entry = chain_cache_entry(lay.nseg, lay.gather, lay.mask, segs)
        cache[key] = entry
        return entry

    def _exec_chain(self, csf: CSFArrays, factors, env: dict, tid: int,
                    length: int):
        """Lower a whole detected reducing chain to ONE Pallas kernel
        (run_fused_chain_stage): the innermost term's block contraction
        feeds a VMEM scratch crossing buffer per intermediate level, and
        segment-close flushes carry partials outward — no HBM round trip
        between the chain's stages."""
        from repro.core.executor import DenseVal, FiberVal

        tids = self._chains[tid]
        terms = [self.path[k] for k in tids]
        first = terms[0]
        lvl0 = self._sparse_level(first.indices)
        levels = tuple(self._sparse_level(t.out.indices) for t in terms)
        dims = self.spec.dims
        sp = set(self.spos)

        if csf.nfib.get(lvl0, 0) == 0:
            # degenerate pattern: fall back to the staged per-term path
            val = None
            for k in tids:
                self._tid = k
                val = self._exec_term(csf, factors, env, self.path[k])
                if k != tids[-1]:
                    env[self.path[k].out.name] = val
            return val

        a = self._get_operand(csf, factors, env, first.lhs)
        b = self._get_operand(csf, factors, env, first.rhs)
        fa, da = self._lift(csf, a, first.lhs, lvl0)
        fb, db = self._lift(csf, b, first.rhs, lvl0)
        dtype = jnp.result_type(fa.dtype, fb.dtype)

        operands, arrays = [], []
        for arr, inds in ((fa, da), (fb, db)):
            shape = tuple(dims[i] for i in inds)
            operands.append(StageOperand(
                subs="".join(self._letter[i] for i in inds),
                shape=shape, fiber=arr.ndim == len(inds) + 1))
            arrays.append(arr)
        out_dense0 = tuple(i for i in first.out.indices if i not in sp)
        out_subs = "".join(self._letter[i] for i in out_dense0)
        out_shape = tuple(dims[i] for i in out_dense0)

        nseg0, gather, mask, segs = self._chain_layout(csf, lvl0, levels)
        nfib0 = csf.nfib[lvl0]
        with self._scope("lift"):
            padded = [
                arr.reshape(nfib0, -1)[gather] if op.fiber
                else arr.reshape(1, -1)
                for arr, op in zip(arrays, operands)]
        stage = Stage(operands=tuple(operands), out_subs=out_subs,
                      out_shape=out_shape, reduce=True, block=self.block,
                      nseg=nseg0, interpret=self.interpret,
                      tile=self.tile_align)

        links, link_arrays = [], []
        for pos, term in enumerate(terms[1:]):
            lvl_k = levels[pos]          # level the intermediate lives on
            inter = terms[pos].out.name
            other = term.rhs if term.lhs.name == inter else term.lhs
            val = self._get_operand(csf, factors, env, other)
            arr, dense_inds = self._lift(csf, val, other, lvl_k)
            link_ops = [StageOperand(subs=out_subs, shape=out_shape,
                                     fiber=True)]
            fiber = arr.ndim == len(dense_inds) + 1
            link_ops.append(StageOperand(
                subs="".join(self._letter[i] for i in dense_inds),
                shape=tuple(dims[i] for i in dense_inds), fiber=fiber))
            link_arrays.append(
                arr.reshape(csf.nfib[lvl_k], -1) if fiber
                else arr.reshape(1, -1))
            out_dense = tuple(i for i in term.out.indices if i not in sp)
            out_subs = "".join(self._letter[i] for i in out_dense)
            out_shape = tuple(dims[i] for i in out_dense)
            links.append(ChainLink(operands=tuple(link_ops),
                                   out_subs=out_subs, out_shape=out_shape))

        out_lvl = levels[-1]
        nseg_out = csf.nfib[out_lvl] if out_lvl > 0 else 1
        dtype = jnp.result_type(dtype, *[a.dtype for a in link_arrays])
        nseg_lvls = tuple(csf.nfib[l] if l > 0 else 1 for l in levels)
        ir = StageIR(kind="chain", stage=stage, links=tuple(links),
                     nseg_out=nseg_out, nseg_lvls=nseg_lvls)
        self.emitted_stages.append(stage)
        self.emitted_chains.append((stage, tuple(links)))
        self.emitted_ir.append(ir)
        with self._scope("stage.chain"):
            out2d = self.lowering.chain(ir, segs, mask, padded, link_arrays,
                                        dtype)
            arr = out2d.reshape((nseg_out,) + out_shape)
            if out_lvl == 0:
                arr = arr.reshape(out_shape)
        self.stage_strategy[(lvl0, out_lvl)] = "fused"
        if out_lvl == 0:
            return DenseVal(arr, out_dense)
        return FiberVal(arr, out_lvl, out_dense)

    # -- the lowering unit ---------------------------------------------- #
    def _fiber_contract(self, csf: CSFArrays, fa, da, fb, db,
                        out_dense: tuple[str, ...], lvl: int,
                        out_lvl: int) -> jnp.ndarray:
        dims = self.spec.dims
        nfib = csf.nfib[lvl]
        oshape = tuple(dims[i] for i in out_dense)
        dtype = jnp.result_type(fa.dtype, fb.dtype)
        reduce_ = out_lvl < lvl

        if nfib == 0:
            if out_lvl == 0:
                return jnp.zeros(oshape, dtype)
            rows = csf.nfib[out_lvl] if reduce_ else 0
            return jnp.zeros((rows,) + oshape, dtype)

        operands, arrays = [], []
        for arr, inds in ((fa, da), (fb, db)):
            shape = tuple(dims[i] for i in inds)
            fiber = arr.ndim == len(inds) + 1
            operands.append(StageOperand(
                subs="".join(self._letter[i] for i in inds),
                shape=shape, fiber=fiber))
            arrays.append(arr)
        out_subs = "".join(self._letter[i] for i in out_dense)

        if reduce_ and self._use_row(csf, lvl, out_lvl):
            nseg, gather, mask, block_seg = self._layout(csf, lvl, out_lvl)
            with self._scope("lift"):
                padded = [
                    arr.reshape(nfib, -1)[gather] if op.fiber
                    else arr.reshape(1, -1)
                    for arr, op in zip(arrays, operands)]
            stage = Stage(operands=tuple(operands), out_subs=out_subs,
                          out_shape=oshape, reduce=True, block=self.block,
                          nseg=nseg, interpret=self.interpret,
                          tile=self.tile_align)
            ir = StageIR(kind="reduce", stage=stage)
            self.emitted_stages.append(stage)
            self.emitted_ir.append(ir)
            with self._scope("stage.reduce"):
                out2d = self.lowering.reduce(ir, block_seg, mask, padded,
                                             dtype)
                arr = out2d.reshape((nseg,) + oshape)
                return arr.reshape(oshape) if out_lvl == 0 else arr

        # product stage: fused per-fiber contraction; the sparse reduction
        # (if any) is the engines' shared sorted segment sum
        P = round_up(nfib, self.block)
        padded = []
        with self._scope("lift"):
            for arr, op in zip(arrays, operands):
                if op.fiber:
                    flat = arr.reshape(nfib, -1)
                    padded.append(jnp.pad(flat, ((0, P - nfib), (0, 0))))
                else:
                    padded.append(arr.reshape(1, -1))
        stage = Stage(operands=tuple(operands), out_subs=out_subs,
                      out_shape=oshape, reduce=False, block=self.block,
                      nseg=0, interpret=self.interpret,
                      tile=self.tile_align)
        ir = StageIR(kind="product", stage=stage)
        self.emitted_stages.append(stage)
        self.emitted_ir.append(ir)
        with self._scope("stage.product"):
            per_fiber = self.lowering.product(ir, padded, dtype)
            arr = per_fiber[:nfib].reshape((nfib,) + oshape)
        if reduce_:
            arr = self._segment_sum(csf, arr, lvl, out_lvl)
        self._count_fiber_bytes(csf, lvl, out_dense, dtype)
        return arr
