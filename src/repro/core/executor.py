"""SpTTN loop-nest execution (paper §5.1, Algorithm 2) — three engines.

1. :func:`reference_execute` — a *literal* implementation of Algorithm 2:
   recursive loop-nest generation over the CSF tree with buffer reset rules.
   Pure numpy, exponentially slow, used as the semantic oracle.

2. :class:`VectorizedExecutor` — the XLA engine.  The same fused
   loop-nest plan is compiled to a vectorized JAX program:
     * sparse loops          -> flattened fiber arrays (gather / segment_sum)
     * innermost dense loops -> a single einsum/dot_general (MXU; the
                                paper's BLAS offload, §5.1/Fig 7)
     * loop fusion depth     -> the CSF level at which each intermediate is
                                materialized (nnz^(I1..Ip) x dense buffer)
   This is the TPU adaptation documented in DESIGN.md §3.

3. ``backend="pallas"`` — :class:`repro.kernels.codegen.PallasPlanExecutor`,
   a code generator that lowers the same plan to fused Pallas TPU kernels
   (block-segment grids + VMEM accumulators, DESIGN.md §6).

4. ``backend="pallas-gpu"`` — the same code generator driving the
   Mosaic-GPU-style stage lowering (split-K over segment ranges + a
   segment-combine pass, docs/backends.md): GPU grids guarantee no
   sequential execution, so the TPU lowering's revisited VMEM
   accumulator is replaced, behind the same target-neutral stage IR.

Select an engine with :func:`make_executor`; all four share one
semantics.
"""
from __future__ import annotations

import dataclasses
import json
import string
from collections.abc import Mapping, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.analysis.diagnostics import (BACKENDS, PALLAS_BACKENDS,
                                        PALLAS_TARGETS)
from repro.core.loopnest import LoopOrder, buffer_indices
from repro.core.paths import ContractionPath, Term, consumer_map
from repro.core.spec import SpTTNSpec
from repro.sparse.csf import CSFTensor, level_segments
from repro.spans import count, span

# The three execution engines (DESIGN.md §3/§6) live in ``BACKENDS``,
# owned by the static verifier (repro.analysis.invariants) and
# re-exported here: ``backend`` is a plan attribute — the autotuner
# measures schedules per backend and the winner's backend is persisted
# with the plan — and verification must share the same vocabulary.


# =========================================================================== #
# Plan serialization (DESIGN.md §4) — plans are pattern-static, so a chosen
# schedule survives process restarts via the autotuner's disk cache.
# Version 2 added the ``backend`` field; version 3 added the ``mesh``
# shard-context field (DESIGN.md §7); version 4 added the ``fused`` flag
# (single-kernel chain lowering on the Pallas backend, DESIGN.md §6);
# version 5 added the ``block`` field (the tuned Pallas fiber block size,
# DESIGN.md §8 — ``null`` means engine default / non-Pallas backend);
# version 6 adds the ``slice_mode``/``slice_chunks`` fields (the
# memory-budgeted slicing decision of DESIGN.md §10 — ``null``/1 means
# the plan fits its budget, or was never budgeted).
# Any other version is rejected — the forward/backward-compat rule is
# "re-plan, never guess".
# =========================================================================== #
PLAN_JSON_VERSION = 6


def _operand_to_dict(op) -> dict:
    return {"name": op.name, "indices": list(op.indices),
            "sparse": bool(op.is_sparse)}


def _operand_from_dict(d):
    from repro.core.paths import Operand
    return Operand(name=d["name"], indices=tuple(d["indices"]),
                   is_sparse=bool(d["sparse"]))


def plan_to_dict(plan) -> dict:
    """Serialize an :class:`~repro.core.planner.SpTTNPlan` to plain JSON
    types.  Everything a plan holds is structural (names, index tuples,
    dims) plus float diagnostics, so the round trip is exact."""
    spec = plan.spec
    return {
        "version": PLAN_JSON_VERSION,
        "spec": {
            "inputs": [_operand_to_dict(t) for t in spec.inputs],
            "output": _operand_to_dict(spec.output),
            "dims": {k: int(v) for k, v in spec.dims.items()},
        },
        "path": [{"lhs": _operand_to_dict(t.lhs),
                  "rhs": _operand_to_dict(t.rhs),
                  "out": _operand_to_dict(t.out)} for t in plan.path],
        "order": [list(a) for a in plan.order],
        "cost": plan.cost,
        "flops": plan.flops,
        "depth": plan.depth,
        "backend": plan.backend,
        "mesh": None if plan.mesh is None else dict(plan.mesh),
        "fused": bool(plan.fused),
        "block": None if plan.block is None else int(plan.block),
        "slice_mode": plan.slice_mode,
        "slice_chunks": int(plan.slice_chunks),
    }


def plan_from_dict(doc: dict):
    # lazy: core.executor is imported during repro.core package init,
    # before repro.analysis.invariants can finish (it imports core
    # submodules); only the leaf diagnostics module is safe at top level
    from repro.analysis.invariants import (check_block, check_mesh,
                                           check_slice)
    from repro.core.paths import Term
    from repro.core.planner import SpTTNPlan
    if doc.get("version") != PLAN_JSON_VERSION:
        # found vs expected, spelled out: version triage on a corrupt or
        # stale cache must never be guesswork [SPTTN-E060]
        raise ValueError(
            f"unsupported plan version {doc.get('version')!r}: plan JSON "
            f"v{doc.get('version')}, expected v{PLAN_JSON_VERSION}; "
            "re-plan, never guess [SPTTN-E060]")
    sd = doc["spec"]
    spec = SpTTNSpec(
        inputs=tuple(_tensor_ref(t) for t in sd["inputs"]),
        output=_tensor_ref(sd["output"]),
        dims=dict(sd["dims"]))
    path = tuple(Term(lhs=_operand_from_dict(t["lhs"]),
                      rhs=_operand_from_dict(t["rhs"]),
                      out=_operand_from_dict(t["out"]))
                 for t in doc["path"])
    order = tuple(tuple(a) for a in doc["order"])
    backend = doc.get("backend", "xla")
    if backend not in BACKENDS:
        raise ValueError(f"unknown plan backend {backend!r}; expected one "
                         f"of {BACKENDS} [SPTTN-E040]")
    mesh = doc.get("mesh")
    for d in check_mesh(mesh):
        raise ValueError(f"{d.message} [{d.code}]")
    fused = doc.get("fused", False)
    if not isinstance(fused, bool):
        raise ValueError(f"plan fused must be a boolean, got {fused!r}")
    block = doc.get("block")
    if block is not None and (not isinstance(block, int)
                              or isinstance(block, bool)):
        raise ValueError("plan block must be a positive multiple of 8 "
                         f"or null, got {block!r}")
    for d in check_block(block):
        # the sweep only ever emits sublane-aligned blocks (DESIGN.md §8);
        # accepting a misaligned one here would let compiled-mode replay
        # silently round it — rejected, never coerced
        raise ValueError("plan block must be a positive multiple of 8 "
                         f"or null, got {block!r} [{d.code}]")
    smode = doc.get("slice_mode")
    schunks = doc.get("slice_chunks", 1)
    if smode is not None and not isinstance(smode, str):
        raise ValueError(f"plan slice_mode must be a string or null, "
                         f"got {smode!r}")
    if (not isinstance(schunks, int) or isinstance(schunks, bool)
            or schunks < 1):
        raise ValueError(f"plan slice_chunks must be a positive int, "
                         f"got {schunks!r}")
    # the decision is only ever stamped for a real split of a dense mode
    # (DESIGN.md §10); anything else is a foreign/corrupt doc — rejected
    # by the verifier's slice-kind invariants, never coerced
    for d in check_slice(spec, smode, schunks):
        raise ValueError(f"plan {d.message} [{d.code}]")
    return SpTTNPlan(spec=spec, path=path, order=order, cost=doc["cost"],
                     flops=doc["flops"], depth=doc["depth"], backend=backend,
                     mesh=mesh, fused=fused, block=block,
                     slice_mode=smode, slice_chunks=schunks)


def _tensor_ref(d):
    from repro.core.spec import TensorRef
    return TensorRef(name=d["name"], indices=tuple(d["indices"]),
                     is_sparse=bool(d["sparse"]))


def plan_to_json(plan) -> str:
    return json.dumps(plan_to_dict(plan), sort_keys=True)


def plan_from_json(s: str):
    return plan_from_dict(json.loads(s))


# =========================================================================== #
# Reference engine — Algorithm 2, literally
# =========================================================================== #
def _children_ptr(csf: CSFTensor, p: int) -> np.ndarray:
    """Start offsets of each level-(p-1) fiber's children among level-p
    fibers (contiguous because coordinates are lexicographically sorted)."""
    nparent = csf.nfib[p - 1] if p > 1 else 1
    if csf.nfib.get(p, 0) == 0:
        return np.zeros(nparent + 1, dtype=np.int64)
    parents = csf.parent[p] if p > 1 else np.zeros(csf.nfib[p], dtype=np.int32)
    return np.searchsorted(parents, np.arange(nparent + 1))


def reference_execute(spec: SpTTNSpec, path: ContractionPath,
                      order: LoopOrder, csf: CSFTensor,
                      factors: Mapping[str, np.ndarray]) -> np.ndarray:
    """Execute a fused loop nest exactly as Algorithm 2 would (numpy loops).

    Returns the DENSE output (sparse-pattern outputs are densified so tests
    can compare against einsum oracles directly).
    """
    spos = {s: i for i, s in enumerate(spec.sparse_indices)}
    cons = consumer_map(path)
    binds = buffer_indices(path, order)
    dims = spec.dims

    # dense buffer allocation (reference keeps buffers at full declared size)
    bufs: dict[str, np.ndarray] = {}
    for u, inds in binds.items():
        bufs[path[u].out.name] = np.zeros([dims[i] for i in inds],
                                          dtype=np.float64)
    buf_inds = {path[u].out.name: inds for u, inds in binds.items()}
    out_arr = np.zeros([dims[i] for i in spec.output.indices],
                       dtype=np.float64)

    ptr = {p: _children_ptr(csf, p) for p in range(1, csf.order + 1)}

    def term_value(op, env, fibers):
        if op.name in factors:
            return factors[op.name][tuple(env[i] for i in op.indices)]
        if op.is_sparse and op.name == spec.sparse_input.name:
            # the sparse tensor's term always has a full fiber chain: its
            # sparse loops appear in storage order on the leaf's root path
            assert len(fibers) == csf.order, "broken CSF chain at sparse leaf"
            return csf.values[fibers[-1]]
        b = bufs[op.name]
        return b[tuple(env[i] for i in buf_inds[op.name])]

    def exec_term(tid: int, env, fibers):
        t = path[tid]
        val = term_value(t.lhs, env, fibers) * term_value(t.rhs, env, fibers)
        if t.out.name == "OUT":
            out_arr[tuple(env[i] for i in spec.output.indices)] += val
        else:
            bufs[t.out.name][tuple(env[i] for i in buf_inds[t.out.name])] += val

    def loop_nest(seq, env, fibers):
        """seq: (term_id, remaining_order) pairs; ``fibers`` is the chain of
        CSF fiber ids bound so far (levels 1..len(fibers) consecutively).

        Buffer reset per Algorithm 2: a producer/consumer pair whose fused
        loops diverge at this level has a buffer private to one iteration of
        the enclosing loops, so it is zeroed here (they never rejoin deeper,
        hence the reset fires exactly once per enclosing iteration)."""
        pos_in = {tid: n for n, (tid, _) in enumerate(seq)}
        for u, v in cons.items():
            if u in pos_in and v in pos_in:
                if not _same_group(seq, pos_in[u], pos_in[v]):
                    bufs[path[u].out.name][...] = 0.0

        i = 0
        while i < len(seq):
            tid, rem = seq[i]
            if not rem:
                exec_term(tid, env, fibers)
                i += 1
                continue
            q = rem[0]
            group = []
            j = i
            while j < len(seq) and seq[j][1] and seq[j][1][0] == q:
                group.append((seq[j][0], seq[j][1][1:]))
                j += 1
            lvl = spos[q] + 1 if q in spos else None
            if lvl is not None and len(fibers) == lvl - 1:
                # sparse loop with intact chain: iterate CSF children
                parent = fibers[-1] if fibers else 0
                for fib in range(ptr[lvl][parent], ptr[lvl][parent + 1]):
                    env2 = dict(env)
                    env2[q] = int(csf.coord[lvl][fib])
                    loop_nest(group, env2, fibers + (fib,))
            else:
                # dense loop (also the correct semantics for a sparse index
                # whose CSF chain is broken — all reads are then from dense
                # buffers/factors, e.g. a non-prefix intermediate)
                for v in range(dims[q]):
                    env2 = dict(env)
                    env2[q] = v
                    loop_nest(group, env2, fibers)
            i = j
        return

    def _same_group(seq, iu, iv):
        """True if positions iu..iv all share the same leading index."""
        ru = seq[iu][1]
        if not ru:
            return False
        q = ru[0]
        for t in range(iu, iv + 1):
            r = seq[t][1]
            if not r or r[0] != q:
                return False
        return True

    loop_nest([(i, a) for i, a in enumerate(order)], {}, ())
    return out_arr


def dense_oracle(spec: SpTTNSpec, csf: CSFTensor,
                 factors: Mapping[str, np.ndarray]) -> np.ndarray:
    """np.einsum over densified operands — the ultimate ground truth."""
    letters = {}
    for i in spec.all_indices:
        letters[i] = string.ascii_lowercase[len(letters)]
    operands, subs = [], []
    for t in spec.inputs:
        if t.is_sparse:
            operands.append(csf.coo.to_dense().astype(np.float64))
        else:
            operands.append(np.asarray(factors[t.name], dtype=np.float64))
        subs.append("".join(letters[i] for i in t.indices))
    out_sub = "".join(letters[i] for i in spec.output.indices)
    return np.einsum(",".join(subs) + "->" + out_sub, *operands)


# =========================================================================== #
# Vectorized JAX engine
# =========================================================================== #
@dataclasses.dataclass
class FiberVal:
    """A tensor carried on the level-p fibers of the sparse tensor:
    array shape = (nfib_p, *dense_dims)."""
    array: jnp.ndarray
    level: int
    dense: tuple[str, ...]


@dataclasses.dataclass
class DenseVal:
    array: jnp.ndarray
    indices: tuple[str, ...]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CSFArrays:
    """Device-resident CSF (one-time upload; pattern is fixed).

    A pytree: its arrays, and the block layouts a Pallas engine attached
    (:func:`prepare_operand`), are the leaves, and the fiber counts are
    static.  So ``jax.jit(ex.__call__)(arrays, factors)`` compiles the
    operand as arguments, one program per pattern shape, instead of
    folding hundreds of MB of index arrays into the program as
    constants.  The host tensor does not cross the boundary."""
    values: jnp.ndarray
    fiber_coord: dict[int, dict[int, jnp.ndarray]]  # level -> mode -> coords
    seg: dict[tuple[int, int], jnp.ndarray]         # (child, parent) -> map
    nfib: dict[int, int]
    order: int
    shape: tuple[int, ...]
    host: "CSFTensor | None" = None   # source tensor (reference engine)

    @classmethod
    @span("csf.upload")
    def from_csf(cls, csf: CSFTensor) -> "CSFArrays":
        """Derive the fiber coordinates and segment maps on the host and
        start their upload with the values (span ``csf.upload``, which
        does not wait for the transfers; their bytes are counted in
        ``csf.upload_bytes``)."""
        fiber_coord: dict[int, dict[int, jnp.ndarray]] = {}
        for p in range(1, csf.order + 1):
            fc = csf.fiber_coords(p)
            fiber_coord[p] = {m: jnp.asarray(fc[:, m]) for m in range(p)}
        seg = {}
        for child in range(1, csf.order + 1):
            for par in range(0, child):
                seg[(child, par)] = jnp.asarray(
                    level_segments(csf, child, par))
        out = cls(values=jnp.asarray(csf.values),
                  fiber_coord=fiber_coord, seg=seg,
                  nfib=dict(csf.nfib), order=csf.order,
                  shape=csf.shape, host=csf)
        count("csf.upload_bytes",
              sum(x.nbytes for x in jax.tree.leaves(out)))
        return out

    def tree_flatten(self):
        layouts = self.__dict__.get("_codegen_layouts", {})
        keys = tuple(layouts)
        children = (self.values, self.fiber_coord, self.seg,
                    tuple(layouts[k][1:] for k in keys))
        aux = (tuple(sorted(self.nfib.items())), self.order,
               tuple(self.shape), keys,
               tuple(layouts[k][0] for k in keys))
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        nfib, order, shape, keys, nsegs = aux
        values, fiber_coord, seg, entries = children
        out = cls(values=values, fiber_coord=fiber_coord, seg=seg,
                  nfib=dict(nfib), order=order, shape=shape)
        if keys:
            out.__dict__["_codegen_layouts"] = {
                k: (n, *e) for k, n, e in zip(keys, nsegs, entries)}
        return out


def prepare_operand(ex, csf, factors: Mapping) -> CSFArrays:
    """The operand, ready to be a ``jax.jit`` argument of ``ex``.

    Pallas engines read their block layouts off the concrete segment
    maps while tracing.  One abstract trace (``jax.eval_shape``, nothing
    compiles) attaches every layout ``ex`` needs to the operand, on the
    device, so a jitted ``ex.__call__(arrays, factors)`` receives them as
    arguments like the rest of the CSF.  ``factors`` gives the shapes.
    """
    arrays = csf if isinstance(csf, CSFArrays) else CSFArrays.from_csf(csf)
    jax.eval_shape(lambda f: ex(arrays, f), factors)
    cache = arrays.__dict__.get("_codegen_layouts", {})
    for k, (nseg, *entry) in cache.items():
        cache[k] = (nseg, *jax.tree.map(jnp.asarray, entry))
    return arrays


def layout_bytes(arrays: CSFArrays) -> int:
    """Bytes of the block layouts attached to ``arrays``."""
    layouts = arrays.__dict__.get("_codegen_layouts", {})
    return sum(x.nbytes for e in layouts.values()
               for x in jax.tree.leaves(e[1:]))


def jit_bound(ex, csf, device=None):
    """``factors -> ex(csf, factors)``, jitted, with the operand passed
    as an argument (:func:`prepare_operand`, on the first call, when the
    factor shapes are known).  With ``device`` the operand, the factors
    and so the whole program live on that device."""
    run = jax.jit(ex.__call__)
    operand = None

    def call(factors):
        nonlocal operand
        if operand is None:
            operand = prepare_operand(ex, csf, factors)
            if device is not None:
                operand = jax.device_put(operand, device)
        if device is not None:
            factors = jax.device_put(dict(factors), device)
        return run(operand, factors)

    return call


def key_compile_cache_by_metadata() -> None:
    """Make op metadata part of JAX's persistent compilation cache key.

    The term scopes live only in op metadata, which the key leaves out by
    default.  A cache shared with programs that had the same ops under
    other scopes, or none (another checkout, an older build), would then
    hand back their executables, and a device trace would attribute time
    to the wrong terms, or to none.

    The setting is JAX's, so it holds for every program the process
    compiles from then on, SpTTN or not.  Its cost falls on processes
    that keep a persistent cache: the metadata holds source files and
    lines, so an edit that moves a traced line, or the same code at
    another path, compiles anew instead of hitting the cache.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


class VectorizedExecutor:
    """Compile a (path, order) plan into a JAX function over CSF arrays.

    The plan's fused sparse depth per intermediate decides the CSF level at
    which it is materialized; trailing dense loops become one einsum.

    Every op a term lowers to carries a named scope ``t<i>.<kind>`` in
    its metadata (:meth:`_scope`), and the output's materialization the
    scope ``out``, so a device trace attributes time to loop-nest terms.
    Building an engine keys JAX's persistent compilation cache by that
    metadata too (:func:`key_compile_cache_by_metadata`).
    """

    def __init__(self, spec: SpTTNSpec, path: ContractionPath,
                 order: LoopOrder):
        self.spec = spec
        self.path = path
        self.order = order
        self.spos = {s: i for i, s in enumerate(spec.sparse_indices)}
        from repro.core.loopnest import fused_sparse_depth
        self.fuse_depth = fused_sparse_depth(path, order, spec.sparse_indices)
        self._tid = 0      # term being lowered (named scopes, _scope)
        key_compile_cache_by_metadata()
        self._letter = {}
        for i in spec.all_indices:
            self._letter[i] = string.ascii_lowercase[len(self._letter)]

    # -- helpers -------------------------------------------------------- #
    def _sparse_level(self, inds: Sequence[str]) -> int:
        return max((self.spos[i] + 1 for i in inds if i in self.spos),
                   default=0)

    def _scope(self, kind: str):
        """``jax.named_scope`` ``t<i>.<kind>`` of the term being lowered
        (``_tid``, set while tracing): ``lift`` for gathers onto fibers,
        ``contract`` for the per-fiber einsum, ``reduce`` for segmented
        sums, ``scatter`` for a final scatter-add, ``dense`` for the dense
        fallback, ``stage.<kind>`` for a generated Pallas stage.  Compile-
        time metadata only: the program's ops are unchanged."""
        return jax.named_scope(f"t{self._tid}.{kind}")

    def _is_prefix(self, inds: Sequence[str]) -> bool:
        """True if the sparse indices of ``inds`` form a CSF storage prefix."""
        sp = sorted(self.spos[i] for i in inds if i in self.spos)
        return sp == list(range(len(sp)))

    def _lift_dense_factor(self, csf: CSFArrays, arr: jnp.ndarray,
                           inds: tuple[str, ...], level: int
                           ) -> tuple[jnp.ndarray, tuple[str, ...]]:
        """Gather a dense operand's rows onto level-``level`` fibers, one
        gather per sparse index it carries."""
        sp_axes = [(ax, self.spos[i] ) for ax, i in enumerate(inds)
                   if i in self.spos]
        if not sp_axes:
            return arr, inds
        take = arr
        dense_inds = tuple(i for i in inds if i not in self.spos)
        # build advanced-index tuple
        index_tuple = []
        for ax, i in enumerate(inds):
            if i in self.spos:
                index_tuple.append(csf.fiber_coord[level][self.spos[i]])
            else:
                index_tuple.append(slice(None))
        # numpy-style mixed advanced indexing: all advanced indices are 1-D
        # fiber-length vectors -> broadcast to a single fiber axis in front
        out = take[tuple(index_tuple)]
        # jnp places the broadcast advanced axis first when advanced indices
        # are non-contiguous; when contiguous it stays in place.  Normalize:
        adv_pos = [ax for ax, i in enumerate(inds) if i in self.spos]
        contiguous = adv_pos == list(range(adv_pos[0], adv_pos[0] + len(adv_pos)))
        if contiguous and adv_pos[0] != 0:
            # fiber axis sits at adv_pos[0]; move to front
            out = jnp.moveaxis(out, adv_pos[0], 0)
        return out, dense_inds

    def _einsum(self, a: jnp.ndarray, ai: Sequence[str],
                b: jnp.ndarray, bi: Sequence[str],
                oi: Sequence[str], fiber: bool) -> jnp.ndarray:
        L = self._letter
        batch = "Z" if fiber else ""
        sa = batch + "".join(L[i] for i in ai)
        sb = batch + "".join(L[i] for i in bi)
        so = batch + "".join(L[i] for i in oi)
        return jnp.einsum(f"{sa},{sb}->{so}", a, b)

    # -- main ----------------------------------------------------------- #
    def _get_operand(self, csf: CSFArrays, factors: Mapping, env: dict,
                     op) -> "FiberVal | DenseVal":
        if op.is_sparse and op.name == self.spec.sparse_input.name:
            return FiberVal(csf.values, csf.order, ())
        if op.name in factors:
            return DenseVal(jnp.asarray(factors[op.name]), op.indices)
        return env[op.name]

    def _to_dense(self, csf: CSFArrays, v: "FiberVal | DenseVal",
                  want: tuple[str, ...]) -> jnp.ndarray:
        """Materialize onto a dense array with index order ``want``."""
        spec = self.spec
        if isinstance(v, DenseVal):
            perm = [v.indices.index(i) for i in want]
            return jnp.transpose(v.array, perm)
        # scatter fiber rows into a dense array over its sparse prefix
        sp_inds = tuple(spec.sparse_indices[:v.level])
        full = sp_inds + v.dense
        shape = [spec.dims[i] for i in full]
        coords = tuple(csf.fiber_coord[v.level][m] for m in range(v.level))
        out = jnp.zeros(shape, v.array.dtype).at[coords].add(
            v.array, unique_indices=True)  # distinct fibers: no dups
        perm = [full.index(i) for i in want]
        return jnp.transpose(out, perm)

    def _chain_len(self, tid: int) -> int:
        """Number of consecutive terms starting at ``tid`` this engine
        executes as one unit.  The XLA engine is strictly one term per
        lowering; the Pallas engine overrides this with its detected
        fused chains (DESIGN.md §6)."""
        return 1

    def _exec_chain(self, csf: CSFArrays, factors: Mapping, env: dict,
                    tid: int, length: int):
        raise NotImplementedError   # pragma: no cover - chain engines only

    def _exec_term(self, csf: CSFArrays, factors: Mapping, env: dict,
                   term: Term) -> "FiberVal | DenseVal":
        """Execute one contraction term, returning its intermediate value
        (a final term's value is materialized by ``_materialize_output``)."""
        a = self._get_operand(csf, factors, env, term.lhs)
        b = self._get_operand(csf, factors, env, term.rhs)
        out_inds = term.out.indices
        term_sp = [i for i in term.indices if i in self.spos]
        prefix_ok = (self._is_prefix(term.indices)
                     and self._is_prefix(out_inds))
        is_final = term.out.name == "OUT"

        if term_sp and prefix_ok and (isinstance(a, FiberVal)
                                      or isinstance(b, FiberVal)):
            return self._exec_fiber_term(csf, term, a, b)
        if (term_sp and is_final and self._is_prefix(term.indices)
                and (isinstance(a, FiberVal) or isinstance(b, FiberVal))):
            # final term keeping a non-prefix sparse subset (e.g. TTTc's
            # OUT(e,n)): einsum at the term level, then scatter-add by
            # the kept coordinate columns (implicitly summing the rest)
            arr = self._exec_final_scatter(csf, term, a, b)
            return DenseVal(arr, self.spec.output.indices)
        # dense fallback (covers dense x dense and non-prefix cases)
        ai = tuple(term.lhs.indices)
        bi = tuple(term.rhs.indices)
        with self._scope("dense"):
            da = self._to_dense(csf, a, ai)
            db = self._to_dense(csf, b, bi)
            arr = self._einsum(da, ai, db, bi, out_inds, fiber=False)
        return DenseVal(arr, out_inds)

    def _materialize_output(self, csf: CSFArrays,
                            val: "FiberVal | DenseVal") -> jnp.ndarray:
        spec = self.spec
        if isinstance(val, DenseVal):
            perm = [val.indices.index(i) for i in spec.output.indices]
            return jnp.transpose(val.array, perm)
        if spec.output_is_sparse:
            # same-sparsity output: return leaf values (level = order)
            assert val.level == csf.order and not val.dense
            return val.array
        return self._to_dense(csf, val, spec.output.indices)

    def __call__(self, csf: CSFArrays,
                 factors: Mapping[str, jnp.ndarray]) -> jnp.ndarray:
        env: dict[str, FiberVal | DenseVal] = {}
        tid, n = 0, len(self.path)
        while tid < n:
            self._tid = tid
            length = self._chain_len(tid)
            if length > 1:
                val = self._exec_chain(csf, factors, env, tid, length)
                term = self.path[tid + length - 1]
                tid += length
            else:
                term = self.path[tid]
                val = self._exec_term(csf, factors, env, term)
                tid += 1
            if term.out.name == "OUT":
                with jax.named_scope("out"):
                    return self._materialize_output(csf, val)
            env[term.out.name] = val
        raise AssertionError("path had no final term")

    # ------------------------------------------------------------------ #
    def _lift(self, csf: CSFArrays, v, ref, lvl: int):
        """Bring an operand onto level-``lvl`` fibers."""
        with self._scope("lift"):
            if isinstance(v, FiberVal):
                arr = v.array
                if v.level < lvl:
                    arr = arr[csf.seg[(lvl, v.level)]]
                return arr, v.dense
            return self._lift_dense_factor(csf, v.array, ref.indices, lvl)

    def _exec_final_scatter(self, csf: CSFArrays, term: Term, a, b):
        """Final term whose kept sparse indices are not a storage prefix:
        scatter-add fiber rows by the kept coordinate columns."""
        spec = self.spec
        lvl = self._sparse_level(term.indices)
        fa, da = self._lift(csf, a, term.lhs, lvl)
        fb, db = self._lift(csf, b, term.rhs, lvl)
        out_inds = spec.output.indices
        out_sp = [i for i in out_inds if i in self.spos]
        out_dense = tuple(i for i in out_inds if i not in self.spos)
        arr = self._fiber_contract(csf, fa, da, fb, db, out_dense, lvl, lvl)
        coords = tuple(csf.fiber_coord[lvl][self.spos[i]] for i in out_sp)
        shape = [spec.dims[i] for i in out_sp] + \
            [spec.dims[i] for i in out_dense]
        full = tuple(out_sp) + out_dense
        perm = [full.index(i) for i in out_inds]
        with self._scope("scatter"):
            out = jnp.zeros(shape, arr.dtype).at[coords].add(arr)
            return jnp.transpose(out, perm) \
                if perm != list(range(len(perm))) else out

    def _exec_fiber_term(self, csf: CSFArrays, term: Term,
                         a: "FiberVal | DenseVal",
                         b: "FiberVal | DenseVal") -> FiberVal:
        """sparse-structured term: lift to the term's CSF level, contract the
        dense dims (MXU), segment-reduce to the output's level."""
        lvl = self._sparse_level(term.indices)
        out_lvl = self._sparse_level(term.out.indices)

        fa, da = self._lift(csf, a, term.lhs, lvl)
        fb, db = self._lift(csf, b, term.rhs, lvl)
        sp = set(self.spos)
        out_dense = tuple(i for i in term.out.indices if i not in sp)
        arr = self._fiber_contract(csf, fa, da, fb, db, out_dense, lvl,
                                   out_lvl)
        if out_lvl == 0:
            return DenseVal(arr, out_dense)      # fully contracted prefix
        return FiberVal(arr, out_lvl, out_dense)

    def _fiber_contract(self, csf: CSFArrays, fa, da, fb, db,
                        out_dense: tuple[str, ...], lvl: int,
                        out_lvl: int) -> jnp.ndarray:
        """Contract two level-``lvl`` operands and reduce to ``out_lvl``.

        The overridable lowering unit shared by the XLA and Pallas engines:
        dense-contracted indices collapse into one einsum (BLAS/MXU) and
        the sparse reduction becomes a segmented sum.  ``out_lvl == lvl``
        means no sparse reduction (per-fiber output); ``out_lvl == 0``
        returns the dense array of shape ``out_dense``.
        """
        with self._scope("contract"):
            arr = self._einsum(fa, da, fb, db, out_dense, fiber=True)
        if out_lvl < lvl:
            arr = self._segment_sum(csf, arr, lvl, out_lvl)
        return arr

    def _segment_sum(self, csf: CSFArrays, arr: jnp.ndarray, lvl: int,
                     out_lvl: int) -> jnp.ndarray:
        """Sum level-``lvl`` fiber rows into their level-``out_lvl``
        parents (``out_lvl == 0``: into one row, returned without its
        fiber axis), under the term's ``reduce`` scope.

        CSF order is lexicographic, so the segment ids are sorted.  On
        TPU a float32 reduction whose operand carries its merge path
        (:func:`repro.kernels.segment_sum.attached_work`) runs the
        one-hot kernel and counts ``reduce.kernel``; any other runs XLA's
        sorted ``segment_sum`` and counts ``reduce.scatter``.
        """
        from repro.kernels import segment_sum as ss
        with self._scope("reduce"):
            work = ss.attached_work(csf, lvl, out_lvl, arr.dtype)
            if work is not None:
                count("reduce.kernel", 1)
                return ss.sorted_segment_sum(arr, csf.seg[(lvl, out_lvl)],
                                             csf.nfib[out_lvl], work)
            count("reduce.scatter", 1)
            seg = csf.seg[(lvl, out_lvl)] if out_lvl > 0 else \
                jnp.zeros(arr.shape[0], jnp.int32)
            nseg = csf.nfib[out_lvl] if out_lvl > 0 else 1
            arr = jax.ops.segment_sum(arr, seg, num_segments=nseg,
                                      indices_are_sorted=True)
            return arr[0] if out_lvl == 0 else arr


def execute_unfactorized(spec: SpTTNSpec, csf: CSFArrays,
                         factors: Mapping[str, jnp.ndarray]) -> jnp.ndarray:
    """The 'unfactorized' schedule (paper §2.4.1): all factors gathered to
    the leaves and multiplied in one pass (TACO/COMET default).  Kept as a
    baseline for the benchmarks."""
    spos = {s: i for i, s in enumerate(spec.sparse_indices)}
    letters = {}
    for i in spec.all_indices:
        letters[i] = string.ascii_lowercase[len(letters)]
    lvl = csf.order
    operands = [csf.values]
    subs = ["Z"]
    for t in spec.inputs:
        if t.is_sparse:
            continue
        arr = jnp.asarray(factors[t.name])
        idx = []
        for ax, i in enumerate(t.indices):
            if i in spos:
                idx.append(csf.fiber_coord[lvl][spos[i]])
            else:
                idx.append(slice(None))
        g = arr[tuple(idx)]
        adv = [ax for ax, i in enumerate(t.indices) if i in spos]
        if adv and adv != list(range(adv[0], adv[0] + len(adv))):
            pass  # jnp already moved fiber axis front
        elif adv and adv[0] != 0:
            g = jnp.moveaxis(g, adv[0], 0)
        operands.append(g)
        subs.append("Z" + "".join(letters[i] for i in t.indices
                                  if i not in spos))
    out_sp = [i for i in spec.output.indices if i in spos]
    out_dn = [i for i in spec.output.indices if i not in spos]
    expr = ",".join(subs) + "->Z" + "".join(letters[i] for i in out_dn)
    per_leaf = jnp.einsum(expr, *operands)
    if spec.output_is_sparse:
        return per_leaf
    p_out = len(out_sp)
    if p_out < lvl:
        seg = csf.seg[(lvl, p_out)] if p_out > 0 else jnp.zeros(
            per_leaf.shape[0], jnp.int32)
        nseg = csf.nfib[p_out] if p_out > 0 else 1
        per_leaf = jax.ops.segment_sum(per_leaf, seg, num_segments=nseg,
                                       indices_are_sorted=True)
    # scatter onto the dense output over the sparse output indices
    full = tuple(out_sp) + tuple(out_dn)
    if p_out == 0:
        out = per_leaf[0]
    else:
        shape = [spec.dims[i] for i in full]
        coords = tuple(csf.fiber_coord[p_out][m] for m in range(p_out))
        out = jnp.zeros(shape, per_leaf.dtype).at[coords].add(
            per_leaf, unique_indices=True)
    perm = [full.index(i) for i in spec.output.indices]
    return jnp.transpose(out, perm) if perm != list(range(len(perm))) else out


# =========================================================================== #
# Engine registry
# =========================================================================== #
class ReferenceExecutor:
    """Algorithm-2 interpreter behind the common executor signature.

    Accepts a host :class:`CSFTensor` or a :class:`CSFArrays` built via
    :meth:`CSFArrays.from_csf` (which retains the host tensor).  Output is
    always the dense numpy array; sparse-pattern outputs are densified —
    callers needing leaf values should use the vectorized engines.
    """

    def __init__(self, spec: SpTTNSpec, path: ContractionPath,
                 order: LoopOrder):
        self.spec = spec
        self.path = path
        self.order = order

    def __call__(self, csf, factors: Mapping) -> np.ndarray:
        if isinstance(csf, CSFArrays):
            if csf.host is None:
                raise ValueError(
                    "reference backend needs the host CSFTensor; build "
                    "CSFArrays via from_csf or pass the CSFTensor directly")
            csf = csf.host
        np_factors = {k: np.asarray(v) for k, v in factors.items()}
        return reference_execute(self.spec, self.path, self.order, csf,
                                 np_factors)


def default_interpret() -> bool:
    """Pallas kernels run in interpret mode everywhere but real TPUs."""
    return jax.default_backend() != "tpu"


# The full extra-kwarg vocabulary of the engines: all three are Pallas
# code-generator options (DESIGN.md §6/§8).  Anything else is a typo and
# is rejected — historically e.g. ``blocks=128`` was silently swallowed
# and the engine ran with its default block size.
ENGINE_KWARGS = ("block", "strategy", "tile_align")


def _check_engine_kwargs(kwargs: Mapping, backend: str, who: str) -> None:
    unknown = sorted(k for k in kwargs if k not in ENGINE_KWARGS)
    if unknown:
        import difflib
        hints = []
        for k in unknown:
            close = difflib.get_close_matches(k, ENGINE_KWARGS, n=1)
            if close:
                hints.append(f"{k!r} -> did you mean {close[0]!r}?")
        hint = ("; " + "; ".join(hints)) if hints else ""
        raise ValueError(
            f"{who}() got unknown argument(s) {unknown}; valid engine "
            f"options are {sorted(ENGINE_KWARGS)} (plus 'interpret' and "
            f"'backend'){hint}")
    if kwargs and backend not in PALLAS_BACKENDS:
        raise ValueError(
            f"{who}() argument(s) {sorted(kwargs)} apply only to the "
            f"Pallas backends {PALLAS_BACKENDS}, got backend={backend!r}")


def make_executor(spec: SpTTNSpec, path: ContractionPath, order: LoopOrder,
                  backend: str = "xla", interpret: bool | None = None,
                  **kwargs):
    """Instantiate an execution engine for a (path, order) schedule.

    All engines share the call signature ``ex(csf_arrays, factors)``.
    ``backend`` is one of :data:`BACKENDS`; ``interpret=None`` resolves via
    :func:`default_interpret` (True off-TPU).  Extra kwargs reach the
    Pallas code generator (:data:`ENGINE_KWARGS`: ``block``, ``strategy``,
    ``tile_align``); unknown kwargs — or Pallas options on a non-Pallas
    backend — raise ``ValueError`` instead of being silently dropped.

    >>> import numpy as np
    >>> from repro.core import spec as S
    >>> from repro.core.planner import plan
    >>> from repro.sparse import build_csf, random_sparse
    >>> spec = S.mttkrp(8, 6, 5, 4)
    >>> csf = build_csf(random_sparse((8, 6, 5), 0.2, seed=0))
    >>> rng = np.random.default_rng(0)
    >>> factors = {"B": rng.standard_normal((6, 4)).astype(np.float32),
    ...            "C": rng.standard_normal((5, 4)).astype(np.float32)}
    >>> p = plan(spec, nnz_levels=csf.nnz_levels())
    >>> ex = make_executor(spec, p.path, p.order, backend="xla")
    >>> out = ex(CSFArrays.from_csf(csf), factors)
    >>> out.shape
    (8, 4)
    >>> make_executor(spec, p.path, p.order, blocks=128)
    Traceback (most recent call last):
        ...
    ValueError: make_executor() got unknown argument(s) ['blocks']; ...
    """
    _check_engine_kwargs(kwargs, backend, "make_executor")
    if backend == "xla":
        return VectorizedExecutor(spec, path, order)
    if backend in PALLAS_BACKENDS:
        from repro.kernels.codegen import PallasPlanExecutor
        return PallasPlanExecutor(spec, path, order, interpret=interpret,
                                  target=PALLAS_TARGETS[backend], **kwargs)
    if backend == "reference":
        return ReferenceExecutor(spec, path, order)
    raise ValueError(f"unknown backend {backend!r}; expected one of "
                     f"{BACKENDS}")


def execute_plan(plan, csf, factors: Mapping, backend: str | None = None,
                 memory_budget: int | None = None, **kwargs):
    """Run an :class:`~repro.core.planner.SpTTNPlan` end to end, honoring
    the plan's tuned backend unless overridden.

    ``memory_budget`` (bytes) prices the plan's working set against the
    operand's actual nnz profile and, when over budget, replays the same
    schedule per chunk of one dense mode
    (:func:`repro.core.slicing.sliced_execute`, DESIGN.md §10).  With no
    explicit budget, a plan stamped ``slice_chunks > 1`` at planning time
    replays sliced as stamped.  Both compose with sharded operands: the
    budget applies within each shard.

    ``csf`` is either a single operand (a :class:`CSFArrays` /
    :class:`~repro.sparse.csf.CSFTensor`) or a *sharded* operand: a
    list/tuple of per-shard CSF tensors that partition the nonzeros of one
    global tensor **in global coordinates** (every shard keeps the full
    declared ``dims``).  For a dense output each shard's partial output is
    exact on the rows its nonzeros touch and zero elsewhere, so the global
    result is the plain sum of the per-shard partials — the host-side
    mirror of the distributed engine's psum (DESIGN.md §7).  ``factors``
    may then be one mapping (replicated operands) or a per-shard sequence.
    Sharded execution of a same-sparsity (TTTP-like) output is rejected:
    leaf values are per-shard local and need the distributed engine's
    layout to reassemble.

    >>> import numpy as np
    >>> from repro.core import spec as S
    >>> from repro.core.planner import plan
    >>> from repro.sparse import build_csf, random_sparse
    >>> spec = S.mttkrp(8, 6, 5, 4)
    >>> csf = build_csf(random_sparse((8, 6, 5), 0.2, seed=0))
    >>> rng = np.random.default_rng(0)
    >>> factors = {"B": rng.standard_normal((6, 4)).astype(np.float32),
    ...            "C": rng.standard_normal((5, 4)).astype(np.float32)}
    >>> p = plan(spec, nnz_levels=csf.nnz_levels())
    >>> out = execute_plan(p, CSFArrays.from_csf(csf), factors)
    >>> out.shape
    (8, 4)
    """
    _check_engine_kwargs({k: v for k, v in kwargs.items()
                          if k != "interpret"},
                         backend or plan.backend, "execute_plan")
    # static pre-flight: every invariant an engine would trip over deep
    # inside a lowering is rejected here, before anything compiles, with
    # a structured SPTTN-E* diagnostic (DESIGN.md §11)
    from repro.analysis import verify_plan
    verify_plan(plan, backend=backend or plan.backend).raise_if_error(
        "execute_plan")
    if isinstance(csf, (list, tuple)):
        if plan.spec.output_is_sparse:
            raise ValueError(
                "sharded operands with a same-sparsity output need the "
                "distributed engine (repro.distributed.spttn_dist); "
                "per-shard leaf values cannot be summed")
        if not csf:
            raise ValueError("empty shard list")
        per_shard = (list(factors) if isinstance(factors, (list, tuple))
                     else [factors] * len(csf))
        if len(per_shard) != len(csf):
            raise ValueError(
                f"{len(csf)} shards but {len(per_shard)} factor mappings")
        total = None
        for shard, f in zip(csf, per_shard):
            part = jnp.asarray(execute_plan(plan, shard, f,
                                            backend=backend,
                                            memory_budget=memory_budget,
                                            **kwargs))
            total = part if total is None else total + part
        return total
    if memory_budget is not None:
        # price against the operand's true profile; slice only if needed
        from repro.core import slicing
        plan = slicing.stamp_plan_slicing(plan, slicing.nnz_levels_of(csf),
                                          memory_budget)
    if getattr(plan, "slice_chunks", 1) > 1:
        from repro.core.slicing import sliced_execute
        return sliced_execute(plan, csf, factors, backend=backend, **kwargs)
    return plan_executor(plan, backend=backend, **kwargs)(csf, factors)


def plan_executor(plan, backend: str | None = None, **kwargs):
    """The engine :func:`execute_plan` runs for ``plan``: its tuned
    backend (unless overridden), and on a Pallas backend its tuned chain
    fusion and block size.  For callers that jit the engine themselves
    (``jax.jit(ex.__call__)`` over :func:`prepare_operand`'s operand)."""
    resolved = backend or plan.backend
    if resolved in PALLAS_BACKENDS and getattr(plan, "fused", False):
        # a fused-winner plan replays through the chain lowering it was
        # tuned with (DESIGN.md §6; one kernel on TPU, split-K + link
        # combines on GPU)
        kwargs.setdefault("strategy", "fused")
    if resolved in PALLAS_BACKENDS and getattr(plan, "block", None):
        # ... and with the exact fiber block size that won (DESIGN.md §8)
        kwargs.setdefault("block", plan.block)
    return make_executor(plan.spec, plan.path, plan.order,
                         backend=resolved, **kwargs)
