"""Distributed SpTTN execution — the paper's §5.2 mapped to shard_map.

CTF layout, TPU-native:
  * the sparse tensor is partitioned by tensor modes onto mesh axes and
    NEVER moves (cyclic load balance = host-side row permutation + block
    partition, which is the same layout up to relabeling);
  * each dense factor is sharded along the modes it shares with a
    partitioned sparse mode and *partially replicated* along every other
    mesh axis (the paper's replication scheme);
  * each device runs the SAME fused loop-nest plan on its local CSF (the
    local problem is an SpTTN of identical structure — paper §1);
  * the output is reduced (psum) only over mesh axes that own contracted
    sparse modes, and comes out naturally sharded over output modes.

Local CSFs are padded to common sizes so one jaxpr serves all shards; all
padding is provably zero-contributing (zero values, segment tails held at
the last segment id so every segment map stays sorted).

Three execution modes (DESIGN.md §7, docs/distributed.md):

* :func:`make_distributed` — the XLA collective engine: one plan, one
  shard_map jaxpr, psum over contracted partitioned modes.
* :func:`make_distributed_pallas` — the stacked Pallas engine: the
  generated-kernel executor traced ONCE inside shard_map for every
  shard.  Pallas stages need concrete segment layouts at trace time, so
  each shard's block layout is precomputed on host, padded to the
  mesh-wide maximum with inert blocks, stacked ``(n_shards, ...)``, and
  re-installed per shard inside the traced function — the scalar-
  prefetch operands become traced per-shard slices, the kernel trace is
  shared, and contracted-mode partials still reduce with psum (no host
  round trip).
* :func:`make_distributed_tuned` — distributed *plan replay*: the
  autotuner runs (or cache-hits) per shard on each shard's local nnz
  profile.  Homogeneous XLA winners route through the collective
  engine; homogeneous Pallas winners whose plan passes
  :func:`stackable_plan` route through the stacked Pallas engine
  (mode ``"collective-pallas"``); anything else replays shard-by-shard
  with a host-side sum of partials (exact, since shards keep global
  coordinates and partition the nonzeros).
"""
from __future__ import annotations

import dataclasses
import types
from collections.abc import Mapping

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.analysis.diagnostics import PALLAS_BACKENDS
from repro.analysis.invariants import plan_layout_walk as _plan_layout_walk
from repro.core.executor import CSFArrays, VectorizedExecutor, jit_bound
from repro.core.planner import SpTTNPlan
from repro.core.spec import SpTTNSpec
from repro.sparse.coo import COOTensor
from repro.sparse.csf import build_csf, level_segments


@dataclasses.dataclass
class DistributedSpTTN:
    """Compiled distributed kernel: call with (values_stack, factors)."""

    spec: SpTTNSpec
    plan: SpTTNPlan
    mesh: Mesh
    mode_axis: dict[int, str]           # sparse mode -> mesh axis
    stacked: dict                       # (P, ...) padded CSF arrays
    perm: np.ndarray                    # nnz permutation (global -> stacked)
    fn: object                          # jitted shard_map callable
    factor_perm: dict = dataclasses.field(default_factory=dict)

    def __call__(self, factors: Mapping[str, jnp.ndarray]) -> jnp.ndarray:
        prepared = {}
        for name, arr in factors.items():
            perm = self.factor_perm.get(name)
            if perm is not None:
                axis, take = perm
                arr = jnp.asarray(arr)
                pad = [(0, 0)] * arr.ndim
                pad[axis] = (0, 1)  # zero row for out-of-range slots
                arr = jnp.pad(arr, pad)
                arr = jnp.take(arr, jnp.asarray(take), axis=axis)
            prepared[name] = arr
        return self.fn(self.stacked, prepared)


def _pad_local_csf(csf, max_nnz: int, max_nfib: dict[int, int]):
    """Flattened per-level arrays padded with zero-contribution entries.

    Values pad with zeros and fiber coordinates with 0 (a real local
    coordinate — harmless because the padded values are zero).  Segment
    tails pad with the LAST segment id (``max_nfib[par] - 1``), not 0:
    every CSF segment map is sorted ascending, and both the Pallas block
    layouts (:func:`repro.kernels.util.padded_segment_layout`) and
    ``segment_sum(..., indices_are_sorted=True)`` rely on that — a zero
    tail after a nonzero id would silently break it.  The padded rows
    still contribute nothing (their values are zero), they just
    accumulate into the final row instead of row 0.
    """
    order = csf.order
    out = {"values": np.zeros(max_nnz, csf.values.dtype)}
    out["values"][: csf.nnz] = csf.values
    for p in range(1, order + 1):
        fc = csf.fiber_coords(p)
        for m in range(p):
            a = np.zeros(max_nfib[p], np.int32)
            a[: csf.nfib[p]] = fc[:, m]
            out[f"coord_{p}_{m}"] = a
    for child in range(1, order + 1):
        for par in range(0, child):
            seg = level_segments(csf, child, par)
            padval = (max_nfib[par] - 1) if par > 0 else 0
            a = np.full(max_nfib[child], padval, np.int32)
            a[: len(seg)] = seg
            out[f"seg_{child}_{par}"] = a
    return out


def unpad_local_csf(packed: Mapping[str, np.ndarray], order: int,
                    nnz: int, nfib: Mapping[int, int]) -> dict:
    """Invert :func:`_pad_local_csf`: slice one shard's padded arrays
    back to its real ``nnz`` / per-level ``nfib`` counts.  Padding never
    mixes into real slots (it is strictly appended), so the round trip
    is bit-exact — the property the stacked engines rest on, and what
    the hypothesis suite in tests/test_stacked_dist.py checks."""
    out = {"values": np.asarray(packed["values"])[:nnz]}
    for p in range(1, order + 1):
        for m in range(p):
            out[f"coord_{p}_{m}"] = \
                np.asarray(packed[f"coord_{p}_{m}"])[: nfib[p]]
    for child in range(1, order + 1):
        for par in range(0, child):
            out[f"seg_{child}_{par}"] = \
                np.asarray(packed[f"seg_{child}_{par}"])[: nfib[child]]
    return out


def _unpack_csf(stacked_local: dict, order: int, nfib: dict[int, int],
                shape) -> CSFArrays:
    fiber_coord = {p: {m: stacked_local[f"coord_{p}_{m}"]
                       for m in range(p)} for p in range(1, order + 1)}
    seg = {(c, par): stacked_local[f"seg_{c}_{par}"]
           for c in range(1, order + 1) for par in range(0, c)}
    return CSFArrays(values=stacked_local["values"], fiber_coord=fiber_coord,
                     seg=seg, nfib=nfib, order=order, shape=shape)


@dataclasses.dataclass
class MeshPartition:
    """Host-side result of partitioning a COO over the mesh — everything
    the shard_map engines share: per-shard padded CSF arrays (numpy
    ``packed`` for layout precomputation, jnp ``stacked`` for the traced
    call), factor/output shardings, and the psum axes.  Built by
    :func:`partition_mesh`; consumed by :func:`make_distributed` (XLA
    collective) and :func:`make_distributed_pallas` (stacked Pallas)."""

    order: int
    nshards: int
    csfs: list                          # per-shard local CSFTensors
    packed: list                        # per-shard padded numpy arrays
    stacked: dict                       # (n_shards, ...) jnp arrays
    perm: np.ndarray                    # nnz permutation (global -> stacked)
    local_shape: tuple
    local_spec: SpTTNSpec
    max_nnz: int
    max_nfib: dict
    part_axes: tuple
    factor_specs: dict
    factor_perm: dict
    out_spec: object
    reduce_axes: list


def partition_mesh(spec: SpTTNSpec, coo: COOTensor, mesh: Mesh,
                   mode_axis: dict[int, str],
                   cyclic: bool = True) -> MeshPartition:
    """Partition ``coo`` per ``mode_axis`` into the stacked shard layout.

    Only mode 0 (+ optionally mode 1) partitioning is exercised in tests;
    the construction is generic over any subset of modes.
    """
    sp_inds = spec.sparse_indices
    shape = coo.shape
    coords = coo.coords.copy()
    values = coo.values.copy()

    # cyclic load balance == row permutation + block partition
    nparts = {m: mesh.shape[ax] for m, ax in mode_axis.items()}
    local_dim = {m: -(-shape[m] // nparts[m]) for m in mode_axis}
    owner = np.zeros(len(values), np.int64)
    mult = 1
    part_of = {}
    for m, ax in mode_axis.items():
        if cyclic:
            part = coords[:, m] % nparts[m]
            local = coords[:, m] // nparts[m]
        else:
            part = coords[:, m] // local_dim[m]
            local = coords[:, m] % local_dim[m]
        coords[:, m] = local
        part_of[m] = part
        owner = owner * nparts[m] + part
        mult *= nparts[m]
    nshards = mult

    # bucket nonzeros per shard, build local CSFs, pad to common sizes
    order = coo.order
    buckets = [np.flatnonzero(owner == s) for s in range(nshards)]
    local_shape = tuple(local_dim.get(m, shape[m]) for m in range(order))
    csfs = []
    sorted_ids = []                 # global nnz id per (shard, local slot)
    for idx in buckets:
        key = np.lexsort(coords[idx].T[::-1])
        lc = COOTensor(coords=np.ascontiguousarray(coords[idx][key]),
                       values=np.ascontiguousarray(values[idx][key]),
                       shape=local_shape)
        csfs.append(build_csf(lc))
        sorted_ids.append(idx[key])
    max_nnz = max(max(c.nnz for c in csfs), 1)
    max_nfib = {p: max(max(c.nfib.get(p, 0) for c in csfs), 1)
                for p in range(1, order + 1)}
    packed = [_pad_local_csf(c, max_nnz, max_nfib) for c in csfs]
    stacked = {k: jnp.asarray(np.stack([pk[k] for pk in packed]))
               for k in packed[0]}

    # shardings: stacked CSF arrays over the partition axes (flattened)
    part_axes = tuple(mode_axis[m] for m in mode_axis)
    dims_local = dict(spec.dims)
    for m, ind in enumerate(sp_inds):
        if m in mode_axis:
            dims_local[ind] = local_shape[m]
    import dataclasses as dc
    local_spec = dc.replace(
        spec,
        dims=dims_local,
        output=spec.output)

    # factor shardings: shard along partitioned shared modes, replicate
    # rest (paper §5.2 partial replication).  shard_map splits factor rows
    # BLOCK-wise, so rows are pre-permuted into [part, local] stacked order
    # to match the (cyclic) relabeling of the sparse tensor's coordinates.
    factor_specs = {}
    factor_perm: dict[str, tuple[int, np.ndarray] | None] = {}
    for t in spec.inputs:
        if t.is_sparse:
            continue
        parts = []
        factor_perm[t.name] = None
        for axpos, ind in enumerate(t.indices):
            ax = None
            for m, a in mode_axis.items():
                if sp_inds[m] == ind:
                    ax = a
                    P_m, Ld, I_m = nparts[m], local_dim[m], shape[m]
                    take = np.full(P_m * Ld, I_m, np.int64)  # pad row id
                    for part in range(P_m):
                        for l in range(Ld):
                            g = (l * P_m + part) if cyclic else \
                                (part * Ld + l)
                            if g < I_m:
                                take[part * Ld + l] = g
                    factor_perm[t.name] = (axpos, take)
            parts.append(ax)
        factor_specs[t.name] = P(*parts)

    # output sharding: partitioned output sparse modes stay sharded;
    # contracted partitioned modes need a psum
    out_parts = []
    reduce_axes = []
    for ind in spec.output.indices:
        ax = None
        for m, a in mode_axis.items():
            if sp_inds[m] == ind:
                ax = a
        out_parts.append(ax)
    for m, a in mode_axis.items():
        if sp_inds[m] not in spec.output.indices:
            reduce_axes.append(a)
    out_spec = P(*out_parts) if not spec.output_is_sparse else P(part_axes)

    return MeshPartition(
        order=order, nshards=nshards, csfs=csfs, packed=packed,
        stacked=stacked, perm=np.concatenate(sorted_ids),
        local_shape=local_shape, local_spec=local_spec, max_nnz=max_nnz,
        max_nfib=max_nfib, part_axes=part_axes, factor_specs=factor_specs,
        factor_perm=factor_perm, out_spec=out_spec,
        reduce_axes=reduce_axes)


def _compile_shard_map(mesh: Mesh, part: MeshPartition, local_fn,
                       extra_stacked: dict | None = None):
    """jit(shard_map(local_fn)) over the stacked arrays (+ any stacked
    layout tables), every stacked input sharded over the partition axes."""
    stacked = dict(part.stacked)
    if extra_stacked:
        stacked.update(extra_stacked)
    csf_specs = {k: P(part.part_axes) for k in stacked}
    # each shard's slice lives on its own device once, instead of being
    # scattered from the default device on every call
    stacked = {k: jax.device_put(v, NamedSharding(mesh, csf_specs[k]))
               for k, v in stacked.items()}
    from repro.distributed.collectives import shard_map
    fn = jax.jit(shard_map(
        local_fn, mesh=mesh,
        in_specs=(csf_specs, part.factor_specs),
        out_specs=part.out_spec,
        check_vma=False))
    return stacked, fn


def make_distributed(spec: SpTTNSpec, plan: SpTTNPlan, coo: COOTensor,
                     mesh: Mesh, mode_axis: dict[int, str],
                     cyclic: bool = True) -> DistributedSpTTN:
    """Partition ``coo`` per ``mode_axis`` and build the XLA collective
    shard_map kernel (one :class:`VectorizedExecutor` jaxpr serves every
    shard; see :func:`make_distributed_pallas` for the generated-kernel
    sibling)."""
    part = partition_mesh(spec, coo, mesh, mode_axis, cyclic=cyclic)
    executor = VectorizedExecutor(part.local_spec, plan.path, plan.order)
    nfib_static = dict(part.max_nfib)
    order, local_shape = part.order, part.local_shape
    reduce_axes = part.reduce_axes

    def local_fn(stacked_local, factors):
        # shard_map delivers block-local arrays with a leading shard dim of 1
        local = {k: v.reshape(v.shape[1:]) for k, v in stacked_local.items()}
        arrays = _unpack_csf(local, order, nfib_static, local_shape)
        out = executor(arrays, factors)
        for a in reduce_axes:
            out = jax.lax.psum(out, a)
        return out

    stacked, fn = _compile_shard_map(mesh, part, local_fn)
    dist = DistributedSpTTN(spec=spec, plan=plan, mesh=mesh,
                            mode_axis=dict(mode_axis), stacked=stacked,
                            perm=part.perm, fn=fn,
                            factor_perm=part.factor_perm)
    dist.nnz_per_shard = [c.nnz for c in part.csfs]
    dist.max_nnz = part.max_nnz
    return dist


# =========================================================================== #
# Stacked-layout Pallas engine: one generated-kernel trace for all shards
# =========================================================================== #
# The zero-on-pads induction walk is a static invariant, owned by the
# verifier (repro.analysis.invariants.plan_layout_walk, imported above
# as ``_plan_layout_walk``): the stacked lowering consumes the walk's
# layout *requests*, the verifier its stackability verdict.
def stackable_plan(spec: SpTTNSpec, path, fused: bool = False) -> bool:
    """True when a plan can run through the stacked Pallas engine.

    Structural check, no CSF needed: every sparse-structured stage must
    consume at least one operand that is zero on padded fibers at the
    stage's own CSF level (the sparse leaf values, or an intermediate
    produced by such a stage).  Pad fibers then multiply to zero
    everywhere and the zero-nnz tails of the stacked layout contribute
    nothing on any shard — including entirely empty shard slots.  Dense
    outputs only; :func:`make_distributed_tuned` falls back to replay
    when this returns False.

    Thin wrapper over
    :func:`repro.analysis.invariants.stackable_diagnostics` — the
    verifier's E051/E052 diagnostics ARE this predicate, so engine
    routing and static verification cannot disagree."""
    from repro.analysis.invariants import stackable_diagnostics
    return not stackable_diagnostics(spec, path, fused=fused)


def _stacked_layout_tables(part: MeshPartition, ex, requests):
    """Precompute every shard's Pallas block layouts, pad them to the
    mesh-wide maximum with inert blocks, and stack to ``(n_shards, ...)``
    tables that ride into shard_map next to the CSF arrays.

    Returns ``(extra_stacked, manifest)`` — the jnp tables plus the
    recipe :func:`_install_stacked_layouts` uses to rebuild each shard's
    layout-cache entries from traced local slices."""
    from repro.kernels.codegen.executor import (chain_block_arrays,
                                                chain_layout_key,
                                                stage_layout_key)
    from repro.kernels.util import (pad_segment_layout,
                                    padded_segment_layout)

    shard_views = []
    for pk in part.packed:
        seg = {(c, par): pk[f"seg_{c}_{par}"]
               for c in range(1, part.order + 1) for par in range(0, c)}
        shard_views.append(types.SimpleNamespace(seg=seg,
                                                 nfib=dict(part.max_nfib)))

    extra: dict[str, np.ndarray] = {}
    manifest: list[tuple] = []
    for req in requests:
        if req[0] == "stage":
            _, lvl, out_lvl = req
            nseg = part.max_nfib[out_lvl] if out_lvl > 0 else 1
            lays = [padded_segment_layout(v.seg[(lvl, out_lvl)], nseg,
                                          ex.block) for v in shard_views]
            pmax = max(l.padded_len for l in lays)
            lays = [pad_segment_layout(l, pmax) for l in lays]
            name = f"stage_{lvl}_{out_lvl}"
            extra[f"{name}__gather"] = np.stack([l.gather for l in lays])
            extra[f"{name}__mask"] = np.stack([l.mask for l in lays])
            extra[f"{name}__bseg"] = np.stack([l.block_seg for l in lays])
            manifest.append(("stage", stage_layout_key(lvl, out_lvl,
                                                       ex.block),
                             name, nseg, 0))
        else:
            _, lvl0, levels = req
            per = [chain_block_arrays(v, lvl0, levels, ex.block)
                   for v in shard_views]
            pmax = max(p[0].padded_len for p in per)
            nbmax = pmax // ex.block
            name = "chain_" + "_".join(map(str, (lvl0,) + levels))
            gathers, masks = [], []
            segs_j = [[] for _ in levels]
            for lay, segs in per:
                lay = pad_segment_layout(lay, pmax)
                gathers.append(lay.gather)
                masks.append(lay.mask)
                for j in range(len(levels)):
                    nb = nbmax - segs[j].shape[0]
                    # inert appended blocks: edge segment id (contiguous
                    # revisit of the final row: no buffer reset, and the
                    # final flush moves to the grid's end)
                    segs_j[j].append(np.pad(segs[j], (0, nb), mode="edge"))
            extra[f"{name}__gather"] = np.stack(gathers)
            extra[f"{name}__mask"] = np.stack(masks)
            for j in range(len(levels)):
                extra[f"{name}__seg{j}"] = np.stack(segs_j[j])
            manifest.append(("chain", chain_layout_key(lvl0, levels,
                                                       ex.block),
                             name, part.max_nfib[levels[0]], len(levels)))
    return {k: jnp.asarray(v) for k, v in extra.items()}, manifest


def _install_stacked_layouts(arrays: CSFArrays, local: Mapping,
                             manifest) -> None:
    """Populate the executor's layout cache with this shard's traced
    slices so the Pallas lowering never touches numpy at trace time."""
    from repro.kernels.codegen.executor import (chain_cache_entry,
                                                layout_cache,
                                                stage_cache_entry)

    cache = layout_cache(arrays)
    for kind, key, name, nseg, nlvl in manifest:
        if kind == "stage":
            cache[key] = stage_cache_entry(
                nseg, local[f"{name}__gather"], local[f"{name}__mask"],
                local[f"{name}__bseg"])
        else:
            cache[key] = chain_cache_entry(
                nseg, local[f"{name}__gather"], local[f"{name}__mask"],
                tuple(local[f"{name}__seg{j}"] for j in range(nlvl)))


def make_distributed_pallas(spec: SpTTNSpec, plan: SpTTNPlan,
                            coo: COOTensor, mesh: Mesh,
                            mode_axis: dict[int, str], cyclic: bool = True,
                            **executor_kwargs) -> DistributedSpTTN:
    """The stacked Pallas engine: ONE generated-kernel trace inside
    shard_map serves every shard, contracted-mode partials reduce with
    psum — no host round trip, no per-shard retrace.

    Pallas stages need concrete block-segment layouts at trace time,
    which per-shard tracers cannot provide; instead each shard's layout
    is precomputed on host from its padded CSF, padded to the mesh-wide
    maximum with inert blocks, stacked, and passed through shard_map as
    extra sharded inputs.  Inside the traced function the local slices
    are re-installed into the executor's layout cache, turning the
    scalar-prefetch operands into traced per-shard values under one
    shared kernel trace.

    ``plan`` must be homogeneous across shards (one schedule for all)
    and pass :func:`stackable_plan`; extra kwargs reach
    :class:`~repro.kernels.codegen.PallasPlanExecutor` (``block``,
    ``strategy``, ``tile_align``, ``interpret``) — ``plan.fused`` and
    ``plan.block`` are applied automatically like plan replay does.
    """
    if spec.output_is_sparse:
        raise ValueError(
            "make_distributed_pallas requires a dense output; same-"
            "sparsity (TTTP-like) outputs go through make_distributed")
    from repro.kernels.codegen import PallasPlanExecutor

    part = partition_mesh(spec, coo, mesh, mode_axis, cyclic=cyclic)
    kw = dict(executor_kwargs)
    if plan.fused:
        kw.setdefault("strategy", "fused")
    if getattr(plan, "block", None):
        kw.setdefault("block", plan.block)
    ex = PallasPlanExecutor(part.local_spec, plan.path, plan.order, **kw)

    nfib_stub = types.SimpleNamespace(nfib=dict(part.max_nfib))
    ok, requests = _plan_layout_walk(
        spec, plan.path, ex._chains,
        lambda lvl, out_lvl: ex.strategy_for(nfib_stub, lvl,
                                             out_lvl) == "row")
    if not ok:
        raise ValueError(
            "plan is not stackable: some sparse-structured stage has no "
            "operand that is zero on padded fibers at its own CSF level, "
            "so the stacked zero-nnz tails would pollute the result — "
            "check stackable_plan() first and fall back to replay "
            "[SPTTN-E051]")
    extra, manifest = _stacked_layout_tables(part, ex, requests)

    nfib_static = dict(part.max_nfib)
    order, local_shape = part.order, part.local_shape
    reduce_axes = part.reduce_axes

    def local_fn(stacked_local, factors):
        local = {k: v.reshape(v.shape[1:]) for k, v in stacked_local.items()}
        arrays = _unpack_csf(local, order, nfib_static, local_shape)
        _install_stacked_layouts(arrays, local, manifest)
        out = ex(arrays, factors)
        for a in reduce_axes:
            out = jax.lax.psum(out, a)
        return out

    stacked, fn = _compile_shard_map(mesh, part, local_fn, extra)
    dist = DistributedSpTTN(spec=spec, plan=plan, mesh=mesh,
                            mode_axis=dict(mode_axis), stacked=stacked,
                            perm=part.perm, fn=fn,
                            factor_perm=part.factor_perm)
    dist.nnz_per_shard = [c.nnz for c in part.csfs]
    dist.max_nnz = part.max_nnz
    dist.executor = ex           # inspection: emitted stages / strategies
    dist.layout_manifest = manifest
    return dist


# =========================================================================== #
# Distributed plan replay (DESIGN.md §7): per-shard tuned backends
# =========================================================================== #
def shard_mesh_key(mesh, mode_axis: Mapping[int, str],
                   shard: int) -> dict:
    """JSON-able shard context for the plan cache key (DESIGN.md §7).

    Names everything that distinguishes one shard-local tuning problem
    from the single-device one and from other mesh layouts: the sizes of
    the partitioned mesh axes, the mode→axis assignment, and the shard
    index.  Feed it to ``TunerConfig.mesh`` /
    :func:`repro.autotune.cache_key`; it is also stamped onto the tuned
    plan and persisted in plan JSON v3.

    ``mesh`` is a :class:`jax.sharding.Mesh` or a plain ``{axis: size}``
    mapping (handy for key computations without building devices).

    >>> shard_mesh_key({"data": 4}, {0: "data"}, shard=2)
    {'mesh_shape': {'data': 4}, 'mode_axis': {'0': 'data'}, 'shard': 2}
    """
    shape = mesh.shape if hasattr(mesh, "shape") else mesh
    return {
        "mesh_shape": {ax: int(shape[ax])
                       for ax in sorted(set(mode_axis.values()))},
        "mode_axis": {str(m): ax for m, ax in sorted(mode_axis.items())},
        "shard": int(shard),
    }


def partition_nonzeros(coo: COOTensor, nparts: Mapping[int, int],
                       cyclic: bool = True) -> list[COOTensor]:
    """Partition ``coo``'s nonzeros by (cyclic) ownership over the
    partitioned modes, **keeping global coordinates** — each shard is a
    same-shape COO holding a disjoint nonzero subset, so per-shard dense
    partial outputs sum exactly to the global output (the replay-mode
    reduction; contrast :func:`make_distributed`, which relabels
    coordinates for the equal-block shard_map layout).

    ``nparts`` maps mode → number of parts; ownership composes over modes
    in sorted order (mixed radix, same shard enumeration as
    :func:`make_distributed`'s owner computation for one-mode grids).
    """
    owner = np.zeros(coo.nnz, np.int64)
    nshards = 1
    for m in sorted(nparts):
        P_m = int(nparts[m])
        if cyclic:
            part = coo.coords[:, m] % P_m
        else:
            local_dim = -(-coo.shape[m] // P_m)
            part = coo.coords[:, m] // local_dim
        owner = owner * P_m + part
        nshards *= P_m
    out = []
    for s in range(nshards):
        idx = np.flatnonzero(owner == s)
        # a subset of lexicographically sorted rows stays sorted
        out.append(COOTensor(coords=np.ascontiguousarray(coo.coords[idx]),
                             values=np.ascontiguousarray(coo.values[idx]),
                             shape=coo.shape))
    return out


@dataclasses.dataclass
class TunedShard:
    """One shard of a :class:`DistributedPlanReplay`: the shard-locally
    tuned plan, the search stats (cache hit/miss accounting), and the
    compiled executor closure.  Only the operand representation the
    shard's backend executes is retained — ``csf`` (host CSFTensor,
    global coordinates) for ``reference`` replay, the operand bound into
    ``fn`` on ``device`` for ``xla``/``pallas`` replay, neither in
    collective mode (the shard_map engine builds its own stacked
    layout)."""

    index: int
    nnz: int
    plan: SpTTNPlan | None       # None for an empty shard
    stats: object | None         # autotune SearchStats
    csf: object | None = None
    fn: object | None = None     # factors -> partial output
    device: object | None = None  # where a replayed shard runs


#: the three distributed execution modes a tuned replay can land on
DIST_MODES = ("collective", "collective-pallas", "replay")


@dataclasses.dataclass
class DistributedPlanReplay:
    """Distributed SpTTN execution with per-shard tuned plans.

    ``mode`` is one of :data:`DIST_MODES`: ``"collective"`` when every
    shard's winner agreed on one XLA schedule — execution then goes
    through the shard_map engine (:func:`make_distributed`), psum
    included; ``"collective-pallas"`` when they agreed on one *Pallas*
    schedule whose plan passes :func:`stackable_plan` (the fused axis
    is harmonized to the majority winner — a lowering detail timing
    noise may split across shards, never a routing forfeit) — one
    generated-kernel trace inside shard_map
    (:func:`make_distributed_pallas`), psum included; otherwise ``"replay"``: each shard executes its own
    tuned plan via its compiled backend (``reference``/``xla``/
    ``pallas``) and the dense partials are summed host-side (exact,
    because shards keep global coordinates).  Calling the object always
    returns the **global** dense output, so results are directly
    comparable against ``reference_execute``/``dense_oracle``.
    """

    spec: SpTTNSpec
    mesh: Mesh
    mode_axis: dict[int, str]
    shape: tuple[int, ...]       # global sparse-tensor shape
    shards: list[TunedShard]
    mode: str
    cyclic: bool = True
    collective: DistributedSpTTN | None = None
    # pattern-static undo-relabeling gathers, built lazily once
    _undo: list | None = dataclasses.field(default=None, repr=False,
                                           compare=False)

    @property
    def plans(self) -> list[SpTTNPlan | None]:
        return [sh.plan for sh in self.shards]

    @property
    def backends(self) -> list[str | None]:
        return [None if sh.plan is None else sh.plan.backend
                for sh in self.shards]

    @property
    def nnz_per_shard(self) -> list[int]:
        return [sh.nnz for sh in self.shards]

    def __call__(self, factors: Mapping) -> np.ndarray:
        if self.mode in ("collective", "collective-pallas"):
            out = np.asarray(self.collective(factors))
            if self._undo is None:
                self._undo = undo_cyclic_plan(self.spec, self.mode_axis,
                                              self.mesh, self.shape,
                                              cyclic=self.cyclic)
            for axis, take in self._undo:
                out = np.take(out, take, axis=axis)
            return out
        # dispatch every shard before reading any back: each runs on its
        # own device, so the programs overlap
        parts = [sh.fn(factors) for sh in self.shards if sh.fn is not None]
        total = None
        for part in parts:
            part = np.asarray(part)
            total = part if total is None else total + part
        if total is None:       # all shards empty: zero output
            dims = self.spec.dims
            total = np.zeros([dims[i] for i in self.spec.output.indices],
                             np.float32)
        return total


def _annotate_dist_mode(cache_dir, shards, mode: str) -> None:
    """Record the distributed mode the tuned plans were routed through
    into each live shard's plan-cache entry meta — the tuner's timings
    then tell the whole story (which backend won AND how it executed on
    the mesh) without re-deriving the routing."""
    if cache_dir is None:
        return
    from repro.autotune.cache import PlanCache
    cache = PlanCache(cache_dir)
    for sh in shards:
        key = getattr(sh.stats, "cache_key", "") if sh.stats else ""
        if key:
            cache.annotate(key, dist_mode=mode)


def make_distributed_tuned(spec: SpTTNSpec, coo: COOTensor, mesh: Mesh,
                           mode_axis: Mapping[int, str],
                           cache_dir: str | None = None,
                           tuner=None, cyclic: bool = True,
                           prefer_collective: bool = True,
                           **executor_kwargs) -> DistributedPlanReplay:
    """Partition ``coo`` over the mesh and replay a tuned plan per shard.

    The end-to-end pipeline of DESIGN.md §7: partition the nonzeros over
    the partitioned mesh axes → per shard, run (or cache-hit) the
    autotuner on the *shard's local nnz profile* under a mesh-extended
    cache key (:func:`shard_mesh_key` via ``TunerConfig.mesh``) → execute
    every shard through its winner's backend → reduce the partial
    outputs.  When all shards agree on one schedule (the common case for
    well-balanced partitions) and ``prefer_collective`` is set, the
    reduction is a shard_map psum: XLA winners go through
    :func:`make_distributed`, Pallas winners whose plan passes
    :func:`stackable_plan` through :func:`make_distributed_pallas` (one
    kernel trace for all shards); heterogeneous or non-stackable winners
    replay shard-by-shard with a host-side sum.  The chosen mode is
    recorded into each live shard's plan-cache entry meta
    (``dist_mode``) when ``cache_dir`` is given.

    ``tuner`` is a :class:`repro.autotune.TunerConfig` template (its
    ``mesh`` field is overwritten per shard); extra kwargs reach the
    Pallas code generator for pallas-backend shards (``block``,
    ``strategy``).  Same-sparsity (TTTP-like) outputs need the collective
    layout to reassemble leaf values and are rejected here — use
    :func:`make_distributed`.
    """
    if spec.output_is_sparse:
        raise ValueError(
            "make_distributed_tuned requires a dense output; same-sparsity "
            "outputs (TTTP-like) reassemble leaf values through "
            "make_distributed's stacked layout instead")
    from repro.autotune import TunerConfig, tune
    from repro.core.executor import make_executor

    base = tuner if tuner is not None else TunerConfig()
    nparts = {m: int(mesh.shape[ax]) for m, ax in mode_axis.items()}
    shards: list[TunedShard] = []
    for s, local in enumerate(partition_nonzeros(coo, nparts,
                                                 cyclic=cyclic)):
        if local.nnz == 0:
            shards.append(TunedShard(s, 0, None, None))
            continue
        csf_s = build_csf(local)
        cfg = dataclasses.replace(
            base, mesh=shard_mesh_key(mesh, mode_axis, s))
        plan_s, stats_s = tune(spec, csf=csf_s, cache_dir=cache_dir,
                               tuner=cfg)
        shards.append(TunedShard(s, csf_s.nnz, plan_s, stats_s, csf=csf_s))

    live = [sh for sh in shards if sh.plan is not None]
    dist = DistributedPlanReplay(spec=spec, mesh=mesh,
                                 mode_axis=dict(mode_axis), shape=coo.shape,
                                 shards=shards, mode="replay", cyclic=cyclic)
    if not live:
        return dist              # degenerate: empty tensor, zero output

    # static pre-flight on every live shard's winner: a corrupt cache
    # entry (doctored mesh context, illegal axes) fails here with a
    # structured diagnostic instead of deep inside a shard's lowering
    from repro.analysis import verify_plan
    for sh in live:
        verify_plan(sh.plan).raise_if_error(
            f"make_distributed_tuned[shard {sh.index}]")

    first = live[0].plan
    # homogeneity on the schedule (path/order/backend).  The fused axis
    # is deliberately NOT part of it: fused-vs-staged is a lowering
    # detail of the same plan whose per-shard winner is decided by
    # measured timings, so on near-tied candidates shards split on it
    # by noise — forfeiting collective routing over that would make the
    # routed mode nondeterministic run to run.  fusibility depends only
    # on (spec, path), identical across shards, so harmonizing to the
    # majority winner is always legal; everything else heterogeneous
    # still falls back to replay.
    homogeneous = all(
        (sh.plan.path, sh.plan.order, sh.plan.backend)
        == (first.path, first.order, first.backend)
        for sh in live)
    fused_votes = sum(1 for sh in live if sh.plan.fused)
    fused = homogeneous and fused_votes * 2 > len(live)
    if first.fused != fused:
        first = dataclasses.replace(first, fused=fused)
    if prefer_collective and homogeneous and first.backend == "xla":
        dist.mode = "collective"
        dist.collective = make_distributed(spec, first, coo, mesh,
                                           dict(mode_axis), cyclic=cyclic)
        for sh in live:          # shard_map holds its own stacked layout
            sh.csf = None
        _annotate_dist_mode(cache_dir, live, dist.mode)
        return dist
    if (prefer_collective and homogeneous and first.backend == "pallas"
            and stackable_plan(spec, first.path, fused=first.fused)):
        # homogeneous TPU-Pallas winners: one kernel trace for all
        # shards, replaying the tuned fused/block axes from the cache
        # entries.  Deliberately "pallas" only, not PALLAS_BACKENDS: the
        # stacked engine's one-trace-many-shards trick rides the TPU
        # lowering's scalar-prefetched layouts; pallas-gpu winners take
        # the per-shard replay below (split-K needs no stacking to be
        # grid-parallel)
        dist.mode = "collective-pallas"
        dist.collective = make_distributed_pallas(
            spec, first, coo, mesh, dict(mode_axis), cyclic=cyclic,
            **executor_kwargs)
        for sh in live:          # shard_map holds its own stacked layout
            sh.csf = None
        _annotate_dist_mode(cache_dir, live, dist.mode)
        return dist

    _annotate_dist_mode(cache_dir, live, "replay")
    devices = list(mesh.devices.flat)
    for sh in live:
        pallas_kind = sh.plan.backend in PALLAS_BACKENDS
        kw = dict(executor_kwargs) if pallas_kind else {}
        if pallas_kind and sh.plan.fused:
            # the shard's winner used the fused chain lowering
            # (DESIGN.md §6); replay through the same strategy
            kw.setdefault("strategy", "fused")
        if pallas_kind and getattr(sh.plan, "block", None):
            # ... and with the shard's tuned fiber block size (DESIGN.md
            # §8) — shards may win at different blocks on skewed
            # partitions, so the knob is per shard, not per mesh
            kw.setdefault("block", sh.plan.block)
        ex = make_executor(spec, sh.plan.path, sh.plan.order,
                           backend=sh.plan.backend, **kw)
        if sh.plan.backend == "reference":
            sh.fn = (lambda f, ex=ex, csf=sh.csf: ex(csf, f))
        else:
            # each shard's operand and program on its own mesh device
            sh.device = devices[sh.index % len(devices)]
            sh.fn = jit_bound(ex, sh.csf, device=sh.device)
            sh.csf = None
    return dist


def gather_sparse_values(dist: DistributedSpTTN, out_stacked) -> np.ndarray:
    """Reassemble a same-sparsity (TTTP-like) output into the original COO
    nonzero order from the stacked per-shard value layout."""
    vals = np.asarray(out_stacked).reshape(len(dist.nnz_per_shard),
                                           dist.max_nnz)
    total = int(sum(dist.nnz_per_shard))
    out = np.zeros(total, vals.dtype)
    for s, n in enumerate(dist.nnz_per_shard):
        ids = dist.perm[sum(dist.nnz_per_shard[:s]):
                        sum(dist.nnz_per_shard[:s]) + n]
        out[ids] = vals[s, :n]
    return out


def undo_cyclic_plan(spec: SpTTNSpec, mode_axis, mesh, shape,
                     cyclic: bool = True) -> list[tuple[int, np.ndarray]]:
    """Pattern-static (axis, take) gathers inverting the cyclic row
    relabeling on partitioned output modes — compute once, apply per
    call (the stacked layout is [part, local]; global = local*nparts +
    part)."""
    sp_inds = spec.sparse_indices
    plan = []
    for m, ax in mode_axis.items():
        ind = sp_inds[m]
        if ind not in spec.output.indices:
            continue
        axis = spec.output.indices.index(ind)
        nparts = mesh.shape[ax]
        I = shape[m]
        local = -(-I // nparts)
        if not cyclic:
            plan.append((axis, np.arange(I)))
            continue
        take = np.zeros(I, np.int64)
        for p in range(nparts):
            for l in range(local):
                g = l * nparts + p
                if g < I:
                    take[g] = p * local + l
        plan.append((axis, take))
    return plan


def undo_cyclic(out: np.ndarray, spec: SpTTNSpec, mode_axis, mesh,
                shape, cyclic: bool = True) -> np.ndarray:
    """Invert the cyclic row relabeling on output modes for comparison."""
    res = out
    for axis, take in undo_cyclic_plan(spec, mode_axis, mesh, shape,
                                       cyclic=cyclic):
        res = np.take(res, take, axis=axis)
    return res
