"""Logical-axis sharding rules (MaxText-style) and activation constraints.

Parameters carry *logical* axis names (see models/layers.py); a rules table
maps them to mesh axes per mesh layout.  Defaults implement:

  FSDP   — weights sharded over the data axes on their 'embed'/'ffn' dim
  TP     — heads / ffn-hidden / experts / vocab sharded over 'model'
  DP     — batch over ('pod','data'); long-context decode shards the KV/seq
           axis over 'data' instead (flash-decode partial-softmax psum)

``set_mesh_context`` installs a mesh + rules for the duration of a lowering;
``shard_activation`` is a no-op outside a mesh context so models stay pure.
"""
from __future__ import annotations

import contextlib
import threading
from collections.abc import Mapping, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

_ctx = threading.local()


def model_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """A mesh for the models: every axis ``Auto``.  The models place
    activations with ``with_sharding_constraint`` and let GSPMD propagate
    the rest, which JAX allows only on Auto axes (``jax.make_mesh``
    makes Explicit axes unless told otherwise)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


# --------------------------------------------------------------------------- #
# rules
# --------------------------------------------------------------------------- #
def default_rules(multi_pod: bool, shape_kind: str = "train",
                  seq_shard: bool = False,
                  preset: str = "2d") -> dict[str, object]:
    """Sharding presets.

    '2d' (default)    — DP/FSDP over data axes, TP/EP over 'model'.
    'seq_parallel'    — sequence sharded over 'model', weights replicated
                        across it (vocab stays model-sharded).  The right
                        scheme for models too narrow for 16-way TP (heads
                        or ffn not divisible): attention/MLP compute
                        partitions over tokens instead of being replicated,
                        and the per-layer partial-sum all-reduces disappear
                        (see EXPERIMENTS.md §Perf).
    """
    data_axes = ("pod", "data") if multi_pod else ("data",)
    seqp = preset == "seq_parallel"
    tp = None if seqp else "model"
    rules: dict[str, object] = {
        # parameter logical axes
        "vocab": "model",
        "embed": data_axes,          # FSDP shard on the embed dim
        "ffn": tp,
        "q_heads": tp,
        "kv_heads": tp,
        "experts": "model",          # EP stays even under seq_parallel
        "lora": None,
        "heads": tp,
        "head_dim": None,
        "conv": None,
        "layers": None,
        # activation logical axes
        "act_batch": data_axes,
        "act_seq": "model" if seqp else ("data" if seq_shard else None),
        "act_embed": None,
    }
    return rules


def spec_for(logical: Sequence[str] | None,
             rules: Mapping[str, object]) -> P:
    if logical is None:
        return P()
    return P(*[rules.get(ax, None) for ax in logical])


def tree_sharding(params_or_shapes, spec_tree, rules, mesh: Mesh):
    """NamedSharding tree for a params tree (arrays or ShapeDtypeStructs)."""
    is_spec = lambda s: isinstance(s, tuple) and all(
        isinstance(x, (str, type(None))) for x in s)

    def one(spec, arr):
        parts = []
        used: set[str] = set()
        for dim, ax in zip(arr.shape, spec):
            m = rules.get(ax, None)
            if m is None:
                parts.append(None)
                continue
            axes = (m,) if isinstance(m, str) else tuple(m)
            # a mesh axis may appear at most once per spec: earlier
            # (higher-priority) logical dims win, e.g. experts>ffn for EP
            if used & set(axes):
                parts.append(None)
                continue
            extent = 1
            for a in axes:
                extent *= mesh.shape[a]
            if extent > 0 and dim % extent == 0 and dim >= extent:
                parts.append(m)
                used |= set(axes)
            else:
                parts.append(None)
        return NamedSharding(mesh, P(*parts))

    return jax.tree.map(one, spec_tree, params_or_shapes, is_leaf=is_spec)


# --------------------------------------------------------------------------- #
# activation constraint context
# --------------------------------------------------------------------------- #
@contextlib.contextmanager
def mesh_context(mesh: Mesh, rules: Mapping[str, object]):
    _ctx.mesh = mesh
    _ctx.rules = rules
    try:
        yield
    finally:
        _ctx.mesh = None
        _ctx.rules = None


_ACT_SPECS = {
    # (batch, seq, embed)
    "btd": ("act_batch", "act_seq", "act_embed"),
    # (batch, seq, heads, head_dim)
    "bthd": ("act_batch", "act_seq", "heads", None),
    # MoE expert buffers: (experts, capacity, embed).  Explicit pinning was
    # tried and REFUTED twice (EXPERIMENTS.md §Perf granite iterations 1-2:
    # experts->model regressed 2.4x, capacity->data regressed collectives
    # 20x) — GSPMD's inferred placement wins; leave unconstrained.
    "ecd": (None, None, None),
}


def replicate(x):
    """Constrain to fully-replicated (no-op outside a mesh context).
    Used to force a cheap table all-gather before an embedding lookup —
    GSPMD otherwise lowers the gather from a vocab-sharded table as a
    one-hot matmul (~10x the model's FLOPs at 1M tokens; §Perf)."""
    mesh = getattr(_ctx, "mesh", None)
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*([None] * x.ndim))))


def shard_activation(x, kind: str):
    mesh = getattr(_ctx, "mesh", None)
    rules = getattr(_ctx, "rules", None)
    if mesh is None or rules is None:
        return x
    logical = _ACT_SPECS.get(kind)
    if logical is None or len(logical) != x.ndim:
        return x
    parts = []
    for dim, ax in zip(x.shape, logical):
        m = rules.get(ax, None) if ax else None
        if m is None:
            parts.append(None)
            continue
        axes = (m,) if isinstance(m, str) else tuple(m)
        extent = 1
        for a in axes:
            extent *= mesh.shape[a]
        parts.append(m if dim % extent == 0 and dim >= extent else None)
    if all(p is None for p in parts):
        # a fully-None spec is NOT a no-op: it would FORCE replication
        # (measured 17x per-layer FLOP blowup on the MoE buffers — §Perf)
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*parts)))
