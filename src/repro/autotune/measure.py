"""Empirical candidate timing (paper §4.1: 'enumeration enables
autotuning').

Each candidate is compiled through its backend's engine (``make_executor``;
XLA or generated Pallas) + jax.jit, warmed up (absorbing compile time),
then timed ``repeats`` times; the score is the median.  Early-exit
pruning: once any candidate has finished, a
later candidate whose *first* timed call already exceeds
``prune_ratio x best_median`` is abandoned — the paper's kernels make the
model ranking good enough that most losers die after one call.

The operand is a jit *argument* of every candidate's program
(:func:`~repro.core.executor.prepare_operand`), never a constant folded
into it.  A candidate whose program does not fit the device's memory is
recorded as infeasible and can never win: one buffer of its traced
program larger than the device holds (a dense fallback that
materializes the whole sparse tensor, a fused chain padding millions of
short fibers to whole blocks) skips the compile, and ``RESOURCE_EXHAUSTED``
from its first call catches the rest.
"""
from __future__ import annotations

import dataclasses
import math
import time
from collections.abc import Mapping, Sequence

import numpy as np

from repro.analysis.diagnostics import PALLAS_BACKENDS
from repro.autotune.candidates import Candidate
from repro.core.spec import SpTTNSpec


@dataclasses.dataclass
class MeasureConfig:
    warmup: int = 1
    repeats: int = 3
    prune_ratio: float = 2.0     # 0/inf disables early-exit pruning


@dataclasses.dataclass
class Measurement:
    candidate: Candidate
    seconds: float               # median over completed repeats
    pruned: bool = False         # abandoned after the first timed call
    infeasible: bool = False     # does not fit the device (never runs)


def synth_inputs(spec: SpTTNSpec, density: float = 0.05, seed: int = 0):
    """Deterministic measurement inputs when the caller has no data yet:
    a random sparse tensor over the spec's sparse dims + random factors.
    Determinism matters — the synthesized nnz-level profile is part of the
    plan-cache key, so a restart must resynthesize the same pattern."""
    from repro.sparse import build_csf, random_sparse
    shape = tuple(spec.dims[i] for i in spec.sparse_indices)
    csf = build_csf(random_sparse(shape, density, seed=seed))
    factors = synth_factors(spec, seed=seed)
    return csf, factors


def synth_factors(spec: SpTTNSpec, seed: int = 0):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    factors = {}
    for t in spec.inputs:
        if t.is_sparse:
            continue
        shape = tuple(spec.dims[i] for i in t.indices)
        factors[t.name] = jnp.asarray(
            rng.standard_normal(shape).astype(np.float32))
    return factors


def device_bytes_limit() -> int | None:
    """Memory of the default device in bytes (``None`` where JAX does not
    report it, as on the CPU)."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")


def largest_buffer_bytes(fn, *args) -> int:
    """Bytes of the largest value ``fn(*args)`` computes, from its traced
    program (nested programs included); nothing compiles or runs."""
    import jax

    def walk(jaxpr) -> int:
        big = 0
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                aval = v.aval
                if hasattr(aval, "shape") and hasattr(aval, "dtype"):
                    big = max(big, math.prod(aval.shape)
                              * aval.dtype.itemsize)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                big = max(big, walk(sub))
        return big

    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


def measure_candidates(spec: SpTTNSpec,
                       candidates: Sequence[Candidate],
                       arrays,
                       factors: Mapping[str, object],
                       config: MeasureConfig | None = None,
                       stats=None) -> list[Measurement]:
    """Time every candidate; returns measurements sorted fastest-first.

    ``arrays`` is a device-resident :class:`CSFArrays`.  ``stats`` (a
    :class:`~repro.autotune.tuner.SearchStats`) is incremented in place so
    callers can assert how much empirical work a search performed.
    """
    import jax

    from repro.core.executor import make_executor, prepare_operand

    config = config or MeasureConfig()
    results: list[Measurement] = []
    best: float | None = None
    limit = device_bytes_limit()

    def infeasible(cand) -> None:
        results.append(Measurement(cand, float("inf"), infeasible=True))
        if stats is not None:
            stats.infeasible += 1

    def run(fn) -> float:
        t0 = time.perf_counter()
        out = fn(factors)
        jax.block_until_ready(out)
        if stats is not None:
            stats.executions += 1
        return time.perf_counter() - t0

    for cand in candidates:
        backend = getattr(cand, "backend", "xla")
        kwargs = {}
        if getattr(cand, "fused", False):
            kwargs["strategy"] = "fused"   # single-kernel chain lowering
        if backend in PALLAS_BACKENDS and getattr(cand, "block", 0):
            kwargs["block"] = cand.block   # swept block axis (DESIGN.md §8)
        ex = make_executor(spec, cand.path, cand.order, backend=backend,
                           **kwargs)
        # a fresh view of the operand per candidate, so the layouts one
        # candidate attaches never ride into (or stay resident for)
        # another's program
        operand = prepare_operand(ex, dataclasses.replace(arrays), factors)
        if limit is not None and largest_buffer_bytes(
                ex.__call__, operand, factors) > limit:
            infeasible(cand)
            continue
        jitted = jax.jit(ex.__call__)
        fn = (lambda f, jitted=jitted, operand=operand:
              jitted(operand, f))
        try:
            for _ in range(config.warmup):
                run(fn)
            first = run(fn)
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            infeasible(cand)
            continue
        if stats is not None:
            stats.candidates_timed += 1
        if (best is not None and config.prune_ratio
                and first > config.prune_ratio * best):
            results.append(Measurement(cand, first, pruned=True))
            if stats is not None:
                stats.pruned += 1
            continue
        times = [first] + [run(fn) for _ in range(config.repeats - 1)]
        med = float(np.median(times))
        results.append(Measurement(cand, med))
        best = med if best is None else min(best, med)

    # pruned entries carry a single first-call sample, not a median —
    # they must never outrank (or tie) a fully measured candidate, so
    # they sort strictly after every completed measurement (infeasible
    # ones, with no sample at all, last)
    results.sort(key=lambda m: (m.infeasible, m.pruned, m.seconds))
    return results
