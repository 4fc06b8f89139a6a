"""What the jobs share: one SpTTN kernel per mode through the program's
normal path, and the per-mode CSF storage order.

Per mode ``m`` the program's own ``COOTensor.permute_modes`` ->
``build_csf`` -> ``CSFArrays.from_csf`` build the operand (span
``csf_build``), ``plan(autotune=True)`` resolves the schedule through the
checkout's plan cache (span ``plan``), and ``plan_executor`` gives the
engine.  The harness jits the engine itself under a stable name,
``spttn_<kind>_m<m>``, with the operand as an argument (what
``jit_bound`` does), so the trace names each program.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
PLAN_CACHE = ROOT / ".plan_cache"


def others(mode: int, order: int = 3) -> tuple[int, ...]:
    """The modes other than ``mode``, in increasing order: the CSF storage
    order after ``mode``."""
    return tuple(m for m in range(order) if m != mode)


def named_program(name: str, ex):
    """``jax.jit`` of ``(operand, factors) -> ex(operand, factors)`` under
    the function name ``name`` (the trace shows ``jit_<name>``)."""
    import jax

    def program(operand, factors):
        return ex(operand, factors)

    program.__name__ = program.__qualname__ = name
    return jax.jit(program)


@dataclasses.dataclass
class ModeKernel:
    """One mode's compiled-on-first-call kernel and what was resolved."""
    name: str
    kind: str
    mode: int
    call: object              # factors -> output
    backend: str = "reference"
    cache_hit: bool | None = None
    candidates_timed: int = 0


def spttn_kernels(run, kind: str, spec_for, factor_shapes) -> list[ModeKernel]:
    """One kernel per mode through the program's normal path.

    ``spec_for(mode, dims)`` gives the mode's spec for its storage dims;
    ``factor_shapes(mode)`` the dense operands' shapes, by name, for
    ``prepare_operand``.
    """
    import jax
    import jax.numpy as jnp

    from repro import (CSFArrays, build_csf, plan, plan_executor,
                       prepare_operand)
    from repro.sparse.coo import COOTensor

    coo = COOTensor(coords=run.coords, values=run.values,
                    shape=tuple(run.cfg["dims"]))
    out = []
    for mode in range(len(run.cfg["dims"])):
        with run.spans("csf_build"):
            csf = build_csf(coo.permute_modes((mode,) + others(mode)))
            arrays = CSFArrays.from_csf(csf)
            jax.block_until_ready(arrays)
        spec = spec_for(mode, csf.shape)
        with run.spans("plan"):
            p = plan(spec, autotune=True, cache_dir=str(PLAN_CACHE),
                     csf=arrays, nnz_levels=csf.nnz_levels())
        ex = plan_executor(p)
        shapes = {k: jax.ShapeDtypeStruct(s, jnp.float32)
                  for k, s in factor_shapes(mode).items()}
        operand = prepare_operand(ex, arrays, shapes)
        fn = named_program(f"spttn_{kind}_m{mode}", ex)
        out.append(ModeKernel(
            name=f"spttn_{kind}_m{mode}", kind=kind, mode=mode,
            call=lambda f, fn=fn, operand=operand: fn(operand, f),
            backend=p.backend, cache_hit=p.stats.cache_hit,
            candidates_timed=p.stats.candidates_timed))
    return out


def control_kernels(run, kind: str) -> list[ModeKernel]:
    """The control in the program's place (``reference.control_kernels``)."""
    from chipbench import reference
    calls = reference.control_kernels(run.coords, run.values,
                                      run.cfg["dims"], kind)
    return [ModeKernel(name=f"control_{kind}_m{m}", kind=kind, mode=m,
                       call=c) for m, c in enumerate(calls)]
