"""Top-level facade: the blessed public surface of the reproduction.

Everything a user workflow needs — spec building, planning, tuning,
execution (budgeted or not), CSF construction, caching, serving — is
importable from ``repro`` directly::

    from repro import mttkrp, build_csf, random_sparse, plan, execute_plan

The deep module paths (``repro.core.planner`` etc.) keep working and are
where the implementation docs live; the facade is the stable spelling.

Exports resolve lazily (PEP 562): ``import repro`` touches no submodule,
so it never triggers a JAX import/compile — the first *attribute* access
pays the import of exactly the module that defines it.
"""
from __future__ import annotations

import importlib

__version__ = "0.8.0"

# name -> defining module (the single source of truth for __all__)
_EXPORTS = {
    # kernel specs (repro.core.spec)
    "SpTTNSpec": "repro.core.spec",
    "parse": "repro.core.spec",
    "mttkrp": "repro.core.spec",
    "ttmc3": "repro.core.spec",
    "ttmc4": "repro.core.spec",
    "tttp3": "repro.core.spec",
    "sddmm": "repro.core.spec",
    "tttc6": "repro.core.spec",
    # sparse construction (repro.sparse)
    "COOTensor": "repro.sparse",
    "CSFTensor": "repro.sparse",
    "random_sparse": "repro.sparse",
    "from_dense": "repro.sparse",
    "build_csf": "repro.sparse",
    "build_csf_batch": "repro.sparse",
    # planning (repro.core.planner)
    "plan": "repro.core.planner",
    "cached_plan": "repro.core.planner",
    "SpTTNPlan": "repro.core.planner",
    # execution (repro.core.executor)
    "make_executor": "repro.core.executor",
    "execute_plan": "repro.core.executor",
    "plan_executor": "repro.core.executor",
    "prepare_operand": "repro.core.executor",
    "jit_bound": "repro.core.executor",
    "CSFArrays": "repro.core.executor",
    "dense_oracle": "repro.core.executor",
    "plan_to_json": "repro.core.executor",
    "plan_from_json": "repro.core.executor",
    "BACKENDS": "repro.core.executor",
    # memory-budgeted slicing (repro.core.slicing, DESIGN.md §10)
    "plan_peak_bytes": "repro.core.slicing",
    "choose_slicing": "repro.core.slicing",
    "sliced_execute": "repro.core.slicing",
    "SliceDecision": "repro.core.slicing",
    "MemoryBudgetError": "repro.core.slicing",
    # autotuning + persistent plan cache (repro.autotune)
    "tune": "repro.autotune.tuner",
    "TunerConfig": "repro.autotune.tuner",
    "SearchStats": "repro.autotune.tuner",
    "PlanCache": "repro.autotune.cache",
    # static plan verification (repro.analysis, DESIGN.md §11)
    "verify_plan": "repro.analysis",
    "Diagnostic": "repro.analysis",
    "PlanReport": "repro.analysis",
    "PlanVerificationError": "repro.analysis",
    # serving (repro.serve)
    "PlanService": "repro.serve.serve_step",
}

__all__ = sorted(_EXPORTS) + ["__version__"]


def __getattr__(name: str):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute "
                             f"{name!r}")
    value = getattr(importlib.import_module(mod), name)
    globals()[name] = value          # cache: next access skips __getattr__
    return value


def __dir__():
    return sorted(set(globals()) | set(__all__))
