"""Production mesh construction (single-pod 16x16, multi-pod 2x16x16).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (dryrun.py must set XLA_FLAGS first)."""
from __future__ import annotations

import jax

from repro.distributed.sharding import model_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return model_mesh(shape, axes)


def make_host_mesh(model: int = 2):
    """Small mesh over whatever devices exist (tests/examples)."""
    n = len(jax.devices())
    model = min(model, n)
    data = n // model
    return model_mesh((data, model), ("data", "model"))
