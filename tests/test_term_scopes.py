"""Named scopes per loop-nest term in the engines' programs.

Every op a term lowers to carries ``t<i>.<kind>`` in its ``op_name``
metadata (``out`` for the output's materialization), so a device trace
attributes time to terms; the scopes change nothing else in the program.
"""
import contextlib
import re

import jax
import numpy as np
import pytest

from repro.core import spec as S
from repro.core.executor import (VectorizedExecutor, make_executor,
                                 prepare_operand)
from repro.core.planner import plan
from repro.sparse import build_csf, random_sparse

KERNELS = {
    "mttkrp": lambda: S.mttkrp(30, 20, 25, 4),
    "ttmc": lambda: S.ttmc3(30, 20, 25, 3, 3),
}
ENGINES = {
    "xla": dict(backend="xla"),
    "pallas-auto": dict(backend="pallas", interpret=True),
    "pallas-row": dict(backend="pallas", interpret=True, strategy="row"),
    "pallas-fused": dict(backend="pallas", interpret=True,
                         strategy="fused"),
}
SCOPED = re.compile(r'op_name="[^"]*(/t\d+\.[a-z.]+/|/out/)')


def lowered(kernel: str, engine: str):
    spec = KERNELS[kernel]()
    shape = tuple(spec.dims[i] for i in spec.sparse_indices)
    csf = build_csf(random_sparse(shape, 0.05, seed=0))
    p = plan(spec, nnz_levels=csf.nnz_levels())
    ex = make_executor(spec, p.path, p.order, **ENGINES[engine])
    rng = np.random.default_rng(0)
    factors = {t.name: rng.standard_normal(
        [spec.dims[i] for i in t.indices]).astype(np.float32)
        for t in spec.inputs if not t.is_sparse}
    operand = prepare_operand(ex, csf, factors)
    return jax.jit(ex.__call__).lower(operand, factors)


@pytest.mark.parametrize("engine", sorted(ENGINES))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_every_reduction_carries_a_term_scope(kernel, engine):
    text = lowered(kernel, engine).as_text(dialect="hlo", debug_info=True)
    ops = [line for line in text.splitlines()
           if re.search(r" (scatter|reduce|custom-call)\(", line)]
    assert ops
    unscoped = [line for line in ops if not SCOPED.search(line)]
    assert not unscoped, unscoped[:3]
    scopes = set(re.findall(r"/(t\d+\.[a-z.]+)/", text))
    kinds = {s.split(".", 1)[1] for s in scopes}
    if engine == "xla":
        assert {"lift", "contract", "reduce"} <= kinds
    else:
        assert any(k.startswith("stage.") for k in kinds), kinds


@pytest.mark.parametrize("engine", ["xla", "pallas-fused"])
def test_scopes_change_only_metadata(engine, monkeypatch):
    with_scopes = lowered("mttkrp", engine).as_text(dialect="hlo")
    monkeypatch.setattr(VectorizedExecutor, "_scope",
                        lambda self, kind: contextlib.nullcontext())
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = lowered("mttkrp", engine).as_text(dialect="hlo")
    assert with_scopes == without


def test_engines_key_the_compile_cache_by_metadata():
    """A persistent cache never hands an engine an executable compiled
    from the same ops under other scopes: building one puts the op
    metadata into the cache key."""
    name = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, name)
    try:
        jax.config.update(name, False)
        lowered("mttkrp", "xla")
        assert getattr(jax.config, name) is True
    finally:
        jax.config.update(name, before)
