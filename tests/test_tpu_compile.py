"""Compile the main path's engines for a TPU v5e that is described, not
attached.

The TPU compiler is installed with JAX, and it compiles for a chip that a
topology description names.  So these tests catch, with no chip, what
interpret mode cannot: block shapes Mosaic refuses, in-kernel ops it
cannot lower, scalar maps that overflow SMEM, programs that do not fit
HBM.  Each Pallas case must compile to a program holding a Mosaic kernel
(``tpu_custom_call``); nothing runs.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library, and the test workers all import
this file.
"""
import re

import numpy as np
import pytest

import jax
from jax.sharding import SingleDeviceSharding

from repro.core import spec as S
from repro.core.executor import make_executor, prepare_operand
from repro.core.planner import plan
from repro.sparse import build_csf, random_sparse

#: kernel -> (spec, sparse dims); widths are the ones users run (R=16 and
#: R=64 MTTKRP, a rank-(16, 16) TTMc), the pattern is small
KERNELS = {
    "mttkrp-r16": lambda: S.mttkrp(300, 200, 250, 16),
    "mttkrp-r64": lambda: S.mttkrp(300, 200, 250, 64),
    "ttmc": lambda: S.ttmc3(300, 200, 250, 16, 16),
}


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described ``v5e:2x2`` host, with JAX's persistent
    compilation cache off: a compile for a described chip is written to
    the cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(kernel: str, one_chip, backend: str, **kw):
    spec = KERNELS[kernel]()
    shape = tuple(spec.dims[i] for i in spec.sparse_indices)
    csf = build_csf(random_sparse(shape, 0.002, seed=0,
                                  distribution="frostt"))
    p = plan(spec, nnz_levels=csf.nnz_levels())
    ex = make_executor(spec, p.path, p.order, backend=backend, **kw)
    rng = np.random.default_rng(0)
    factors = {t.name: rng.standard_normal(
        [spec.dims[i] for i in t.indices]).astype(np.float32)
        for t in spec.inputs if not t.is_sparse}
    operand = prepare_operand(ex, csf, factors)
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=one_chip),
        (operand, factors))
    return ex, jax.jit(ex.__call__).lower(*args).compile()


@pytest.mark.parametrize("strategy", ["row", "segsum", "fused"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_pallas_engine_compiles_for_v5e(kernel, strategy, one_chip):
    ex, compiled = _compile(kernel, one_chip, "pallas", interpret=False,
                            tile_align=True, strategy=strategy)
    assert ex.interpret is False
    assert "tpu_custom_call" in compiled.as_text()
    assert ex.stage_strategy
    if strategy != "fused":
        assert set(ex.stage_strategy.values()) == {strategy}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_xla_engine_compiles_for_v5e(kernel, one_chip):
    _, compiled = _compile(kernel, one_chip, "xla")
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("strategy,kind", [("row", "reduce"),
                                           ("segsum", "product"),
                                           ("fused", "chain")])
def test_pallas_stages_carry_their_name_and_term_scope(strategy, kind,
                                                       one_chip):
    """Each Mosaic kernel is named for its stage kind (``spttn_<kind>``)
    and sits under its term's scope ``t<i>.stage.<kind>``, which the
    chip trace keeps."""
    _, compiled = _compile("mttkrp-r16", one_chip, "pallas",
                           interpret=False, tile_align=True,
                           strategy=strategy)
    kernels = [line for line in compiled.as_text().splitlines()
               if "custom-call(" in line and "tpu_custom_call" in line]
    assert kernels
    for line in kernels:
        assert re.search(rf'op_name="[^"]*/t\d+\.stage\.{kind}/', line)
        assert f"spttn_{kind}" in line
