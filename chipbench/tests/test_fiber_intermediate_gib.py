"""``fiber_intermediate_gib`` reads the program's ``contract.fiber_bytes``
counter."""
from chipbench.metrics import fiber_intermediate_gib


def test_reads_the_counter_in_gib():
    from repro import spans
    spans.reset()
    assert fiber_intermediate_gib.read(None) is None
    spans.count("contract.fiber_bytes", 3 * 2 ** 29)
    assert fiber_intermediate_gib.read(None) == 1.5
    spans.reset()


def test_a_run_that_tuned_reads_none():
    """The tuner's candidate engines add their fiber arrays too."""
    from repro import spans
    spans.reset()
    spans.count("contract.fiber_bytes", 2 ** 30)
    with spans.span("tune.measure"):
        pass
    assert fiber_intermediate_gib.read(None) is None
    spans.reset()


def test_a_rehearsal_engine_counts_its_fiber_arrays():
    """A TTMc's leaf (nnz x R2) and level-2 (nnz^(IJ) x R1 R2) arrays, on
    the CPU, counted once though ``prepare_operand`` and the jit both
    trace."""
    import numpy as np

    import jax

    from repro import spans
    from repro.core import spec as S
    from repro.core.executor import make_executor, prepare_operand
    from repro.core.planner import plan
    from repro.sparse import build_csf, random_sparse

    spec = S.ttmc3(30, 20, 25, 8, 8)
    csf = build_csf(random_sparse((30, 20, 25), 0.05, seed=0))
    p = plan(spec, nnz_levels=csf.nnz_levels())
    ex = make_executor(spec, p.path, p.order, backend="xla")
    rng = np.random.default_rng(0)
    factors = {t.name: rng.standard_normal(
        [spec.dims[i] for i in t.indices]).astype(np.float32)
        for t in spec.inputs if not t.is_sparse}
    spans.reset()
    jax.jit(ex.__call__)(prepare_operand(ex, csf, factors), factors)
    n = csf.nnz_levels()
    assert fiber_intermediate_gib.read(None) == 4 * (
        n[3] * 8 + n[2] * 64) / 2 ** 30
    spans.reset()
