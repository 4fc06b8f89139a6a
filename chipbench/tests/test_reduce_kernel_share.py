"""``reduce_kernel_share`` reads the program's lowering counters."""
from chipbench.metrics import reduce_kernel_share


def test_reads_the_lowering_counters():
    from repro import spans
    spans.reset()
    assert reduce_kernel_share.read(None) is None
    spans.count("reduce.scatter", 1)
    assert reduce_kernel_share.read(None) == 0.0
    spans.count("reduce.kernel", 3)
    assert reduce_kernel_share.read(None) == 75.0
    spans.reset()


def test_a_rehearsal_engine_counts_its_reductions():
    """Off TPU every reduction is XLA's: the share reads 0."""
    import numpy as np

    from repro import spans
    from repro.core import spec as S
    from repro.core.executor import make_executor, prepare_operand
    from repro.core.planner import plan
    from repro.sparse import build_csf, random_sparse

    spec = S.mttkrp(30, 20, 25, 4)
    csf = build_csf(random_sparse((30, 20, 25), 0.05, seed=0))
    p = plan(spec, nnz_levels=csf.nnz_levels())
    ex = make_executor(spec, p.path, p.order, backend="xla")
    rng = np.random.default_rng(0)
    factors = {t.name: rng.standard_normal(
        [spec.dims[i] for i in t.indices]).astype(np.float32)
        for t in spec.inputs if not t.is_sparse}
    spans.reset()
    prepare_operand(ex, csf, factors)
    assert reduce_kernel_share.read(None) == 0.0
    spans.reset()
