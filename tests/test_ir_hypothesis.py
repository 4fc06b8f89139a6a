"""Hypothesis properties of the target-neutral stage IR
(docs/backends.md).

(1) **IR neutrality + shape preservation** — for random operands and
enumerated loop nests, executors on both registered Pallas targets emit
the *identical* ``StageIR`` sequence and produce outputs of the logical
shape the reference interpreter produces (and the same values, to
float32 tolerance).  The IR is the contract: a lowering may reorder
partial sums but never reshape the logical result.

(2) **split-K combine exactness** — ``segment_combine`` (the Mosaic-GPU
reduce tail) equals a sequential left-to-right accumulation loop
bit-for-bit on float64: ``segment_sum`` over a sorted block->segment map
adds partials in ascending block order, the exact order the TPU
sequential-grid accumulator uses, so the two lowerings are not just
close — they are the same sum.

Skipped wholesale where hypothesis is not installed (the CI full lane
has it; minimal local envs may not).
"""
import numpy as np
import pytest

from repro.core import spec as S
from repro.core.executor import CSFArrays, make_executor, reference_execute
from repro.core.planner import plan
from repro.kernels.codegen import segment_combine
from repro.sparse import build_csf, random_sparse

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

KERNELS = {
    "mttkrp": lambda: S.mttkrp(6, 7, 8, 4),
    "ttmc": lambda: S.ttmc3(6, 7, 8, 4, 3),
    "tttc": lambda: S.tttc6(4, 3),
}


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000),
       kernel=st.sampled_from(sorted(KERNELS)),
       density=st.floats(0.05, 0.4),
       strategy=st.sampled_from(["auto", "fused"]))
def test_lowerings_preserve_ir_and_logical_shapes(seed, kernel, density,
                                                  strategy):
    spec = KERNELS[kernel]()
    shape = tuple(spec.dims[i] for i in spec.sparse_indices)
    csf = build_csf(random_sparse(shape, density, seed=seed))
    if csf.nnz == 0:
        return
    rng = np.random.default_rng(seed)
    factors = {t.name: rng.standard_normal(
        [spec.dims[i] for i in t.indices]).astype(np.float32)
        for t in spec.inputs if not t.is_sparse}
    p = plan(spec, nnz_levels=csf.nnz_levels())
    arrays = CSFArrays.from_csf(csf)
    ref = np.asarray(reference_execute(spec, p.path, p.order, csf, factors))
    outs, irs = {}, {}
    for backend in ("pallas", "pallas-gpu"):
        ex = make_executor(spec, p.path, p.order, backend=backend,
                           block=8, interpret=True, strategy=strategy)
        outs[backend] = np.asarray(ex(arrays, factors))
        irs[backend] = list(ex.emitted_ir)
    assert irs["pallas"], "no stage IR emitted"
    assert irs["pallas"] == irs["pallas-gpu"]
    # float32 rounding grows with the output's magnitude (tttc sums reach
    # ~1e2), so the absolute tolerances scale with it
    scale = max(1.0, float(np.abs(ref).max()))
    for backend, out in outs.items():
        if spec.output_is_sparse:
            assert out.shape[0] == csf.nnz, backend
        else:
            assert out.shape == ref.shape, backend
        np.testing.assert_allclose(out, ref, atol=1e-4 * scale,
                                   err_msg=backend)
    np.testing.assert_allclose(outs["pallas"], outs["pallas-gpu"],
                               atol=1e-5 * scale)


@settings(max_examples=50, deadline=None)
@given(seed=st.integers(0, 10_000),
       nseg=st.integers(1, 9),
       width=st.sampled_from([1, 3, 8]),
       empty_head=st.booleans())
def test_segment_combine_is_bitexact_sequential_accumulation(
        seed, nseg, width, empty_head):
    rng = np.random.default_rng(seed)
    # sorted block->segment map with possibly empty segments (an empty
    # head exercises segments owning zero partials: exact zeros out)
    counts = rng.integers(0, 4, size=nseg)
    if empty_head:
        counts[0] = 0
    seg = np.repeat(np.arange(nseg), counts).astype(np.int32)
    parts = rng.standard_normal((len(seg), width)).astype(np.float64)
    # magnitude spread makes float addition order-observable, so the
    # bit-for-bit assertion below really pins the order
    parts *= 10.0 ** rng.integers(-6, 7, size=(len(seg), 1))
    import jax
    jax.config.update("jax_enable_x64", True)
    try:
        got = np.asarray(segment_combine(parts, seg, nseg))
    finally:
        jax.config.update("jax_enable_x64", False)
    want = np.zeros((nseg, width), np.float64)
    for b in range(len(seg)):             # ascending block order
        want[seg[b]] = want[seg[b]] + parts[b]
    assert got.dtype == np.float64
    np.testing.assert_array_equal(got, want)
