"""The sorted segment sum kernel and its use by the engines' reduce term.

Pallas runs in interpret mode here, so these tests check values, the
merge path and which lowering each engine picks; the compiled kernel is
covered by tests/test_tpu_compile.py.  The kernel is forced on off TPU
by patching the module's platform check, never through an option of the
program.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.core import spec as S
from repro.core.executor import (CSFArrays, layout_bytes, make_executor,
                                 prepare_operand, reference_execute)
from repro.core.planner import plan
from repro.kernels import segment_sum as ss
from repro.sparse import build_csf, random_sparse

#: small blocks, so small maps span many of them
TO, TI = 128, 256


def _rows(seg, nseg, w, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((len(seg), w)).astype(np.float32)


def _kernel_sum(x, seg, nseg, to=TO, ti=TI):
    work = ss.merge_path(seg, nseg, to, ti)
    return np.asarray(ss.sorted_segment_sum(
        jnp.asarray(x), jnp.asarray(seg, jnp.int32), nseg,
        jnp.asarray(work), out_block=to, in_block=ti, interpret=True))


def _maps():
    rng = np.random.default_rng(7)
    gaps = np.sort(rng.choice(np.r_[0:40, 300:340, 900:905], 700))
    return {
        "one-segment-many-blocks": (np.full(3000, 2), 5),
        "all-singletons": (np.arange(1000), 1000),
        "gaps-and-empty-blocks": (gaps, 1000),
        "n-not-a-block-multiple": (np.sort(rng.integers(0, 400, 777)), 400),
        "empty": (np.zeros(0, np.int64), 300),
    }


@pytest.mark.parametrize("w", [1, 16, 64, 300])
@pytest.mark.parametrize("case", sorted(_maps()))
def test_kernel_matches_segment_sum(case, w):
    """1e-6 relative to each sum's magnitude, the sum of its terms'
    absolute values: two float32 sums in different orders differ by that
    much, however much the terms cancel."""
    seg, nseg = _maps()[case]
    x = _rows(seg, nseg, w)
    want = np.asarray(jax.ops.segment_sum(
        jnp.asarray(x), jnp.asarray(seg, jnp.int32), num_segments=nseg,
        indices_are_sorted=True))
    got = _kernel_sum(x, seg, nseg)
    assert got.shape == (nseg, w)
    magnitude = np.zeros((nseg, w))
    np.add.at(magnitude, seg, np.abs(x.astype(np.float64)))
    assert (np.abs(got - want) <= 1e-6 * magnitude).all()
    empty = np.setdiff1d(np.arange(nseg), seg)
    assert not got[empty].any()


def test_kernel_keeps_float32_accuracy():
    """The three-way bfloat16 split loses nothing a float32 sum keeps:
    against a float64 reference the kernel is as close as XLA's float32
    segment sum, and a single bfloat16 pass is not."""
    rng = np.random.default_rng(3)
    seg = np.sort(rng.integers(0, 50, 4000))
    x = (rng.standard_normal((4000, 16)) * 10.0 ** rng.integers(
        -3, 4, (4000, 1))).astype(np.float32)
    exact = np.zeros((50, 16))
    np.add.at(exact, seg, x.astype(np.float64))
    scale = np.abs(exact).max()
    got = _kernel_sum(x, seg, 50)
    f32 = np.asarray(jax.ops.segment_sum(jnp.asarray(x), jnp.asarray(seg),
                                         num_segments=50))
    one_pass = np.zeros((50, 16))
    np.add.at(one_pass, seg, np.asarray(
        jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32)))
    err = np.abs(got - exact).max() / scale
    assert err <= 1e-6
    assert err <= 4 * np.abs(f32 - exact).max() / scale + 1e-7
    assert np.abs(one_pass - exact).max() / scale > 100 * err


@pytest.mark.parametrize("n,nseg", [(0, 10), (1, 1), (5000, 3), (1000, 1000),
                                    (700, 4000)])
def test_merge_path_visits_each_overlap_once_in_order(n, nseg):
    rng = np.random.default_rng(n)
    seg = np.sort(rng.integers(0, nseg, n))
    work = ss.merge_path(seg, nseg, TO, TI).reshape(-1, 2)
    nob, nib = -(-nseg // TO), max(-(-n // TI), 1)
    assert len(work) <= nob + nib
    ob, ib = work[:, 0], work[:, 1]
    assert (np.diff(ob) >= 0).all() and (np.diff(ib) >= 0).all()
    assert set(ob) == set(range(nob))
    assert len({tuple(p) for p in work}) == len(work)
    rows = np.arange(n)
    need = {(s // TO, r // TI) for s, r in zip(seg, rows)}
    assert need <= {tuple(p) for p in work}
    assert ((ib >= 0) & (ib < nib)).all()


# --------------------------------------------------------------------- #
# the engines' reduce term
# --------------------------------------------------------------------- #
@pytest.fixture
def kernel_on(monkeypatch):
    """The kernel path as on TPU, run in interpret mode."""
    monkeypatch.setattr(ss, "on_tpu", lambda: True)


def _problem(spec, density=0.3, seed=0):
    shape = tuple(spec.dims[i] for i in spec.sparse_indices)
    csf = build_csf(random_sparse(shape, density, seed=seed))
    rng = np.random.default_rng(seed)
    factors = {t.name: rng.standard_normal(
        [spec.dims[i] for i in t.indices]).astype(np.float32)
        for t in spec.inputs if not t.is_sparse}
    return csf, factors


ENGINES = {"xla": dict(backend="xla"),
           "pallas-segsum": dict(backend="pallas", strategy="segsum",
                                 block=8)}
KERNELS = {"mttkrp-r16": lambda: S.mttkrp(9, 7, 8, 16),
           "ttmc": lambda: S.ttmc3(9, 7, 8, 4, 3)}


def _counts():
    c = spans.counters()
    return c.get("reduce.kernel", 0), c.get("reduce.scatter", 0)


@pytest.mark.parametrize("engine", sorted(ENGINES))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_engines_with_the_kernel_match_the_reference(kernel, engine,
                                                     kernel_on):
    spec = KERNELS[kernel]()
    csf, factors = _problem(spec)
    p = plan(spec, nnz_levels=csf.nnz_levels())
    ex = make_executor(spec, p.path, p.order, **ENGINES[engine])
    operand = prepare_operand(ex, csf, factors)
    spans.reset()
    out = jax.jit(ex.__call__)(operand, factors)
    kernels, scatters = _counts()
    assert kernels >= 1 and scatters == 0
    ref = reference_execute(spec, p.path, p.order, csf, factors)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_prepare_operand_attaches_the_merge_paths(engine, kernel_on):
    spec = KERNELS["mttkrp-r16"]()
    csf, factors = _problem(spec)
    p = plan(spec, nnz_levels=csf.nnz_levels())
    ex = make_executor(spec, p.path, p.order, **ENGINES[engine])
    operand = prepare_operand(ex, csf, factors)
    cache = operand.__dict__["_codegen_layouts"]
    keys = [k for k in cache if k[0] == "segsum"]
    assert keys
    for key in keys:
        _, lvl, out_lvl, to, ti = key
        nseg, work = cache[key]
        assert (to, ti) == (ss.OUT_BLOCK, ss.IN_BLOCK)
        assert nseg == csf.nfib[out_lvl]
        assert isinstance(work, jax.Array)
        np.testing.assert_array_equal(
            np.asarray(work),
            ss.merge_path(np.asarray(operand.seg[(lvl, out_lvl)]), nseg))
    # the segsum strategy builds no block layouts: the lists are all
    assert layout_bytes(operand) == sum(cache[k][1].nbytes for k in keys)


def test_traced_operand_without_merge_paths_falls_back(kernel_on):
    """A traced operand that was not prepared (as a shard inside
    shard_map) carries no list: XLA's segment sum, counted as such."""
    spec = KERNELS["mttkrp-r16"]()
    csf, factors = _problem(spec)
    p = plan(spec, nnz_levels=csf.nnz_levels())
    ex = make_executor(spec, p.path, p.order, backend="xla")
    arrays = CSFArrays.from_csf(csf)
    spans.reset()
    jaxpr = jax.make_jaxpr(ex.__call__)(arrays, factors)
    kernels, scatters = _counts()
    assert kernels == 0 and scatters >= 1
    assert "pallas_call" not in str(jaxpr)
    assert "_codegen_layouts" not in arrays.__dict__


def test_float64_falls_back(kernel_on):
    spec = KERNELS["mttkrp-r16"]()
    csf, factors = _problem(spec)
    p = plan(spec, nnz_levels=csf.nnz_levels())
    ex = make_executor(spec, p.path, p.order, backend="xla")
    with jax.enable_x64(True):
        f64 = {k: v.astype(np.float64) for k, v in factors.items()}
        arrays = CSFArrays.from_csf(csf)
        arrays.values = jnp.asarray(csf.values, jnp.float64)
        spans.reset()
        out = ex(arrays, f64)
        kernels, scatters = _counts()
        assert out.dtype == jnp.float64
    assert kernels == 0 and scatters >= 1
    ref = reference_execute(spec, p.path, p.order, csf, factors)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_off_tpu_the_engines_keep_segment_sum():
    """Without the patch (this host is no TPU) the XLA engine's program
    is the sorted segment sum it always was, and attaches nothing."""
    spec = KERNELS["mttkrp-r16"]()
    csf, factors = _problem(spec)
    p = plan(spec, nnz_levels=csf.nnz_levels())
    ex = make_executor(spec, p.path, p.order, backend="xla")
    operand = prepare_operand(ex, csf, factors)
    spans.reset()
    text = jax.jit(ex.__call__).lower(operand, factors).as_text()
    kernels, scatters = _counts()
    assert kernels == 0 and scatters >= 1
    assert "scatter" in text and "pallas" not in text
    assert not operand.__dict__.get("_codegen_layouts")
