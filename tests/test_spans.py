"""The program's host spans and counters (``repro.spans``) and where they
sit: the CSF build phases, the upload's bytes, and the tuner's phases in
``SearchStats`` and the plan cache's ``meta``."""
import threading
import time

import jax
import numpy as np
import pytest

from repro import spans
from repro.autotune.cache import PlanCache
from repro.autotune.tuner import SearchStats, TunerConfig, tune
from repro.core import spec as S
from repro.core.executor import (CSFArrays, VectorizedExecutor,
                                 plan_executor, prepare_operand)
from repro.core.planner import plan
from repro.sparse import build_csf, build_csf_batch, random_sparse


@pytest.fixture(autouse=True)
def fresh():
    spans.reset()
    yield
    spans.reset()


def test_nested_spans_each_count_their_whole_time():
    with spans.span("outer") as outer:
        time.sleep(0.01)
        for _ in range(2):
            with spans.span("inner"):
                time.sleep(0.02)
    t = spans.totals()
    assert t["outer"].calls == 1 and t["inner"].calls == 2
    assert t["outer"].seconds == pytest.approx(outer.seconds)
    assert t["inner"].seconds >= 0.04
    assert t["outer"].seconds >= t["inner"].seconds + 0.01


def test_a_span_inside_one_of_its_name_counts_once():
    with spans.span("a") as outer:
        with spans.span("a") as inner:
            time.sleep(0.01)
    assert spans.totals()["a"].calls == 1
    assert spans.totals()["a"].seconds == outer.seconds >= 0.01
    assert inner.seconds == 0.0


def test_a_span_closes_when_its_block_raises():
    with pytest.raises(KeyError):
        with spans.span("fails"):
            raise KeyError("x")
    assert spans.totals()["fails"].calls == 1
    with spans.span("fails"):         # no longer open: counted again
        pass
    assert spans.totals()["fails"].calls == 2


def test_span_decorates_a_function():
    @spans.span("f")
    def f(x):
        """Doubles."""
        return 2 * x

    assert f(2) == 4 and f(3) == 6
    assert f.__name__ == "f" and f.__doc__ == "Doubles."
    assert spans.totals()["f"].calls == 2


def test_counters_and_reset():
    spans.count("n", 2)
    spans.count("n", 3)
    spans.count("m", 0.5)
    assert spans.counters() == {"n": 5, "m": 0.5}
    snapshot = spans.counters()
    snapshot["n"] = 0
    assert spans.counters()["n"] == 5          # a copy
    with spans.span("s"):
        pass
    spans.reset()
    assert spans.counters() == {} and spans.totals() == {}


def test_threads_keep_their_own_open_spans():
    """A span open in one thread does not swallow the same name in
    another: both are counted."""
    start = threading.Barrier(2)

    def work(sleep_s):
        with spans.span("a"):
            start.wait(timeout=10)
            time.sleep(sleep_s)

    threads = [threading.Thread(target=work, args=(s,))
               for s in (0.05, 0.0)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=30)
        assert not th.is_alive()
    t = spans.totals()["a"]
    assert t.calls == 2 and t.seconds >= 0.05


def test_csf_build_records_each_phase_once_and_the_upload_bytes():
    coo = random_sparse((30, 20, 25), 0.05, seed=0)
    spans.reset()
    csf = build_csf(coo.permute_modes((1, 0, 2)))
    arrays = CSFArrays.from_csf(csf)
    t = spans.totals()
    assert {k: v.calls for k, v in t.items()} == {
        "coo.sort": 1, "csf.levels": 1, "csf.upload": 1}
    leaves = jax.tree.leaves(arrays)
    assert len(leaves) == 1 + 6 + 6        # values, fiber coords, seg maps
    assert spans.counters() == {
        "csf.upload_bytes": sum(x.nbytes for x in leaves)}


def test_build_csf_batch_is_one_levels_span():
    coos = [random_sparse((6, 5, 4), 0.2, seed=s) for s in range(3)]
    spans.reset()
    build_csf_batch(coos + [random_sparse((6, 5, 4), 0.0, seed=9)])
    assert spans.totals()["csf.levels"].calls == 1


def test_cold_tune_fills_its_phases(tmp_path):
    spec = S.mttkrp(12, 10, 8, 4)
    csf = build_csf(random_sparse((12, 10, 8), 0.1, seed=1))
    cfg = TunerConfig(max_paths=2, max_candidates=2, orders_per_path=1,
                      repeats=2)
    _, stats = tune(spec, csf=csf, cache_dir=str(tmp_path), tuner=cfg)
    assert not stats.cache_hit and stats.candidates_timed >= 1
    seconds = [f for f in SearchStats.TRACED if f.endswith("_seconds")]
    assert all(getattr(stats, f) > 0 for f in seconds)
    assert (stats.cache_seconds + stats.generate_seconds
            + stats.measure_seconds) <= stats.search_seconds
    assert (stats.prepare_seconds + stats.warmup_seconds
            + stats.time_seconds) <= stats.measure_seconds
    assert stats.layout_bytes_max == 0          # the xla engine has none
    t = spans.totals()
    for f in seconds:
        name = "tune." + f.removesuffix("_seconds")
        assert t[name].seconds == pytest.approx(getattr(stats, f)), name
    meta = PlanCache(str(tmp_path)).meta(stats.cache_key)
    for field in SearchStats.TRACED:
        assert meta[field] == getattr(stats, field), field
    # a warm lookup measures nothing
    _, warm = tune(spec, csf=csf, cache_dir=str(tmp_path), tuner=cfg)
    assert warm.cache_hit and warm.cache_seconds > 0
    assert warm.generate_seconds == warm.prepare_seconds == 0.0


def test_pallas_candidates_record_their_layout_peak():
    spec = S.mttkrp(12, 10, 8, 4)
    csf = build_csf(random_sparse((12, 10, 8), 0.1, seed=1))
    cfg = TunerConfig(max_paths=2, max_candidates=2, orders_per_path=1,
                      repeats=1, backends=("pallas",), blocks=(8,))
    _, stats = tune(spec, csf=csf, tuner=cfg)
    assert stats.layout_bytes_max > 0
    assert np.isfinite(stats.best_seconds)


#: kernel -> (spec, (leaf width, level-2 width)) of the default plan: the
#: leaf term writes ``nnz x R2``, the level-2 term ``nnz^(IJ) x R1 (R2)``
FIBER_KERNELS = {
    "mttkrp-r16": (lambda: S.mttkrp(30, 20, 25, 16), (16, 16)),
    "ttmc-r8": (lambda: S.ttmc3(30, 20, 25, 8, 8), (8, 64)),
}
FIBER_ENGINES = {"xla": dict(backend="xla"),
                 "pallas-segsum": dict(backend="pallas", strategy="segsum",
                                       block=8)}


def _fiber_problem(kernel, engine):
    spec = FIBER_KERNELS[kernel][0]()
    csf = build_csf(random_sparse((30, 20, 25), 0.05, seed=0,
                                  distribution="frostt"))
    p = plan(spec, nnz_levels=csf.nnz_levels())
    ex = plan_executor(p, **FIBER_ENGINES[engine])
    rng = np.random.default_rng(0)
    factors = {t.name: rng.standard_normal(
        [spec.dims[i] for i in t.indices]).astype(np.float32)
        for t in spec.inputs if not t.is_sparse}
    return csf, ex, factors


@pytest.mark.parametrize("engine", sorted(FIBER_ENGINES))
@pytest.mark.parametrize("kernel", sorted(FIBER_KERNELS))
def test_fiber_bytes_are_the_level_count_reckoning(kernel, engine):
    """``contract.fiber_bytes`` holds the float32 bytes of the fiber
    arrays one call writes, from the level counts alone; the abstract
    trace of ``prepare_operand`` and the jit's trace count them once."""
    csf, ex, factors = _fiber_problem(kernel, engine)
    operand = prepare_operand(ex, csf, factors)
    jax.jit(ex.__call__)(operand, factors)
    jax.jit(ex.__call__).lower(operand, factors)
    leaf, level2 = FIBER_KERNELS[kernel][1]
    n = csf.nnz_levels()
    assert spans.counters()["contract.fiber_bytes"] == 4 * (
        n[3] * leaf + n[2] * level2)


@pytest.mark.parametrize("kernel", sorted(FIBER_KERNELS))
def test_counting_fiber_bytes_leaves_the_program_unchanged(kernel,
                                                           monkeypatch):
    """The count is host work at trace time: the program, with its op
    metadata (scopes, source lines), is the same without it."""
    def text():
        csf, ex, factors = _fiber_problem(kernel, "xla")
        operand = prepare_operand(ex, csf, factors)
        return jax.jit(ex.__call__).lower(operand, factors).as_text(
            debug_info=True)

    texts = []
    for count in (True, False):     # one call site: its line is metadata
        if not count:
            monkeypatch.setattr(VectorizedExecutor, "_count_fiber_bytes",
                                lambda *a: None)
        texts.append(text())
        assert bool(spans.counters().get("contract.fiber_bytes")) == count
        spans.reset()
    assert texts[0] == texts[1]
