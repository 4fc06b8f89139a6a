"""Autotuning runtime + persistent plan cache (DESIGN.md §4).

Covers: (a) the tuned plan's measured runtime never exceeds the model
pick's (the model pick is always in the measured candidate set); (b) plan
serialization round-trips to an identical SpTTNPlan with identical executor
output; (c) the cache key is a pure function of (spec, nnz-level profile,
device) — values never enter, pattern changes do.
"""
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from repro.autotune import (PlanCache, TunerConfig, cache_key,
                            device_kind, generate_candidates, spec_signature,
                            tune)
from repro.core import spec as S
from repro.core.executor import (CSFArrays, VectorizedExecutor, dense_oracle,
                                 plan_from_json, plan_to_json)
from repro.core.planner import plan
from repro.sparse import build_csf, random_sparse
from repro.sparse.coo import from_coords

FAST = TunerConfig(max_paths=4, max_candidates=4, orders_per_path=2,
                   warmup=1, repeats=2)


def _mttkrp_inputs(I=32, J=24, K=16, R=8, density=0.08, seed=3):
    spec = S.mttkrp(I, J, K, R)
    csf = build_csf(random_sparse((I, J, K), density, seed=seed))
    rng = np.random.default_rng(0)
    factors = {"B": jnp.asarray(rng.standard_normal((J, R))
                                .astype(np.float32)),
               "C": jnp.asarray(rng.standard_normal((K, R))
                                .astype(np.float32))}
    return spec, csf, factors


# --------------------------------------------------------------------- #
# (a) tuned <= model-picked, measured — across several small MTTKRPs
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("dims,density,seed", [
    ((32, 24, 16, 8), 0.08, 3),
    ((48, 16, 16, 4), 0.15, 11),
    ((16, 32, 24, 16), 0.05, 7),
])
def test_tuned_runtime_never_worse_than_model(dims, density, seed):
    I, J, K, R = dims
    spec, csf, factors = _mttkrp_inputs(I, J, K, R, density, seed)
    tuned, stats = tune(spec, csf=csf, factors=factors, tuner=FAST)
    # the model's pick is always measured, and the winner is the measured
    # minimum, so this holds by construction *of real measurements*
    assert stats.model_seconds is not None
    assert stats.best_seconds <= stats.model_seconds
    assert stats.candidates_timed >= 1
    assert stats.executions >= stats.candidates_timed
    # and the tuned plan computes the right answer
    out = VectorizedExecutor(spec, tuned.path, tuned.order)(
        CSFArrays.from_csf(csf), factors)
    oracle = dense_oracle(spec, csf,
                          {k: np.asarray(v) for k, v in factors.items()})
    np.testing.assert_allclose(np.asarray(out), oracle, atol=1e-3)


def test_candidates_over_device_memory_are_infeasible(monkeypatch):
    """A candidate whose program holds one buffer larger than the device
    (here: the dense fallback that materializes the whole sparse tensor)
    is never compiled or timed and never wins."""
    from repro.autotune import measure
    from repro.core.executor import make_executor, prepare_operand
    spec, csf, factors = _mttkrp_inputs(64, 48, 40, 8, 0.02, 5)
    cands = generate_candidates(spec, nnz_levels=csf.nnz_levels(),
                                max_paths=4, max_candidates=8,
                                orders_per_path=1)
    arrays = CSFArrays.from_csf(csf)
    sizes = {}
    for c in cands:
        ex = make_executor(spec, c.path, c.order)
        sizes[c.key] = measure.largest_buffer_bytes(
            ex.__call__, prepare_operand(ex, arrays, factors), factors)
    dense_t = 64 * 48 * 40 * 4
    limit = dense_t - 1
    assert min(sizes.values()) <= limit < max(sizes.values())
    monkeypatch.setattr(measure, "device_bytes_limit", lambda: limit)
    results = measure.measure_candidates(spec, cands, arrays, factors)
    assert {m.candidate.key: m.infeasible for m in results} == {
        k: s > limit for k, s in sizes.items()}
    tuned, stats = tune(spec, csf=csf, factors=factors, tuner=FAST)
    assert stats.infeasible >= 1
    ex = make_executor(spec, tuned.path, tuned.order)
    assert measure.largest_buffer_bytes(
        ex.__call__, prepare_operand(ex, arrays, factors), factors) <= limit


def test_tune_refuses_when_no_candidate_fits(monkeypatch):
    from repro.autotune import measure
    spec, csf, factors = _mttkrp_inputs()
    monkeypatch.setattr(measure, "device_bytes_limit", lambda: 1)
    with pytest.raises(ValueError, match="no candidate fits"):
        tune(spec, csf=csf, factors=factors, tuner=FAST)


def test_candidates_are_model_ranked_and_deduped():
    spec, csf, _ = _mttkrp_inputs()
    cands = generate_candidates(spec, nnz_levels=csf.nnz_levels(),
                                max_paths=4, max_candidates=6,
                                orders_per_path=2)
    assert 1 <= len(cands) <= 6
    assert len({c.key for c in cands}) == len(cands)
    scores = [(c.cost, c.flops) for c in cands]
    assert scores == sorted(scores)


# --------------------------------------------------------------------- #
# (b) cache round trip: identical plan, identical output
# --------------------------------------------------------------------- #
def test_plan_serialization_round_trip(tmp_path):
    spec, csf, factors = _mttkrp_inputs()
    tuned, _ = tune(spec, csf=csf, factors=factors, tuner=FAST,
                    cache_dir=str(tmp_path))
    rt = plan_from_json(plan_to_json(tuned))
    assert rt == tuned                      # full dataclass equality
    assert rt.spec == tuned.spec and rt.order == tuned.order
    arrays = CSFArrays.from_csf(csf)
    out_a = np.asarray(VectorizedExecutor(spec, tuned.path, tuned.order)(
        arrays, factors))
    out_b = np.asarray(VectorizedExecutor(rt.spec, rt.path, rt.order)(
        arrays, factors))
    np.testing.assert_array_equal(out_a, out_b)   # same program, bitwise


def test_cache_round_trip_via_disk(tmp_path):
    spec, csf, factors = _mttkrp_inputs()
    p1 = plan(spec, autotune=True, cache_dir=str(tmp_path), csf=csf,
              factors=factors, tuner=FAST)
    assert not p1.stats.cache_hit and p1.stats.executions > 0
    p2 = plan(spec, autotune=True, cache_dir=str(tmp_path), csf=csf,
              factors=factors, tuner=FAST)
    assert p2.stats.cache_hit
    assert p2.stats.executions == 0         # zero candidate executions
    assert p2.stats.candidates_timed == 0
    assert p1 == p2                         # identical SpTTNPlan
    # one well-formed JSON entry on disk
    files = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
    assert len(files) == 1
    with open(tmp_path / files[0]) as f:
        doc = json.load(f)
    assert "plan" in doc and "meta" in doc
    assert doc["meta"]["executions"] == p1.stats.executions


@pytest.mark.parametrize("garbage", [
    "{not json",                       # invalid JSON
    '{"plan": []}',                    # valid JSON, wrong shape
    '{"plan": {"version": 99}}',       # unknown serialization version
    '"just a string"',                 # not even an object
])
def test_corrupt_cache_entry_is_a_miss(tmp_path, garbage):
    spec, csf, factors = _mttkrp_inputs()
    p1 = plan(spec, autotune=True, cache_dir=str(tmp_path), csf=csf,
              factors=factors, tuner=FAST)
    files = os.listdir(tmp_path)
    with open(tmp_path / files[0], "w") as f:
        f.write(garbage)
    p2 = plan(spec, autotune=True, cache_dir=str(tmp_path), csf=csf,
              factors=factors, tuner=FAST)
    assert not p2.stats.cache_hit           # re-searched, then re-wrote
    p3 = plan(spec, autotune=True, cache_dir=str(tmp_path), csf=csf,
              factors=factors, tuner=FAST)
    assert p3.stats.cache_hit and p1.spec == p3.spec


# --------------------------------------------------------------------- #
# (c) cache key: values don't matter, pattern does
# --------------------------------------------------------------------- #
def test_cache_key_same_pattern_different_values_hits(tmp_path):
    I, J, K, R = 24, 16, 12, 4
    spec = S.mttkrp(I, J, K, R)
    base = random_sparse((I, J, K), 0.1, seed=5)
    csf_a = build_csf(base)
    other = from_coords(base.coords.copy(),
                        (base.values * 3.0 + 1.0).astype(np.float32),
                        (I, J, K))
    csf_b = build_csf(other)
    assert not np.allclose(csf_a.values, csf_b.values)
    dev = device_kind()
    key_a = cache_key(spec, csf_a.nnz_levels(), dev)
    key_b = cache_key(spec, csf_b.nnz_levels(), dev)
    assert key_a == key_b                   # values never enter the key

    p1 = plan(spec, autotune=True, cache_dir=str(tmp_path), csf=csf_a,
              tuner=FAST)
    p2 = plan(spec, autotune=True, cache_dir=str(tmp_path), csf=csf_b,
              tuner=FAST)
    assert not p1.stats.cache_hit and p2.stats.cache_hit
    assert p1.stats.cache_key == p2.stats.cache_key

    # a different pattern (different nnz-level profile) misses
    csf_c = build_csf(random_sparse((I, J, K), 0.25, seed=9))
    assert csf_c.nnz_levels() != csf_a.nnz_levels()
    p3 = plan(spec, autotune=True, cache_dir=str(tmp_path), csf=csf_c,
              tuner=FAST)
    assert not p3.stats.cache_hit
    assert p3.stats.cache_key != p1.stats.cache_key


def test_cache_key_depends_on_spec_and_device():
    spec_a = S.mttkrp(24, 16, 12, 4)
    spec_b = S.mttkrp(24, 16, 12, 8)        # different rank dim
    levels = {0: 1, 1: 10, 2: 50, 3: 100}
    assert spec_signature(spec_a) != spec_signature(spec_b)
    assert (cache_key(spec_a, levels, "cpu:x") !=
            cache_key(spec_b, levels, "cpu:x"))
    assert (cache_key(spec_a, levels, "cpu:x") !=
            cache_key(spec_a, levels, "tpu:v5e"))


def test_plan_cache_atomic_put_and_get(tmp_path):
    spec, csf, factors = _mttkrp_inputs()
    tuned, stats = tune(spec, csf=csf, factors=factors, tuner=FAST)
    cache = PlanCache(str(tmp_path))
    path = cache.put("abc123", tuned, meta={"note": "t"})
    assert os.path.exists(path)
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    got = cache.get("abc123")
    assert got == tuned
    assert cache.get("missing") is None
