"""The seeded generator: determinism, exact nnz, sorted unique keys."""
import numpy as np

from chipbench import data

CFG = {"dims": [60, 50, 70], "nnz": 3000, "skew_alpha": 0.8,
       "overdraw": 1.3, "pattern_seed": 11}


def draw(**kw):
    p = dict(CFG, **kw)
    return data.draw_pattern(p["dims"], p["nnz"], p["skew_alpha"],
                             p["overdraw"], p["pattern_seed"])


def test_pattern_is_a_function_of_its_parameters():
    a, b = draw(), draw()
    assert np.array_equal(a, b)
    assert not np.array_equal(a, draw(pattern_seed=12))


def test_pattern_exact_sorted_unique_in_range():
    c = draw()
    assert c.shape == (3000, 3) and c.dtype == np.int32
    keys = np.ravel_multi_index(tuple(c.T.astype(np.int64)), CFG["dims"])
    assert np.all(np.diff(keys) > 0)
    assert np.all(c >= 0) and np.all(c.max(axis=0) < CFG["dims"])


def test_every_mode_is_skewed():
    c = draw(nnz=6000, dims=[200, 200, 200], overdraw=1.3)
    for m in range(3):
        counts = np.bincount(c[:, m], minlength=200)
        assert counts[:20].sum() > 3 * counts[-20:].sum()


def test_values_follow_the_seed():
    big = 2 ** 31 + 12345
    assert np.array_equal(data.draw_values(100, big),
                          data.draw_values(100, big))
    assert not np.array_equal(data.draw_values(100, 1),
                              data.draw_values(100, 2))
    assert data.draw_values(100, 1).dtype == np.float32


def test_cache_round_trip(tmp_path):
    first = data.load_pattern(CFG, cache=tmp_path)
    assert len(list(tmp_path.glob("pattern-*.npy"))) == 1
    assert np.array_equal(first, data.load_pattern(CFG, cache=tmp_path))
    assert np.array_equal(first, data.load_pattern(CFG, cache=None))
    levels = data.pattern_levels(CFG, first, cache=tmp_path)
    assert levels == data.pattern_levels(CFG, first, cache=tmp_path)
    assert levels[0][3] == 3000
