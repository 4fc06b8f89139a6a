"""Seeded sparse tensors with FROSTT-like skew on every mode.

The sparsity pattern stands for the dataset: it is fixed per configuration
by its ``pattern_seed`` and drawn once per checkout, then kept under
``chipbench/.cache/`` (git-ignored) as the compile cache is.  Values and
initial factors come from the run's ``--seed``, so every seed runs the same
shapes and the same plans.

Indices of each mode follow a power law, index ``i`` drawn with weight
``(i + 1) ** -alpha``.  Keys are drawn ``overdraw`` times the target nnz,
deduplicated, and trimmed to exactly ``nnz`` at random, so the pattern has
exactly the configured number of nonzeros.
"""
from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path

import numpy as np

CACHE = Path(__file__).resolve().parent / ".cache"


def power_law_indices(rng: np.random.Generator, n: int, size: int,
                      alpha: float) -> np.ndarray:
    """``size`` draws from ``0..n-1`` with weight ``(i + 1) ** -alpha``."""
    cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -alpha)
    cdf /= cdf[-1]
    idx = np.searchsorted(cdf, rng.random(size), side="right")
    return np.minimum(idx, n - 1).astype(np.int64)


def draw_pattern(dims, nnz: int, alpha: float, overdraw: float,
                 seed: int) -> np.ndarray:
    """Coordinates ``(nnz, order)`` int32, sorted lexicographically and
    free of duplicates; a function of its arguments alone."""
    total = float(np.prod([float(d) for d in dims]))
    if nnz > total:
        raise ValueError(f"nnz {nnz} exceeds the index space {total:g}")
    rng = np.random.default_rng(seed)
    draws = int(np.ceil(nnz * overdraw))
    keys = np.zeros(draws, dtype=np.int64)
    for n in dims:
        keys = keys * n + power_law_indices(rng, n, draws, alpha)
    keys = np.unique(keys)
    if len(keys) < nnz:
        raise ValueError(f"{draws} draws gave {len(keys)} distinct keys, "
                         f"fewer than nnz {nnz}: raise 'overdraw'")
    keys = keys[np.sort(rng.choice(len(keys), size=nnz, replace=False))]
    return np.stack(np.unravel_index(keys, tuple(dims)),
                    axis=1).astype(np.int32)


def pattern_params(cfg: dict) -> dict:
    return {k: cfg[k] for k in ("dims", "nnz", "skew_alpha", "overdraw",
                                "pattern_seed")}


def _cache_file(cache: Path, cfg: dict, stem: str, suffix: str) -> Path:
    """``cache/<stem>-<digest of the pattern's parameters><suffix>``."""
    digest = hashlib.sha256(json.dumps(pattern_params(cfg), sort_keys=True)
                            .encode()).hexdigest()[:16]
    return cache / f"{stem}-{digest}{suffix}"


def _write_atomic(path: Path, write) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(f".{os.getpid()}.tmp")
    with open(tmp, "wb") as f:
        write(f)
    os.replace(tmp, path)


def load_pattern(cfg: dict, cache: Path | None = CACHE) -> np.ndarray:
    """The configuration's pattern, from the checkout's cache when it was
    drawn before."""
    path = None if cache is None else _cache_file(cache, cfg, "pattern",
                                                   ".npy")
    if path is not None and path.exists():
        return np.load(path)
    p = pattern_params(cfg)
    coords = draw_pattern(p["dims"], p["nnz"], p["skew_alpha"],
                          p["overdraw"], p["pattern_seed"])
    if path is not None:
        _write_atomic(path, lambda f: np.save(f, coords))
    return coords


def draw_values(nnz: int, seed: int) -> np.ndarray:
    """The tensor's values for ``--seed``: standard normal float32."""
    return np.random.default_rng([seed, 1]).standard_normal(
        nnz, dtype=np.float32)


def level_counts(coords: np.ndarray, mode: int) -> dict[int, int]:
    """CSF level counts ``nnz^(I1..Ip)`` of the tensor stored with
    ``mode`` first and the other modes in increasing order, computed from
    the coordinates alone (no CSF is built)."""
    perm = [mode] + [m for m in range(coords.shape[1]) if m != mode]
    radix = coords.max(axis=0).astype(np.int64) + 1
    counts = {0: 1, 1: int(np.count_nonzero(np.bincount(coords[:, mode])))}
    key = coords[:, mode].astype(np.int64)
    for p, m in enumerate(perm[1:-1], start=2):
        key = key * radix[m] + coords[:, m]
        counts[p] = len(np.unique(key))
    counts[len(perm)] = len(coords)
    return counts


def pattern_levels(cfg: dict, coords: np.ndarray,
                   cache: Path | None = CACHE) -> list[dict[int, int]]:
    """``level_counts`` of every mode, kept beside the cached pattern."""
    path = None if cache is None else _cache_file(cache, cfg, "levels",
                                                   ".json")
    if path is not None and path.exists():
        return [{int(k): v for k, v in d.items()}
                for d in json.loads(path.read_text())]
    levels = [level_counts(coords, m) for m in range(coords.shape[1])]
    if path is not None:
        _write_atomic(path, lambda f: f.write(json.dumps(levels).encode()))
    return levels
