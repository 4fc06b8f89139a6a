"""The work model: ops and bytes of a direct count, independence from the
plan and backend, and the peaks table."""
import inspect

import numpy as np
import pytest

from chipbench import data, work
from chipbench.metrics import _roofline


def tiny_coords(seed=0):
    return data.draw_pattern((6, 5, 7), 60, 0.8, 1.5, seed)


def direct_count(coords, mode, kind, ranks):
    """Walk the factorized loop nest over the nonzeros and count."""
    o1, o2 = (m for m in range(3) if m != mode)
    fibers1, fibers2, ops = set(), set(), 0
    for c in coords:
        fibers1.add(c[mode])
        fibers2.add((c[mode], c[o1]))
        # leaf: t[b] += v * U2[k, b]
        ops += 2 * ranks[1]
    for _ in fibers2:
        # fiber: out[i, (a,) b] += U1[j, a] * t[b]
        ops += 2 * (ranks[0] if kind == "mttkrp" else ranks[0] * ranks[1])
    dims = coords.max(axis=0) + 1
    I, J, K = dims[mode], dims[o1], dims[o2]
    out = I * ranks[0] * (1 if kind == "mttkrp" else ranks[1])
    nbytes = 4 * (2 * len(coords) + len(fibers1) + len(fibers2)
                  + ranks[0] * J + ranks[1] * K + out)
    return ops, nbytes, (I, J, K)


@pytest.mark.parametrize("kind,ranks", [("mttkrp", (4, 4)),
                                        ("ttmc3", (3, 5))])
@pytest.mark.parametrize("mode", [0, 1, 2])
def test_work_matches_direct_count(kind, ranks, mode):
    coords = tiny_coords()
    ops, nbytes, dims = direct_count(coords, mode, kind, ranks)
    levels = data.level_counts(coords, mode)
    assert work.work(kind, dims, ranks, levels) == (ops, nbytes)


@pytest.mark.parametrize("mode", [0, 1, 2])
def test_level_counts_match_the_programs_csf(mode):
    from repro import build_csf
    from repro.sparse.coo import COOTensor
    coords = tiny_coords(1)
    coo = COOTensor(coords=coords, values=np.ones(len(coords), np.float32),
                    shape=(6, 5, 7))
    perm = (mode,) + tuple(m for m in range(3) if m != mode)
    csf = build_csf(coo.permute_modes(perm))
    assert data.level_counts(coords, mode) == csf.nnz_levels()


def test_work_takes_no_plan():
    params = set(inspect.signature(work.work).parameters)
    assert params == {"kind", "dims", "ranks", "levels"}


class FakeRun:
    def __init__(self, coords, kernels, seconds):
        from chipbench.trace import Summary
        self.coords, self.device_kind = coords, "TPU v5 lite"
        self.job = type("J", (), {"kernels_info": kernels})()
        self.trace = Summary(window_s=1.0, busy_s=0.5, programs={
            k["name"]: (seconds, 10) for k in kernels}, top_ops=[],
            idle_gaps=[], devices=1)
        self.log = lambda msg: None

    def levels(self, mode):
        return data.level_counts(self.coords, mode)


def test_two_plans_get_identical_work():
    """The roofline of one kernel reads the same whichever plan and
    backend ran it: only the spec's kind, ranks and the level counts
    enter."""
    from repro import plan
    from repro.core.spec import mttkrp
    coords = tiny_coords(2)
    spec = mttkrp(6, 5, 7, 4)
    model = plan(spec, nnz_levels=data.level_counts(coords, 0))
    info = {"name": "spttn_mttkrp_m0", "kind": "mttkrp", "mode": 0,
            "dims": (6, 5, 7), "ranks": (4, 4)}
    a = FakeRun(coords, [dict(info, backend="xla", plan=model)], 1e-6)
    b = FakeRun(coords, [dict(info, backend="pallas", plan=None)], 1e-6)
    ra = _roofline.kernel_roofline(a, "mttkrp")
    assert ra is not None and ra > 0
    assert ra == _roofline.kernel_roofline(b, "mttkrp")


def test_roofline_reads_nothing_without_the_kernel():
    coords = tiny_coords(2)
    info = {"name": "spttn_ttmc3_m0", "kind": "ttmc3", "mode": 0,
            "dims": (6, 5, 7), "ranks": (2, 2)}
    assert _roofline.kernel_roofline(FakeRun(coords, [info], 1e-6),
                                     "mttkrp") is None


def test_peaks_known_and_unknown():
    p = work.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_roofline_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline(100, 10, 2.0, peak) == (50.0, "bytes")
    assert work.roofline(1000, 10, 20.0, peak) == (50.0, "flops")
