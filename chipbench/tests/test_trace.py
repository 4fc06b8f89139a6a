"""The trace reduction on a small trace recorded on a TPU v5e chip
(``record_trace.py``): busy-interval union, per-program device time and
calls, and the attribution of idle gaps to host spans."""
import json
from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).resolve().parent / "data"
META = json.loads((DATA / "small.json").read_text())


@pytest.fixture(scope="module")
def small():
    return trace.load(str(DATA / "small.xplane.pb"))


@pytest.fixture(scope="module")
def summary(small):
    return trace.reduce(small, META["spans"])


def sweep_union(intervals):
    """Covered length by an event sweep (another algorithm than
    ``trace.union``'s merge)."""
    points = sorted([(a, 1) for a, b in intervals]
                    + [(b, -1) for a, b in intervals])
    covered, depth, last = 0.0, 0, None
    for t, step in points:
        if depth > 0:
            covered += t - last
        depth += step
        last = t
    return covered


def test_the_trace_is_from_the_chip(small):
    assert META["device_kind"] == "TPU v5 lite"
    assert any(trace.OPS_LINE in lines for lines in small.devices.values())


def test_busy_union(small, summary):
    window = next(e for e in small.host if e.name == trace.WINDOW)
    dev = next(d for d in sorted(small.devices)
               if small.devices[d].get(trace.OPS_LINE))
    ops = [(max(e.start, window.start), min(e.end, window.end))
           for e in small.devices[dev][trace.OPS_LINE]
           if e.end > window.start and e.start < window.end]
    assert summary.devices >= 1
    assert summary.window_s == pytest.approx(
        (window.end - window.start) * 1e-9)
    assert summary.busy_s == pytest.approx(sweep_union(ops) * 1e-9,
                                           rel=1e-9)
    assert 0 < summary.busy_s < summary.window_s
    assert 0 < summary.idle_share < 1


def test_per_program_device_time(summary):
    for name, calls in META["calls"].items():
        seconds, n = summary.programs[name]
        assert n == calls
        assert 0 < seconds <= summary.busy_s
    kernel = summary.programs["spttn_demo_m0"][0]
    assert kernel > summary.programs["dense_demo"][0]


def test_idle_gaps_named_by_host_span(summary):
    gaps = dict(summary.idle_gaps)
    assert gaps["host_sleep"] >= 0.9 * META["sleep_s"]
    assert set(gaps) <= set(META["spans"]) | {trace.NO_SPAN}
    assert sum(gaps.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)


def test_top_ops_descend(summary):
    secs = [s for _, s in summary.top_ops]
    assert secs == sorted(secs, reverse=True) and secs[0] > 0


def test_program_name():
    assert trace.program_name("jit_spttn_mttkrp_m0(12)") == \
        "spttn_mttkrp_m0"
    assert trace.program_name("jit_solve") == "solve"
