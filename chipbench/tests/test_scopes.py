"""Device time by loop-nest term scope (``chipbench/scopes.py``) on the
small chip trace, and the readers of the program's own spans and
counters."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import scopes, trace
from chipbench.metrics import (csf_levels_s, csf_sort_s, csf_upload_gib,
                               csf_upload_s, spttn_lift_share,
                               spttn_reduce_share)

DATA = Path(__file__).resolve().parent / "data"
SMALL = str(DATA / "small.xplane.pb")


@pytest.fixture(scope="module")
def small():
    return trace.load(SMALL)


@pytest.fixture(scope="module")
def paths():
    return scopes.op_paths(SMALL)


def program_ids(tr):
    return {trace.program_name(e.name): scopes.PROGRAM_ID.search(
        e.name).group(1)
        for lines in tr.devices.values()
        for e in lines.get(trace.MODULES_LINE, [])}


def ops_of(paths, pid):
    return {trace.op_label(name): tf for (p, name), tf in paths.items()
            if p == pid}


def test_spttn_demo_ops_map_to_their_jax_ops(small, paths):
    ids = program_ids(small)
    demo = ops_of(paths, ids["spttn_demo_m0"])
    label = next(k for k in demo if k.startswith("fusion.1 = "))
    assert demo[label] == "jit(spttn_demo_m0)/scatter-add:"
    label = next(k for k in demo if k.startswith("broadcast_clamp_fusion"))
    assert demo[label] == "jit(spttn_demo_m0)/gather:"
    # the same op name in another program keeps that program's metadata
    dense = ops_of(paths, ids["dense_demo"])
    label = next(k for k in dense if k.startswith("fusion.1 = "))
    assert dense[label].startswith("jit(dense_demo)/")


def test_every_traced_op_name_is_known(small, paths):
    names = {e.name for lines in small.devices.values()
             for e in lines.get(trace.OPS_LINE, [])}
    known = {name for _, name in paths}
    assert len(names & known) >= len(names) - 2    # copy-start/-done


def test_wire_reader_agrees_with_protobuf(paths):
    """Tensorflow's generated ``xplane_pb2``, where it is installed,
    reads the same ``tf_op`` of every op (in a child process: the reader
    itself must not need tensorflow)."""
    code = (
        "import json, sys\n"
        "from tensorflow.tsl.profiler.protobuf import xplane_pb2\n"
        "xs = xplane_pb2.XSpace()\n"
        "xs.ParseFromString(open(sys.argv[1], 'rb').read())\n"
        "out = []\n"
        "for pl in xs.planes:\n"
        "    if not pl.name.startswith('/device:'):\n"
        "        continue\n"
        "    names = {k: v.name for k, v in pl.stat_metadata.items()}\n"
        "    for md in pl.event_metadata.values():\n"
        "        st = {names[s.metadata_id]: s for s in md.stats}\n"
        "        if 'tf_op' in st and 'program_id' in st:\n"
        "            p = st['program_id']\n"
        "            pid = (p.uint64_value or p.int64_value) % 2 ** 64\n"
        "            out.append([str(pid), md.name, st['tf_op'].str_value])\n"
        "print(json.dumps(out))\n")
    res = subprocess.run([sys.executable, "-c", code, SMALL],
                         capture_output=True, text=True, timeout=300,
                         env=dict(os.environ, TF_CPP_MIN_LOG_LEVEL="3"))
    if res.returncode != 0 and "No module named" in res.stderr:
        pytest.skip("tensorflow's xplane_pb2 is not installed")
    assert res.returncode == 0, res.stderr[-2000:]
    expected = {(p, n): tf for p, n, tf in json.loads(
        res.stdout.strip().splitlines()[-1])}
    assert expected == paths


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(spttn_mttkrp_m0)/t1.reduce/scatter-add:", "t1.reduce"),
    ("jit(spttn_x)/t0.stage.reduce/spttn_reduce/pallas_call:",
     "t0.stage.reduce"),
    ("jit(spttn_x)/t2.dense/t2.lift/gather:", "t2.lift"),
    ("jit(spttn_x)/out/scatter-add:", "out"),
    ("jit(spttn_demo_m0)/gather:", None),
    ("f:", None),
    (None, None),
])
def test_scope_of(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def test_kind_of():
    assert scopes.kind_of("t3.stage.reduce") == "stage.reduce"
    assert scopes.kind_of("t0.lift") == "lift"
    assert scopes.kind_of("out") == "out"


def test_term_seconds_cover_each_programs_ops(small, paths):
    """Every op inside the window and inside a program run is counted
    once, under that program (a direct sum, op by op)."""
    got = scopes.term_seconds(small, paths)
    window = next(e for e in small.host if e.name == trace.WINDOW)
    devices = [d for d in small.devices
               if small.devices[d].get(trace.OPS_LINE)]
    want: dict[str, float] = {}
    for d in devices:
        lines = small.devices[d]
        for e in lines[trace.OPS_LINE]:
            a, b = max(e.start, window.start), min(e.end, window.end)
            runs = [m for m in lines[trace.MODULES_LINE]
                    if m.start <= e.start < m.end]
            if b > a and runs:
                name = trace.program_name(runs[0].name)
                want[name] = want.get(name, 0.0) + (b - a) * 1e-9 / len(
                    devices)
    assert set(got) == set(want)
    for name, seconds in want.items():
        assert sum(got[name].values()) == pytest.approx(seconds, rel=1e-9)
    assert set(got["spttn_demo_m0"]) == {scopes.NO_SCOPE}


class FakeRun:
    def __init__(self, small):
        self.trace = trace.reduce(small, ["window"])
        self.lines = []

    def log(self, msg):
        self.lines.append(msg)


def test_shares_read_nothing_where_no_op_has_a_scope(small, monkeypatch):
    monkeypatch.setattr(scopes, "trace_file", lambda: Path(SMALL))
    run = FakeRun(small)
    assert spttn_reduce_share.read(run) is None
    assert spttn_lift_share.read(run) is None
    assert any("carries no scope" in line for line in run.lines)


def test_shares_of_a_scoped_program(small, paths, monkeypatch):
    """The fixture with its spttn ops renamed as the engine scopes them:
    the gather under ``t0.lift``, the scatter-add under ``t1.reduce``."""
    scoped = {k: tf.replace("/gather:", "/t0.lift/gather:")
              .replace("/scatter-add:", "/t1.reduce/scatter-add:")
              if tf.startswith("jit(spttn_") else tf
              for k, tf in paths.items()}
    monkeypatch.setattr(scopes, "trace_file", lambda: Path(SMALL))
    monkeypatch.setattr(scopes, "op_paths", lambda path: scoped)
    run = FakeRun(small)
    demo = scopes.term_seconds(small, scoped)["spttn_demo_m0"]
    total = sum(demo.values())
    assert spttn_reduce_share.read(run) == pytest.approx(
        100 * demo["t1.reduce"] / total)
    assert spttn_lift_share.read(run) == pytest.approx(
        100 * demo["t0.lift"] / total)
    assert 0 < spttn_lift_share.read(run) < spttn_reduce_share.read(run)
    assert any(line.startswith("scopes spttn_demo_m0:")
               for line in run.lines)


def test_shares_need_a_device_trace():
    run = FakeRun.__new__(FakeRun)
    run.trace, run.lines = None, []
    assert spttn_reduce_share.read(run) is None


def test_sparse_format_readers_read_the_programs_spans():
    from repro import spans
    from repro.core.executor import CSFArrays
    from repro.sparse import build_csf, random_sparse

    readers = (csf_sort_s, csf_levels_s, csf_upload_s, csf_upload_gib)
    spans.reset()
    assert [r.read(None) for r in readers] == [None] * 4
    coo = random_sparse((30, 20, 25), 0.05, seed=0)
    arrays = CSFArrays.from_csf(build_csf(coo.permute_modes((2, 0, 1))))
    totals = spans.totals()
    assert csf_sort_s.read(None) == totals["coo.sort"].seconds > 0
    assert csf_levels_s.read(None) == totals["csf.levels"].seconds > 0
    assert csf_upload_s.read(None) == totals["csf.upload"].seconds > 0
    import jax
    assert csf_upload_gib.read(None) * 2 ** 30 == sum(
        x.nbytes for x in jax.tree.leaves(arrays))
    spans.reset()
