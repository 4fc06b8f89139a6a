"""Every piece of a cell is found by name, and a CPU rehearsal of each mix
at its tiny rehearsal configuration runs end to end."""
import importlib
import json
import os
import subprocess
import sys

import pytest

from chipbench.run import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
HERE = ROOT / "chipbench"
REHEARSALS = {"als": "tiny-cp", "hooi": "tiny-tucker"}


def test_every_cell_finds_its_files():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for cell in BENCH["workloads"]:
        cfg = json.loads((HERE / "configs" / f"{cell['config']}.json")
                         .read_text())
        assert configs[cell["config"]]["file"] == \
            f"chipbench/configs/{cell['config']}.json"
        assert not cfg.get("rehearsal")
        mix = json.loads((HERE / "mixes" / f"{cell['traffic']}.json")
                         .read_text())
        job = importlib.import_module(f"chipbench.jobs.{mix['job']}")
        assert set(job.CHECKS) == set(cfg["limits"])
    for m in BENCH["per_layer"]:
        assert callable(importlib.import_module(
            f"chipbench.metrics.{m['name']}").read)


def test_rehearsal_configs_are_marked_and_unlisted():
    listed = {c["name"] for c in BENCH["configs"]}
    for path in (HERE / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        assert cfg["name"] == path.stem
        if cfg.get("rehearsal"):
            assert path.stem not in listed
        else:
            assert "limits" in cfg and "check_rows" in cfg


def run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(HERE / "run.py"), *args],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=600)


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("traffic", sorted(REHEARSALS))
def test_rehearsal_prints_the_result_line(traffic, trace):
    cell = f"{REHEARSALS[traffic]}.{traffic}"
    p = run("--workload", cell, "--seed", str(2 ** 31 + 7), "--seconds",
            "1", "--trace", trace)
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(res)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(res)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 3 and res["device"]["platform"] == "cpu"
    assert res["metrics"]
    assert all(k.startswith("cpu_rehearsal.") for k in res["metrics"])
    if trace == "1":
        assert {"cpu_rehearsal.csf_build_s", "cpu_rehearsal.plan_s",
                "cpu_rehearsal.warmup_s"} <= set(res["metrics"])
    else:
        assert "cpu_rehearsal.setup_s" in res["metrics"]
    for name, check in res["checks"].items():
        assert check["value"] <= check["limit"]


def test_a_cell_needs_the_chip():
    p = run("--workload", BENCH["workloads"][0]["name"], "--seed", "1",
            "--seconds", "1", "--trace", "0")
    assert p.returncode == 2 and p.stdout == ""


def test_unknown_cell_is_refused():
    p = run("--workload", "nothing.here", "--seed", "1", "--seconds", "1")
    assert p.returncode != 0 and p.stdout == ""
