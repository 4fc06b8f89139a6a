"""The check fails a broken timed path, and fails the control.

Each case drives a whole rehearsal run through ``run.main`` (the chip
check passes: the configuration is a rehearsal), with the job broken
underneath, and sees ``correct`` come out false.  One chip, so there is no
exchange between chips to leave out.
"""
import json

import numpy as np
import pytest

from chipbench import run as harness

CELLS = ["tiny-cp.als", "tiny-tucker.hooi"]


def unchanged(run, load):
    """Each update returns the mode's factor as it was."""
    job = load(run)
    update = job.update

    def stale(m):
        old = job.factors[m]
        update(m)
        job.factors[m] = old
        last = list(job.last[m])
        last[3] = old
        job.last[m] = tuple(last)

    job.update = stale
    return job


def half_batch(run, load):
    """The program's tensor holds every other nonzero, doubled: the mean
    over the half that is left."""
    true = run.values
    half = true.copy()
    half[1::2] = 0
    half[0::2] *= 2
    run.values = half
    try:
        return load(run)
    finally:
        run.values = true


def altered(run, load):
    """Each kernel's output is altered in the row it produces for the
    mode's heaviest slice."""
    job = load(run)
    for k in job.kernels:
        row = int(np.argmax(np.bincount(run.coords[:, k.mode])))
        call = k.call
        k.call = lambda f, call=call, row=row: call(f).at[row].multiply(1.01)
    return job


def control(run, load):
    """The reference at the next precision down, in the program's place."""
    return load(run, impl="control")


def result(monkeypatch, capsys, cell, fault):
    load = harness.load_job
    monkeypatch.setattr(harness, "load_job",
                        lambda run, impl="program": fault(run, load))
    assert harness.main(["--workload", cell, "--seed", "5", "--seconds",
                         "0.3"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", [unchanged, half_batch, altered, control])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_path_is_not_correct(monkeypatch, capsys, cell, fault):
    res = result(monkeypatch, capsys, cell, fault)
    assert res["correct"] is False
    assert res["failed"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_sound_path_is_correct(monkeypatch, capsys, cell):
    res = result(monkeypatch, capsys, cell, lambda run, load: load(run))
    assert res["correct"] is True
