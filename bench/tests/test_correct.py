"""The correctness check at CPU size: a sound run is correct, and each
fault a cell can have, planted under the timed path, makes ``correct``
false; so does the control, the reference in bfloat16 in the program's
place, and a plan that draws rows outside a satellite's partition. The
runs skip the harness's look for a chip and drive the rest of a run on
a CPU-sized copy of each cell, held to that cell's limits."""
from __future__ import annotations

import jax
import numpy as np
import pytest

import calibrate
import faults
import run
from conftest import make_checkout, tiny_cell
from repro.sim.engine import RoundEngine

CELLS = {"tiny-r.fedhap": "paper-5x8.fedhap",
         "tiny-t.fedsat": "paper-5x8.fedsat"}
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    b = make_checkout(tmp_path_factory.mktemp("correct"))
    for name, like in CELLS.items():
        tiny_cell(b, name, like)
    return b


def run_tiny(bench, name: str) -> dict:
    cell = run.load_cell(bench, name)
    return run.run_cell(cell, SEED, 0.01, False, jax.devices()[:1])


@pytest.mark.parametrize("name", sorted(CELLS))
def test_sound_run_is_correct(bench, name):
    res = run_tiny(bench, name)
    assert res["correct"], res["checks"]
    assert list(res["checks"]) == list(run.CHECKS)
    assert res["checks"]["plan_faults"]["value"] == 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("name", sorted(CELLS))
def test_fault_makes_correct_false(bench, name, fault):
    with faults.planted(fault, run.load_cell(bench, name).family):
        res = run_tiny(bench, name)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", sorted(CELLS))
def test_rows_outside_the_partition_make_correct_false(bench, name,
                                                       monkeypatch):
    """The strategy's plan is broken: satellite 0 trains on satellite
    1's rows. The reference trains on the same rows, so only the check
    of the plan against the configuration can see it."""
    orig = RoundEngine.sample_indices

    def foreign(self, *args, **kw):
        idx = np.array(orig(self, *args, **kw))
        idx[0] = idx[1]
        return idx

    monkeypatch.setattr(RoundEngine, "sample_indices", foreign)
    res = run_tiny(bench, name)
    assert not res["correct"], res["checks"]
    assert res["checks"]["plan_faults"]["value"] > 0


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_is_not_correct(bench, name):
    cell = run.load_cell(bench, name)
    got = calibrate.readings(cell, SEED, ["sound", "control"],
                             jax.devices()[:1])
    assert run.judge(got["sound"], cell.limits)[0], got["sound"]
    assert not run.judge(got["control"], cell.limits)[0], got["control"]
