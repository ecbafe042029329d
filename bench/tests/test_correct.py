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
import counts
import faults
import run
from conftest import BENCH, MLP_MODEL, load, make_checkout, tiny_cell
from repro.sim.engine import RoundEngine

CELLS = {"tiny-r.fedhap": "paper-5x8.fedhap",
         "tiny-t.fedsat": "paper-5x8.fedsat",
         "tiny-m.fedhap": "paper-5x8.fedhap"}
SEED = 2**31 + 11
# The 2NN, which no chip cell runs: its tiny cell shows that a payload
# comes in as new files alone.
MODELS = {"tiny-m.fedhap": {"model": MLP_MODEL, "model_kind": "mlp"}}
# The check values, window work and window FLOPs that the harness read on
# the two CNN cells at SEED before the model and the data moved into
# payloads/ and datasets/ (the same runs as ``run_tiny``).
PARITY = load(BENCH / "tests" / "data" / "parity.json")


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    b = make_checkout(tmp_path_factory.mktemp("correct"))
    for name, like in CELLS.items():
        tiny_cell(b, name, like, **MODELS.get(name, {}))
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


@pytest.mark.parametrize("name", sorted(PARITY["cells"]))
def test_parity_with_recorded_values(bench, name, monkeypatch):
    want = PARITY["cells"][name]
    assert PARITY["seed"] == SEED
    seen = {}
    unwrap = run.Recorder.unwrap

    def keep_work(self):
        seen["work"] = dict(self.work)
        unwrap(self)

    monkeypatch.setattr(run.Recorder, "unwrap", keep_work)
    res = run_tiny(bench, name)
    cell = run.load_cell(bench, name)
    work = seen["work"]
    got = {k: v["value"] for k, v in res["checks"].items()}
    assert got == want["checks"]
    assert {k: work[k] for k in want["work"]} == want["work"]
    assert counts.window_flops(cell.config["model"],
                               run.sim_config(cell, SEED),
                               work) == want["window_flops"]


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
