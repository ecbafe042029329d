"""The digits dataset's copy against the program: the same arrays for a
seed, and the same partition of the training rows over the satellites."""
from __future__ import annotations

import numpy as np
import pytest

import parts
from conftest import BENCH, load

SEED = 2**31 + 7
SIM = {**load(BENCH / "configs" / "paper-5x8.json")["sim"],
       "num_samples": 3000, "eval_samples": 500}
DIGITS = parts.dataset("digits")


def test_digits_copy_matches_the_program():
    from repro.clients import load_dataset
    images, labels = load_dataset("digits", num_samples=3000, seed=SEED)
    train_x, train_y, eval_x, eval_y = DIGITS.make(SIM, SEED)
    np.testing.assert_array_equal(np.concatenate([eval_x, train_x]), images)
    np.testing.assert_array_equal(np.concatenate([eval_y, train_y]), labels)


@pytest.mark.parametrize("iid", [False, True])
def test_partition_matches_the_program(iid):
    from repro.data.partition import partition_iid, partition_noniid_by_orbit
    sim = {**SIM, "iid": iid}
    _, train_y, _, _ = DIGITS.make(sim, SEED)
    n_sats = sim["num_orbits"] * sim["sats_per_orbit"]
    if iid:
        held = partition_iid(train_y, n_sats, SEED)
    else:
        held = partition_noniid_by_orbit(train_y, sim["num_orbits"],
                                         sim["sats_per_orbit"], SEED)
    assert list(DIGITS.satellite_sizes(sim, train_y)) == [len(r)
                                                          for r in held]
    sats = np.arange(n_sats)
    every = np.broadcast_to(np.arange(len(train_y)), (n_sats, len(train_y)))
    mine = DIGITS.in_partition(sim, train_y, sats, every)
    for s, rows in enumerate(held):
        assert mine[s, rows].all()
    if not iid:
        # Orbits 0-2 hold classes 0-5, orbits 3-4 classes 6-9.
        assert not mine[0, held[-1]].any() and not mine[-1, held[0]].any()
