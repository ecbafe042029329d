"""FedSat's tick: ``FusedExecutor.fedsat_event``.

One call trains every member of each orbit visited in the tick from that
orbit's last-known global, folds each orbit with its size weights, and
folds the orbits into the global one after another,
``g = (1 - rho) g + rho orbit``. The engine evaluates the global after
every tick, on the host path.
"""
from __future__ import annotations

import jax
import numpy as np

import plancheck

METHOD = "fedsat_event"
WEIGHTS = 4
STATE = (0, 1)


def capture(args: tuple) -> dict:
    _, _, visited, idx, lam_rows, rhos = args[:6]
    return {"visited": np.asarray(visited), "idx": idx,
            "lam": np.asarray(lam_rows), "rhos": np.asarray(rhos)}


def params_in(args: tuple):
    return args[0]


def params_out(out):
    return out[0]


def work(feed: dict, chips: int) -> dict:
    """Only the visited orbits' replicas count as trained: the padding
    orbit that rounds the tick up to a power of two is not model work."""
    v, k = feed["lam"].shape
    return {"updates": v, "trained": v * k, "evals": 1,
            "fold_rows": [k] * v}


def plan_faults(ref, feed: dict, seen: dict) -> int:
    """Rules the tick's plan breaks: the rows of every member of each
    visited orbit, the orbit's member weights ``lam`` (its members'
    shares of the orbit's samples) and its weight ``rho`` (the orbit's
    share of all samples), worked out from the configuration's
    partition."""
    v, k = feed["lam"].shape
    if k != ref.per_orbit or len(feed["visited"]) != v:
        return 1
    sizes = ref.satellite_sizes()
    bad = 0
    for j, orbit in enumerate(feed["visited"]):
        sats = int(orbit) * k + np.arange(k)
        held = sizes[sats].astype(np.float64)
        bad += plancheck.rows(ref, sats, feed["idx"][j * k:(j + 1) * k],
                              seen)
        bad += plancheck.weights(feed["lam"][j], held / held.sum())
        bad += int(not np.isclose(feed["rhos"][j], held.sum() / sizes.sum(),
                                  rtol=1e-5, atol=0))
    return bad


def replay(ref, state: dict, feed: dict) -> list:
    k = feed["lam"].shape[1]
    bases = state.setdefault("bases", {})
    for j, orbit in enumerate(feed["visited"]):
        base = bases.get(int(orbit), state["g0"])
        orbit_model = ref.train_fold([base], np.zeros(k, np.int32),
                                     feed["idx"][j * k:(j + 1) * k],
                                     feed["lam"][j])
        rho = float(feed["rhos"][j])
        state["g"] = jax.tree.map(lambda g, o: (1.0 - rho) * g + rho * o,
                                  state["g"], orbit_model)
        bases[int(orbit)] = state["g"]
        state["updates"] += 1
    return [(state["updates"], ref.accuracy(state["g"]))]
