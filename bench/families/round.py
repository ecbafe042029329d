"""The synchronous round family: ``FusedExecutor.run_block``.

One call executes a block of up to K planned rounds: every satellite
trains from the global model, the Eq. 14-16 weights ``mu`` fold the
replicas into the next global, and rounds flagged ``do_eval`` end in an
eval. Strategies fedhap, fedsink and fedisl drive it.
"""
from __future__ import annotations

import numpy as np

import plancheck

METHOD = "run_block"
# Where the call takes its fold weights, and which of its arguments are
# state that it returns in the same place.
WEIGHTS = 2
STATE = (0,)


def capture(args: tuple) -> dict:
    """The call's plan tensors, as the strategy handed them over."""
    _, idx, mu, do_eval, valid = args[:5]
    return {"idx": idx, "mu": mu, "do_eval": np.asarray(do_eval, bool),
            "valid": np.asarray(valid, bool)}


def params_in(args: tuple):
    return args[0]


def params_out(out):
    return out[0]


def work(feed: dict, chips: int) -> dict:
    """Updates, replica trainings, evals and folds that the call needed.
    Each fold spreads its rows over ``chips`` chips."""
    valid = feed["valid"]
    n = int(valid.sum())
    rows = int(feed["idx"].shape[1])
    return {"updates": n, "trained": n * rows,
            "evals": int((feed["do_eval"] & valid).sum()),
            "fold_rows": [-(-rows // chips)] * n}


def plan_faults(ref, feed: dict, seen: dict) -> int:
    """Rules the call's plan breaks: every satellite's rows of each
    valid round, and that round's weights ``mu``."""
    sats = np.arange(feed["idx"].shape[1])
    return sum(plancheck.rows(ref, sats, feed["idx"][k], seen)
               + plancheck.weights(feed["mu"][k])
               for k in np.flatnonzero(feed["valid"]))


def replay(ref, state: dict, feed: dict) -> list:
    """The reference's rounds of one call. ``state['g']`` is the global
    model; returns ``[(update number, accuracy), ...]`` for the evals."""
    evals = []
    for k in np.flatnonzero(feed["valid"]):
        rows = feed["idx"].shape[1]
        state["g"] = ref.train_fold([state["g"]], np.zeros(rows, np.int32),
                                    feed["idx"][k], feed["mu"][k])
        state["updates"] += 1
        if feed["do_eval"][k]:
            evals.append((state["updates"], ref.accuracy(state["g"])))
    return evals
