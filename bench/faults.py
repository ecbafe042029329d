"""Faults planted under the timed path, as a broken program would make
them: each wraps the executor call of a cell's family. The CPU test of
the correctness check and ``calibrate.py`` on the chip plant the same
faults, so the limits are read against the faults the test keeps.

- ``unchanged``: the call hands its state on as it took it;
- ``half_batch``: the second half of each fold's replicas is left out
  and the weights renormalised over the rest;
- ``answer``: the largest leaf of the new global model (by element
  count, the first of equals in the pytree's order) moves twice as far
  as it should.
"""
from __future__ import annotations

import contextlib
import types

import jax
import jax.numpy as jnp
import numpy as np


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


def unchanged(orig, family: types.ModuleType):
    def call(self, *args, **kw):
        keep = {i: _copy(args[i]) for i in family.STATE}
        out = list(orig(self, *args, **kw))
        for i, v in keep.items():
            out[i] = v
        return tuple(out)
    return call


def half_batch(orig, family: types.ModuleType):
    def renorm(w):
        w = np.array(w, np.float64)
        w[..., (w.shape[-1] + 1) // 2:] = 0.0
        s = w.sum(axis=-1, keepdims=True)
        return np.where(s > 0, w / np.where(s > 0, s, 1.0), 0.0)

    def call(self, *args, **kw):
        args = list(args)
        args[family.WEIGHTS] = renorm(args[family.WEIGHTS])
        return orig(self, *args, **kw)
    return call


def answer(orig, family: types.ModuleType):
    def call(self, *args, **kw):
        leaves = jax.tree.leaves(args[0])
        i = max(range(len(leaves)), key=lambda j: leaves[j].size)
        base = jnp.copy(leaves[i])
        out = orig(self, *args, **kw)
        leaves, tree = jax.tree.flatten(out[0])
        leaves[i] = 2 * leaves[i] - base
        return (jax.tree.unflatten(tree, leaves),) + tuple(out[1:])
    return call


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "answer": answer}


@contextlib.contextmanager
def planted(name, family: types.ModuleType):
    """Plant fault ``name`` (None: none) in the executor's call of
    ``family`` while the block runs."""
    if name is None:
        yield
        return
    from repro.sim.executor import FusedExecutor
    orig = getattr(FusedExecutor, family.METHOD)
    setattr(FusedExecutor, family.METHOD, FAULTS[name](orig, family))
    try:
        yield
    finally:
        setattr(FusedExecutor, family.METHOD, orig)
