"""Plain reference of the simulator's execute phase: local SGD, fold, eval.

Straightforward ``jax.numpy`` with no kernel, no scan over rounds and no
sharding: each replica runs ``local_steps`` steps of mini-batch SGD on
the configuration's model from its base model, a weighted sum folds the
replicas, and the accuracy is the share of the eval labels the model
predicts right. It imports nothing of the simulator and takes nothing it
made: the weights come from the seed by the configuration's init, the
data from the reference's own copy of the generator. What it is fed are
the inputs of the calls the timed path made: which rows each replica
drew and the fold weights. ``satellite_sizes`` and ``in_partition``
restate the configuration's partition, so that the families can check
those inputs against it.

The model is ``payloads/<model.kind>.py`` and the data
``datasets/<sim.dataset>.py`` (``parts.py``). A payload gives, each of
its model sizes read from the configuration's ``model`` group ``m``:

- ``init(m, seed)``: the params, a pytree of float32 arrays, as the
  program initialises them from the seed (the same key split and leaf
  order);
- ``loss(p, x, y)``: the mean loss of one batch;
- ``hits(p, x, y)``: how many eval labels of a chunk the model predicts
  right, a host ``int``;
- ``params(m)``: the parameter count;
- ``forward_flops(m)`` and ``train_flops(m)``: model FLOPs per sample
  of a forward pass and of a training step (``counts.py``).

A dataset gives, from the configuration's ``sim`` group:

- ``make(sim, seed)``: the arrays ``(train_x, train_y, eval_x,
  eval_y)``, rows first, as the program splits the data it makes from
  the seed; floating inputs are cast to the reference's dtype, labels
  and integer inputs are not;
- ``satellite_sizes(sim, train_y)``: how many training rows each
  satellite holds, in satellite order;
- ``in_partition(sim, train_y, sats, rows)``: whether training row
  ``rows[r, j]`` lies in satellite ``sats[r]``'s partition.

``dtype="float32"`` computes at ``highest`` matmul precision, the
reference. ``dtype="bfloat16"`` is the control: the same computation a
precision step below the configuration's float32.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import parts


class Reference:
    """One configuration's reference on the given devices."""

    def __init__(self, config: dict, seed: int, *, dtype: str = "float32",
                 devices: Optional[Sequence[Any]] = None):
        sim, model = config["sim"], config["model"]
        self.sim, self.model = sim, model
        self.payload = parts.payload(model["kind"])
        self.dataset = parts.dataset(sim["dataset"])
        self.seed = seed
        self.dtype = jnp.dtype(dtype)
        self.per_orbit = int(sim["sats_per_orbit"])
        self.lr = float(sim["learning_rate"])
        self.bs = int(sim["batch_size"])
        self.steps = int(sim["local_steps"])
        self.devices = list(devices or jax.devices())
        train_x, self.train_y, self.eval_x, self.eval_y = self.dataset.make(
            sim, seed)
        self.n_train = len(self.train_y)
        train_x = train_x.astype(self._dtype_of(train_x))
        self.train_x = [jax.device_put(train_x, d) for d in self.devices]
        self.train_y_dev = [jax.device_put(self.train_y, d)
                            for d in self.devices]
        self._train = {}

    def precision(self):
        if self.dtype == jnp.float32:
            return jax.default_matmul_precision("highest")
        return contextlib.nullcontext()

    def _dtype_of(self, x: np.ndarray):
        """Inputs are cast to the reference's dtype where they are
        floating."""
        return self.dtype if np.issubdtype(x.dtype, np.floating) \
            else x.dtype

    # ------------------------------------------------------------ model
    def init(self) -> Any:
        """The configuration's init from the seed."""
        return self.cast(self.payload.init(self.model, self.seed))

    def cast(self, tree: Any) -> Any:
        return jax.tree.map(lambda v: jnp.asarray(v).astype(self.dtype),
                            tree)

    def _burst(self, n_rep: int):
        fn = self._train.get(n_rep)
        if fn is None:
            lr, steps, bs = self.lr, self.steps, self.bs
            loss = self.payload.loss

            def one(p, xs, ys):
                def step(p, xy):
                    g = jax.grad(loss)(p, *xy)
                    return jax.tree.map(
                        lambda a, b: a - jnp.asarray(lr, a.dtype) * b,
                        p, g), None
                return jax.lax.scan(step, p, (xs, ys))[0]

            def block(bases, rows, w, data_x, data_y, idx):
                bases = jax.tree.map(lambda v: v[rows], bases)
                x = data_x[idx].reshape(n_rep, steps, bs,
                                        *data_x.shape[1:])
                y = data_y[idx].reshape(n_rep, steps, bs,
                                        *data_y.shape[1:])
                trained = jax.vmap(one)(bases, x, y)
                return jax.tree.map(
                    lambda v: jnp.einsum("s,s...->...", w.astype(v.dtype), v),
                    trained)

            with self.precision():
                fn = jax.jit(block)
            self._train[n_rep] = fn
        return fn

    # ------------------------------------------------------- execution
    def train_fold(self, bases: Sequence[Any], rows_base: np.ndarray,
                   idx: np.ndarray, w: np.ndarray) -> Any:
        """``sum_r w[r] * sgd(bases[rows_base[r]], idx[r])``.

        Replicas run in one block per device, all dispatched before any
        is read, and the partial sums are added on the first device."""
        idx = np.asarray(idx, np.int32)
        rows_base = np.asarray(rows_base, np.int32)
        w = np.asarray(w, np.float32)
        n = len(idx)
        table = jax.tree.map(lambda *leaves: jnp.stack(leaves), *bases)
        nd = len(self.devices)
        per = -(-n // nd)
        partials = []
        for j, dev in enumerate(self.devices):
            sl = slice(j * per, min(n, (j + 1) * per))
            if sl.start >= n:
                break
            put = functools.partial(jax.device_put, device=dev)
            with self.precision():
                partials.append(self._burst(sl.stop - sl.start)(
                    put(table), put(rows_base[sl]), put(w[sl]),
                    self.train_x[j],
                    self.train_y_dev[j], jax.device_put(idx[sl], dev)))
        d0 = self.devices[0]
        return jax.tree.map(
            lambda *leaves: sum(jax.device_put(v, d0) for v in leaves),
            *partials)

    # -------------------------------------------------------- partition
    def satellite_sizes(self) -> np.ndarray:
        """Training samples each satellite holds, in satellite order."""
        return self.dataset.satellite_sizes(self.sim, self.train_y)

    def in_partition(self, sats: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """Whether training row ``rows[r, j]`` lies in satellite
        ``sats[r]``'s partition."""
        return self.dataset.in_partition(self.sim, self.train_y, sats, rows)

    def accuracy(self, p: Any) -> float:
        """Share of the eval labels predicted right, in chunks of 1000
        rows."""
        correct = 0
        with self.precision():
            for i in range(0, len(self.eval_x), 1000):
                x = self.eval_x[i:i + 1000]
                x = jnp.asarray(x, self._dtype_of(x))
                correct += self.payload.hits(p, x, self.eval_y[i:i + 1000])
        return correct / self.eval_y.size


def leaves(tree: Any) -> dict:
    """A pytree's leaves by their paths, keys joined with "/": a flat
    dict's leaves keep their keys, in the pytree's order."""
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", getattr(
        k, "name", k)))) for k in path): v
        for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def to_host(tree: Any) -> dict:
    return {k: np.asarray(jnp.asarray(v, jnp.float32), np.float64)
            for k, v in leaves(tree).items()}
