"""Local training executor for the timeline simulator.

Satellites all train the same small model (the paper's CNN or MLP), so a
round's local training is one replica-stacked SGD burst
(``LocalTrainer.multi_step_many``): one jitted dispatch trains every
replica on its own mini-batch stream, and the mini-batch streams
themselves come from one vectorized index gather across all
participating clients (``sample_client_batches``) rather than a
per-client sampling loop.

The index-sampling half (``sample_client_indices``) is split out so the
fused executor (``repro.sim.executor``) can draw the *same* rng stream
on the host while performing the image/label gather on device, inside
the jitted round megastep.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.data.loader import FederatedData


class LocalTrainer:
    """Wraps a CNN/MLP model with jitted, replica-stacked local SGD.

    ``multi_step_many`` is every burst's one formulation: the fused
    executor's programs, ``_train_many`` and ``_train_one`` (as R=1).
    Each SGD step is one program over all R replicas, differentiating the
    model's ``loss_many`` (the sum of the replicas' losses). Where the
    replica axis lives is the model's choice: the CNN carries it in the
    channel (lane) axis of its activations, the MLP vmaps its ``loss``.
    ``multi_step`` is the one-replica burst the stacked one must match.
    """

    def __init__(self, model: Any, learning_rate: float = 0.01,
                 batch_size: int = 32):
        self.model = model
        self.lr = learning_rate
        self.batch_size = batch_size

        def descend(params, grads):
            return jax.tree.map(lambda p, g: p - learning_rate * g,
                                params, grads)

        def sgd_step(params, images, labels):
            loss, grads = jax.value_and_grad(model.loss)(
                params, images, labels)
            return descend(params, grads), loss

        def multi_step(params, images_steps, labels_steps):
            """images_steps: (n_steps, bs, ...) for ONE satellite."""
            def body(p, xy):
                return sgd_step(p, xy[0], xy[1])
            return jax.lax.scan(body, params, (images_steps, labels_steps))

        def stacked_step(stacked, images, labels):
            (_, losses), grads = jax.value_and_grad(
                model.loss_many, has_aux=True)(stacked, images, labels)
            return descend(stacked, grads), losses

        def multi_step_many(stacked, images, labels):
            """``jax.vmap(multi_step)``'s arguments and results: params
            stacked (R, ...), images (R, n_steps, bs, ...), labels
            (R, n_steps, bs) -> (params (R, ...), losses (R, n_steps))."""
            def body(p, xy):
                return stacked_step(p, xy[0], xy[1])
            new, losses = jax.lax.scan(
                body, stacked,
                (jnp.swapaxes(images, 0, 1), jnp.swapaxes(labels, 0, 1)))
            return new, losses.T

        # The un-jitted replica-stacked SGD burst is shared with the fused
        # executor, which embeds it inside its own donated megastep
        # instead of dispatching `_train_many` per round.
        self.multi_step = multi_step
        self.multi_step_many = multi_step_many

        def train_one(params, images, labels):
            new, losses = multi_step_many(
                jax.tree.map(lambda p: p[None], params), images[None],
                labels[None])
            return jax.tree.map(lambda p: p[0], new), losses[0]

        def train_many(stacked, images, labels):
            with obs.scope("train"):
                return multi_step_many(stacked, images, labels)

        def accuracy(params, images, labels):
            with obs.scope("eval"):
                return model.accuracy(params, images, labels)

        def accuracy_chunks(params, xs, ys):
            with obs.scope("eval"):
                return jax.lax.map(
                    lambda xy: model.accuracy(params, xy[0], xy[1]),
                    (xs, ys))

        self._train_one = jax.jit(train_one)
        self._train_many = jax.jit(train_many)
        self._eval = jax.jit(accuracy)
        self._eval_chunks = jax.jit(accuracy_chunks)

    def init(self, seed: int = 0):
        return self.model.init(jax.random.key(seed))

    # ------------------------------------------------------------------
    def sample_client_indices(self, fd: FederatedData,
                              clients: Sequence[int], n_steps: int,
                              rng: np.random.Generator) -> np.ndarray:
        """Global dataset indices for MANY clients' mini-batch streams.

        Keeps the per-client reference semantics — sample WITHOUT
        replacement when the shard covers the burst, with replacement
        when it doesn't — but draws every participating client at once:
        shards >= ``n_steps*bs`` take the ``need`` smallest of per-row
        uniform sort keys (a batched distinct-uniform draw in random
        order), smaller shards take floor(uniform * size) indices.
        Local indices map to global ones through the cached padded
        table. Returns ``(C, n_steps * bs)`` int64 global indices.
        """
        clients = np.asarray(clients, dtype=np.int64)
        padded, sizes = fd.padded_indices()
        need = n_steps * self.batch_size
        szs = sizes[clients]
        if (szs == 0).any():
            raise ValueError(
                f"clients {clients[szs == 0].tolist()} have empty shards")
        local = np.empty((len(clients), need), dtype=np.int64)
        small = szs < need
        if small.any():
            r = rng.random((int(small.sum()), need))
            bound = szs[small][:, None]
            local[small] = np.minimum((r * bound).astype(np.int64),
                                      bound - 1)
        if (~small).any():
            keys = rng.random((int((~small).sum()), padded.shape[1]))
            valid = np.arange(padded.shape[1])[None, :] < szs[~small][:, None]
            local[~small] = np.argsort(
                np.where(valid, keys, np.inf), axis=1)[:, :need]
        return padded[clients[:, None], local]           # (C, need) global

    def gather_selection(self, fd: FederatedData, sel: np.ndarray):
        """Gather ``(C, need)`` global indices into batch streams.

        One fancy-index op over the dataset arrays; ``sel`` may come
        from ``sample_client_indices`` or from a virtual-client plane
        (``repro.clients.plane``). Returns ``(C, n_steps, bs, ...)``.
        """
        n_clients, need = sel.shape
        n_steps = need // self.batch_size
        x = fd.images[sel].reshape(n_clients, n_steps, self.batch_size,
                                   *fd.images.shape[1:])
        y = fd.labels[sel].reshape(n_clients, n_steps, self.batch_size)
        return x, y

    def sample_client_batches(self, fd: FederatedData,
                              clients: Sequence[int], n_steps: int,
                              rng: np.random.Generator):
        """Mini-batch streams for MANY clients as ONE index gather.

        ``sample_client_indices`` draws the index table; images/labels
        are gathered in a single fancy-index op. Returns
        ``(C, n_steps, bs, ...)`` arrays.
        """
        sel = self.sample_client_indices(fd, clients, n_steps, rng)
        return self.gather_selection(fd, sel)

    def train_client(self, params, fd: FederatedData, client: int,
                     n_steps: int, rng: np.random.Generator):
        """Train ONE satellite's replica for n_steps mini-batches."""
        x, y = self.sample_client_batches(fd, [client], n_steps, rng)
        new_params, losses = self._train_one(params, jnp.asarray(x[0]),
                                             jnp.asarray(y[0]))
        return new_params, float(losses[-1])

    def train_selection(self, stacked_params, fd: FederatedData,
                        sel: np.ndarray):
        """Train MANY satellites on a resolved ``(C, need)`` index table."""
        x, y = self.gather_selection(fd, sel)
        new_params, losses = self._train_many(
            stacked_params, jnp.asarray(x), jnp.asarray(y))
        return new_params, np.asarray(losses[:, -1])

    def train_clients(self, stacked_params, fd: FederatedData,
                      clients: Sequence[int], n_steps: int,
                      rng: np.random.Generator):
        """Train MANY satellites at once (stacked leading dim)."""
        sel = self.sample_client_indices(fd, clients, n_steps, rng)
        return self.train_selection(stacked_params, fd, sel)

    def evaluate(self, params, images: np.ndarray, labels: np.ndarray,
                 batch: int = 2048) -> float:
        """Chunked accuracy with ONE device->host transfer.

        The full chunks run through a single jitted ``lax.map``
        reduction (same per-chunk accuracy math as before, bit-equal),
        the ragged tail through the scalar eval; all per-chunk means
        come back in one stacked transfer and the float64 weighted
        average happens on the host. The old path synced the device
        once per chunk via ``float()``.
        """
        n = len(images)
        n_full, rem = divmod(n, batch)
        means = []
        if n_full:
            xs = obs.upload(images[:n_full * batch]).reshape(
                n_full, batch, *images.shape[1:])
            ys = obs.upload(labels[:n_full * batch]).reshape(n_full, batch)
            means.append(self._eval_chunks(params, xs, ys))
        if rem:
            means.append(self._eval(params, obs.upload(images[-rem:]),
                                    obs.upload(labels[-rem:]))[None])
        means = obs.fetch(jnp.concatenate(means))        # ONE transfer
        lens = [batch] * n_full + ([rem] if rem else [])
        return sum(float(m) * l for m, l in zip(means, lens)) / n

    @staticmethod
    def stack(params_list: Sequence[Any]):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)

    @staticmethod
    def unstack(stacked, i: int):
        return jax.tree.map(lambda x: x[i], stacked)
