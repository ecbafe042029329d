"""Plain reference of the simulator's execute phase: local SGD, fold, eval.

Straightforward ``jax.numpy`` with no kernel, no scan over rounds and no
sharding: each replica runs ``local_steps`` steps of mini-batch SGD on
the paper CNN from its base model, a weighted sum folds the replicas,
and the accuracy is the share of the eval set whose argmax is the label.
It imports nothing of the simulator and takes nothing it made: the
weights come from the seed by the configuration's init, the data from
the reference's own copy of the generator (``refdata``). What it is fed
are the inputs of the calls the timed path made: which rows each
replica drew and the fold weights. ``satellite_sizes`` and
``allowed_classes`` restate the configuration's partition, so that the
families can check those inputs against it.

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

import refdata

LEAVES = ("conv1_b", "conv1_w", "conv2_b", "conv2_w",
          "fc1_b", "fc1_w", "fc2_b", "fc2_w")
# The paper's non-IID split (FedHAP, Sec. IV-A): the first ceil(0.6 L)
# of L orbits hold classes 0-5, the others classes 6-9.
CLASS_GROUPS = ((0, 1, 2, 3, 4, 5), (6, 7, 8, 9))


def cnn_shapes(m: dict) -> dict:
    c1, c2 = m["channels"]
    k, hid, ncls = m["kernel"], m["hidden"], m["num_classes"]
    flat = (m["image_size"] // 4) ** 2 * c2
    return {"conv1_b": (c1,), "conv1_w": (k, k, 1, c1),
            "conv2_b": (c2,), "conv2_w": (k, k, c1, c2),
            "fc1_b": (hid,), "fc1_w": (flat, hid),
            "fc2_b": (ncls,), "fc2_w": (hid, ncls)}


def forward(p: dict, images: jax.Array) -> jax.Array:
    """conv5x5 -> relu -> maxpool2 -> conv5x5 -> relu -> maxpool2 -> fc
    -> relu -> fc. images (B, 28, 28) -> logits (B, classes)."""
    x = images[..., None]
    for i in (1, 2):
        x = jax.lax.conv_general_dilated(
            x, p[f"conv{i}_w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(x + p[f"conv{i}_b"])
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["fc1_w"] + p["fc1_b"])
    return x @ p["fc2_w"] + p["fc2_b"]


def loss(p: dict, images: jax.Array, labels: jax.Array) -> jax.Array:
    logits = forward(p, images)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


_predict = jax.jit(lambda p, x: jnp.argmax(forward(p, x), axis=-1))


class Reference:
    """One configuration's reference on the given devices."""

    def __init__(self, config: dict, seed: int, *, dtype: str = "float32",
                 devices: Optional[Sequence[Any]] = None):
        sim, model = config["sim"], config["model"]
        self.model = model
        self.seed = seed
        self.dtype = jnp.dtype(dtype)
        self.orbits = int(sim["num_orbits"])
        self.per_orbit = int(sim["sats_per_orbit"])
        self.iid = bool(sim["iid"])
        self.lr = float(sim["learning_rate"])
        self.bs = int(sim["batch_size"])
        self.steps = int(sim["local_steps"])
        self.devices = list(devices or jax.devices())
        images, labels = refdata.make_digits_dataset(
            int(sim["num_samples"]), seed)
        n_eval = int(sim["eval_samples"])
        self.eval_x, self.eval_y = images[:n_eval], labels[:n_eval]
        self.train_labels = labels[n_eval:]
        self.train_x = [jax.device_put(images[n_eval:].astype(self.dtype), d)
                        for d in self.devices]
        self.train_y = [jax.device_put(labels[n_eval:], d)
                        for d in self.devices]
        self._train = {}

    def precision(self):
        if self.dtype == jnp.float32:
            return jax.default_matmul_precision("highest")
        return contextlib.nullcontext()

    # ------------------------------------------------------------ model
    def init(self) -> dict:
        """The configuration's init from the seed: one key per leaf, in
        the leaves' sorted order; biases zero, weights normal times the
        leaf's scale."""
        shapes = cnn_shapes(self.model)
        keys = jax.random.split(jax.random.key(self.seed), len(LEAVES))
        out = {}
        for name, key in zip(LEAVES, keys):
            scale = self.model["init_scale"].get(name)
            if scale is None:
                out[name] = jnp.zeros(shapes[name], jnp.float32)
            else:
                out[name] = scale * jax.random.normal(key, shapes[name],
                                                      jnp.float32)
        return self.cast(out)

    def cast(self, tree: dict) -> dict:
        return {k: jnp.asarray(v).astype(self.dtype) for k, v in tree.items()}

    def _burst(self, n_rep: int):
        fn = self._train.get(n_rep)
        if fn is None:
            lr, steps, bs = self.lr, self.steps, self.bs

            def one(p, xs, ys):
                def step(p, xy):
                    g = jax.grad(loss)(p, *xy)
                    return {k: p[k] - jnp.asarray(lr, p[k].dtype) * g[k]
                            for k in p}, None
                return jax.lax.scan(step, p, (xs, ys))[0]

            def block(bases, rows, w, data_x, data_y, idx):
                bases = {k: v[rows] for k, v in bases.items()}
                x = data_x[idx].reshape(n_rep, steps, bs,
                                        *data_x.shape[1:])
                y = data_y[idx].reshape(n_rep, steps, bs)
                trained = jax.vmap(one)(bases, x, y)
                return {k: jnp.einsum("s,s...->...", w.astype(v.dtype), v)
                        for k, v in trained.items()}

            with self.precision():
                fn = jax.jit(block)
            self._train[n_rep] = fn
        return fn

    # ------------------------------------------------------- execution
    def train_fold(self, bases: Sequence[dict], rows_base: np.ndarray,
                   idx: np.ndarray, w: np.ndarray) -> dict:
        """``sum_r w[r] * sgd(bases[rows_base[r]], idx[r])``.

        Replicas run in one block per device, all dispatched before any
        is read, and the partial sums are added on the first device."""
        idx = np.asarray(idx, np.int32)
        rows_base = np.asarray(rows_base, np.int32)
        w = np.asarray(w, np.float32)
        n = len(idx)
        table = {k: jnp.stack([b[k] for b in bases]) for k in LEAVES}
        nd = len(self.devices)
        per = -(-n // nd)
        parts = []
        for j, dev in enumerate(self.devices):
            sl = slice(j * per, min(n, (j + 1) * per))
            if sl.start >= n:
                break
            put = functools.partial(jax.device_put, device=dev)
            with self.precision():
                parts.append(self._burst(sl.stop - sl.start)(
                    put(table), put(rows_base[sl]), put(w[sl]),
                    self.train_x[j],
                    self.train_y[j], jax.device_put(idx[sl], dev)))
        d0 = self.devices[0]
        return {k: sum(jax.device_put(p[k], d0) for p in parts)
                for k in LEAVES}

    # -------------------------------------------------------- partition
    def satellite_sizes(self) -> np.ndarray:
        """Training samples each satellite holds, in satellite order
        (orbit-major): the configuration's split deals each group's
        samples over its satellites as evenly as they go, the larger
        parts first."""
        n_sats = self.orbits * self.per_orbit
        if self.iid:
            return _dealt(len(self.train_labels), n_sats)
        group_a = self._group_a()
        sizes = np.empty(n_sats, np.int64)
        for in_a, classes in ((True, CLASS_GROUPS[0]),
                              (False, CLASS_GROUPS[1])):
            sats = np.flatnonzero(np.repeat(group_a == in_a, self.per_orbit))
            if len(sats):
                n = int(np.isin(self.train_labels, classes).sum())
                sizes[sats] = _dealt(n, len(sats))
        return sizes

    def allowed_classes(self) -> np.ndarray:
        """``(satellites, classes)``: which labels each satellite's
        partition holds."""
        ncls = int(self.model["num_classes"])
        n_sats = self.orbits * self.per_orbit
        if self.iid:
            return np.ones((n_sats, ncls), bool)
        table = np.zeros((self.orbits, ncls), bool)
        group_a = self._group_a()
        for g, classes in enumerate(CLASS_GROUPS):
            table[np.ix_(group_a == (g == 0), classes)] = True
        return np.repeat(table, self.per_orbit, axis=0)

    def _group_a(self) -> np.ndarray:
        return np.arange(self.orbits) < max(1, int(np.ceil(0.6 * self.orbits)))

    def accuracy(self, p: dict) -> float:
        """Share of the eval set classified right, in chunks of 1000."""
        correct = 0
        with self.precision():
            for i in range(0, len(self.eval_x), 1000):
                x = jnp.asarray(self.eval_x[i:i + 1000], self.dtype)
                pred = np.asarray(_predict(p, x))
                correct += int(np.sum(pred == self.eval_y[i:i + 1000]))
        return correct / len(self.eval_x)


def _dealt(n: int, parts: int) -> np.ndarray:
    """Sizes of ``n`` items dealt into ``parts`` parts as evenly as they
    go, the larger parts first."""
    return n // parts + (np.arange(parts) < n % parts)


def to_host(tree: dict) -> dict:
    return {k: np.asarray(jnp.asarray(v, jnp.float32), np.float64)
            for k, v in tree.items()}
