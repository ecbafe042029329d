"""The paper's MLP (McMahan's 2NN), the reference's copy.

784 -> 200 -> 200 -> 10 with ReLUs, on flattened 28x28 images, in plain
``jax.numpy``: the configuration's ``model`` group gives the input
width, the hidden widths, the classes and the scale of each weight's
init. Leaves ``w<i>`` (in, out) and ``b<i>`` for layer ``i``.

FLOPs count multiply and add as two operations, for the dense layers
(biases and activations are left out). A training sample costs three
forward passes: the forward, and the backward's two products per layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _dims(m: dict) -> list:
    return [m["input_dim"], *m["hidden"], m["num_classes"]]


def shapes(m: dict) -> dict:
    dims = _dims(m)
    out = {}
    for i, (a, b) in enumerate(zip(dims, dims[1:])):
        out[f"w{i}"], out[f"b{i}"] = (a, b), (b,)
    return out


def init(m: dict, seed: int) -> dict:
    """The configuration's init from the seed: one key per leaf, in the
    leaves' sorted order; biases zero, weights normal times the leaf's
    scale. float32."""
    s = shapes(m)
    names = sorted(s)
    keys = jax.random.split(jax.random.key(seed), len(names))
    out = {}
    for name, key in zip(names, keys):
        scale = m["init_scale"].get(name)
        if scale is None:
            out[name] = jnp.zeros(s[name], jnp.float32)
        else:
            out[name] = scale * jax.random.normal(key, s[name], jnp.float32)
    return out


def forward(p: dict, images: jax.Array) -> jax.Array:
    """images (B, 28, 28) -> logits (B, classes)."""
    x = images.reshape(images.shape[0], -1)
    n = len(p) // 2 - 1
    for i in range(n):
        x = jax.nn.relu(x @ p[f"w{i}"] + p[f"b{i}"])
    return x @ p[f"w{n}"] + p[f"b{n}"]


def loss(p: dict, images: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean cross-entropy of one batch."""
    logits = forward(p, images)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


_predict = jax.jit(lambda p, x: jnp.argmax(forward(p, x), axis=-1))


def hits(p: dict, images: jax.Array, labels: np.ndarray) -> int:
    """How many of the images the argmax of the logits classifies
    right."""
    return int(np.sum(np.asarray(_predict(p, images)) == labels))


def params(m: dict) -> int:
    dims = _dims(m)
    return sum(a * b + b for a, b in zip(dims, dims[1:]))


def forward_flops(m: dict) -> int:
    dims = _dims(m)
    return sum(2 * a * b for a, b in zip(dims, dims[1:]))


def train_flops(m: dict) -> int:
    return 3 * forward_flops(m)
