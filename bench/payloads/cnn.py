"""The paper's CNN (McMahan's FL-MNIST CNN), the reference's copy.

conv5x5 -> relu -> maxpool2 -> conv5x5 -> relu -> maxpool2 -> fc -> relu
-> fc, on 28x28 images, in plain ``jax.numpy``: the configuration's
``model`` group gives its sizes and the scale of each weight's init.

FLOPs count multiply and add as two operations, for the convolutions
and the dense layers (biases, activations and pooling are left out:
under 1% of the total). A training sample costs three forward passes:
the forward, and the backward's two products per layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LEAVES = ("conv1_b", "conv1_w", "conv2_b", "conv2_w",
          "fc1_b", "fc1_w", "fc2_b", "fc2_w")


def shapes(m: dict) -> dict:
    c1, c2 = m["channels"]
    k, hid, ncls = m["kernel"], m["hidden"], m["num_classes"]
    flat = (m["image_size"] // 4) ** 2 * c2
    return {"conv1_b": (c1,), "conv1_w": (k, k, 1, c1),
            "conv2_b": (c2,), "conv2_w": (k, k, c1, c2),
            "fc1_b": (hid,), "fc1_w": (flat, hid),
            "fc2_b": (ncls,), "fc2_w": (hid, ncls)}


def init(m: dict, seed: int) -> dict:
    """The configuration's init from the seed: one key per leaf, in the
    leaves' sorted order; biases zero, weights normal times the leaf's
    scale. float32."""
    s = shapes(m)
    keys = jax.random.split(jax.random.key(seed), len(LEAVES))
    out = {}
    for name, key in zip(LEAVES, keys):
        scale = m["init_scale"].get(name)
        if scale is None:
            out[name] = jnp.zeros(s[name], jnp.float32)
        else:
            out[name] = scale * jax.random.normal(key, s[name], jnp.float32)
    return out


def forward(p: dict, images: jax.Array) -> jax.Array:
    """images (B, 28, 28) -> logits (B, classes)."""
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
    c1, c2 = m["channels"]
    k, s, hid, ncls = m["kernel"], m["image_size"], m["hidden"], \
        m["num_classes"]
    flat = (s // 4) ** 2 * c2
    return (k * k * c1 + c1 + k * k * c1 * c2 + c2 + flat * hid + hid
            + hid * ncls + ncls)


def _taps(size: int, k: int, valid_only: bool) -> int:
    """Kernel taps summed over one axis of a "SAME" convolution's
    outputs; ``valid_only`` leaves out taps that fall on the padding."""
    if not valid_only:
        return size * k
    half = k // 2
    return sum(min(size, i + half + 1) - max(0, i - half)
               for i in range(size))


def layer_flops(m: dict, valid_only: bool = False) -> dict:
    """Forward FLOPs per sample of each layer ("SAME" 5x5 convolutions,
    2x2 max pools). A convolution counts every tap of its kernel at
    every output, as the model's FLOPs are counted; ``valid_only``
    counts only taps on the image, as XLA's ``cost_analysis`` does."""
    c1, c2 = m["channels"]
    k, s, hid, ncls = m["kernel"], m["image_size"], m["hidden"], \
        m["num_classes"]
    flat = (s // 4) ** 2 * c2
    t1, t2 = _taps(s, k, valid_only), _taps(s // 2, k, valid_only)
    return {"conv1": 2 * t1 * t1 * c1 * 1,
            "conv2": 2 * t2 * t2 * c2 * c1,
            "fc1": 2 * flat * hid,
            "fc2": 2 * hid * ncls}


def forward_flops(m: dict) -> int:
    return sum(layer_flops(m).values())


def train_flops(m: dict) -> int:
    return 3 * forward_flops(m)
